"""chip_smoke.py's contract with the driver, phases stubbed.

What the driver reads is the exit code and the last line of stdout; what
it relies on is that a failure cannot hide.  The phases themselves run on
the chip (``python chip_smoke.py`` through the chip tool) and are
rehearsed by hand at tiny sizes; nothing here compiles or serves.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


class _NoStats:
    def snapshot(self):
        return {}

    def since(self, before):
        return {}


@pytest.fixture
def stubbed(monkeypatch):
    """chip_smoke with its touch points on JAX and the environment cut:
    returns the list the stub phases append their names to."""
    for var in (chip_smoke.constants.ENV_MEM_FRACTION,
                "XLA_PYTHON_CLIENT_MEM_FRACTION",
                "XLA_PYTHON_CLIENT_PREALLOCATE"):
        monkeypatch.setenv(var, "")  # restored after the test
    monkeypatch.setattr(chip_smoke, "configure_compile_cache",
                        lambda: "/nowhere")
    monkeypatch.setattr(chip_smoke, "CompileStats", _NoStats)
    monkeypatch.setattr(chip_smoke, "probe_device", lambda: dict(TPU))
    ran = []

    def phase(name):
        def run_phase(run):
            ran.append(name)
            return {"note": name}
        return name, run_phase

    monkeypatch.setattr(chip_smoke, "PHASES_ONE_CHIP",
                        [phase("first"), phase("second")])
    return ran


def _lines(capsys):
    return [ln for ln in capsys.readouterr().out.splitlines() if ln]


def test_last_line_is_exactly_the_device(stubbed, capsys):
    assert chip_smoke.main([]) == 0
    lines = _lines(capsys)
    assert stubbed == ["first", "second"]
    assert json.loads(lines[-1]) == {"ok": True, "device": TPU}
    assert [json.loads(ln)["phase"] for ln in lines[:-1]] == [
        "start", "first", "second", "done"]


def test_failing_phase_stops_the_run_and_prints_no_ok(stubbed, monkeypatch,
                                                      capsys):
    def broken(run):
        stubbed.append("broken")
        raise RuntimeError("broken phase")

    monkeypatch.setattr(chip_smoke, "PHASES_ONE_CHIP",
                        [("broken", broken)] + chip_smoke.PHASES_ONE_CHIP)
    # an uncaught exception IS the nonzero exit of the script
    with pytest.raises(RuntimeError, match="broken phase"):
        chip_smoke.main([])
    assert stubbed == ["broken"]  # nothing after the failure ran
    assert not any('"ok"' in ln for ln in _lines(capsys))


@pytest.mark.parametrize("device", [
    {"platform": "cpu", "kind": "cpu", "count": 1},
    {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},  # wrong mode
], ids=["cpu", "four_chips_in_one_chip_mode"])
def test_wrong_device_is_refused_before_any_phase(stubbed, monkeypatch,
                                                  capsys, device):
    monkeypatch.setattr(chip_smoke, "probe_device", lambda: dict(device))
    with pytest.raises(SystemExit) as refused:
        chip_smoke.main([])
    assert refused.value.code not in (0, None)
    assert stubbed == []
    assert _lines(capsys) == []


def test_script_exits_nonzero_on_cpu():
    """The real script in a real process, held to the CPU: nonzero exit,
    nothing on stdout."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr

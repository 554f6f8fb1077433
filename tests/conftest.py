"""Test harness configuration.

All tests run on CPU with an 8-device virtual mesh so multi-chip sharding
paths are exercised without TPU hardware (the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip).  Env must be set
before jax is imported anywhere, hence the top-level assignment here.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

from kubeshare_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache)

# Persistent XLA compilation cache: the suite is compile-dominated (every
# ServingEngine jits its own closures, and identical HLO recurs across
# tests and across runs), so caching compiled executables on disk cuts
# the tier-1 wall clock.  Tracing still happens per jit instance, so
# `compile_counts()`-based zero-recompile assertions are unaffected.
# JAX_COMPILATION_CACHE_DIR moves the cache; unset, tests keep their own
# directory (the one CI persists).
#
# The threshold is 0.0, the harness's value alone
# (`kubeshare_tpu/utils/compile_cache.py` leaves JAX's own to the entry
# points the chip runs): an engine's step programs are closures of the
# instance, each compiles in about 0.1 s, and their text is the same from
# engine to engine.  `tests/test_key_blocks.py` builds 20 engines of one
# tiny configuration: 382 compiles, 44 s of its 103 under the profiler, not
# one of them stored at the 0.5 s this value was.  At 0.0 the twentieth
# engine finds what the first stored: that file 92 s -> 45.6 s on an empty
# directory (358 new entries) and 29.9 s on the directory it left (ISSUE
# 46; tier-1 as a whole, CHANGES.md PR 46).
configure_compile_cache(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".jax_compilation_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "serving: continuous-batching serving engine suite (tier-1; "
        "kept fast — what a cell costs on the chip is chipbench's to "
        "measure, PERF.md)",
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the driver's tier-1 verify command "
        "(ROADMAP.md runs pytest with -m 'not slow')",
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection suite (serving/chaos.py seams; "
        "deterministic — virtual clocks, seeded faults; counts reach the "
        "metrics endpoint, ServingEngine.collect_metrics)",
    )


@pytest.fixture(scope="module", autouse=True)
def executables_let_go():
    """Every file's executables are let go with the file.  A tier-1 worker
    runs half a dozen files in one process and each loaded executable keeps
    memory maps of its own (`tests/test_chaos.py` alone leaves 19,000, the
    kernel allows a process 65,530): whichever file came late in a worker
    that had the large ones crashed where XLA loads or stores an executable
    (`tests/test_chaos.py`'s fleet warm-up, on this tree and on its parent
    alike, PR 44).  The persistent cache keeps what a later file compiles
    again cheap."""
    yield
    jax.clear_caches()


def _build_native() -> None:
    """Build the native runtime, interposer fixtures, and TSAN binaries so a
    fresh checkout runs the full isolation suite instead of silently
    skipping it (VERDICT r3 #3).  A failed build raises — the tests guarding
    the isolation runtime must never disappear quietly.  Hosts without a
    toolchain (no make/g++) keep the existing skip markers.
    """
    import shutil
    import subprocess

    if shutil.which("make") is None or shutil.which("g++") is None:
        return
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    native = os.path.join(repo, "native")
    if not os.path.isdir(native):
        return

    artifacts = [
        os.path.join(native, "build", name)
        for name in (
            "tpushare-tokend", "tpushare-pmgr", "libtpushare_client.so",
            "libtpushim.so.1", "fake_pjrt_plugin.so", "interposer_driver",
            "tpushare-tokend-tsan", "tpushare-pmgr-tsan",
        )
    ]
    sources = [os.path.join(native, "Makefile")]
    for sub in ("", "shim", "test"):
        directory = os.path.join(native, sub)
        sources += [
            os.path.join(directory, f)
            for f in os.listdir(directory)
            if f.endswith((".cc", ".h"))
        ]
    newest_source = max(os.path.getmtime(p) for p in sources)
    if all(
        os.path.exists(p) and os.path.getmtime(p) >= newest_source
        for p in artifacts
    ):
        return  # up to date: skip make (its PJRT_INC probe costs seconds)

    # -B: this check is broader than make's own prerequisites (Makefile and
    # header edits count as stale here) — an incremental make would no-op on
    # those and leave the artifacts permanently older than newest_source
    proc = subprocess.run(
        ["make", "-B", "-C", native, "all", "test-fixtures"],
        capture_output=True, text=True,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            "native build failed — the isolation-runtime tests would be "
            f"silently skipped:\n{proc.stdout}\n{proc.stderr}"
        )
    # TSAN needs the sanitizer runtime, which a make/g++ host may lack:
    # build it best-effort and warn loudly instead of killing the whole
    # session's pure-Python tests over a missing libtsan
    tsan = subprocess.run(
        ["make", "-B", "-C", native, "tsan"], capture_output=True, text=True,
    )
    if tsan.returncode != 0:
        import warnings

        warnings.warn(
            "TSAN build failed — the tokend race-detection test will be "
            f"SKIPPED:\n{tsan.stderr[-500:]}",
            stacklevel=1,
        )


_build_native()


@pytest.fixture
def chipbench_apart(monkeypatch, tmp_path):
    """For the collectors of ``chipbench/tests``, whose whole-window cases
    stand the system up (``chipbench.run.Session``: scheduler, configd,
    tokend, one pmgr a pod).  A session empties ``run.STATE_DIR`` and its
    pods listen on the scheduler's first ports, so two sessions on one
    host (two xdist workers, or ``test_e2e.py`` beside one) would take each
    other's files and ports: each test gets a state directory of its own
    and each worker a port range of its own.  What a session sets for the
    whole process (pod A's HBM share in the environment, every compile to
    the persistent cache) is put back for the tests that follow."""
    from chipbench import run
    from kubeshare_tpu import constants

    worker = int(os.environ.get("PYTEST_XDIST_WORKER", "gw0")[2:])
    monkeypatch.setattr(run, "STATE_DIR", str(tmp_path / "state"))
    monkeypatch.setattr(
        constants, "POD_MANAGER_PORT_START",
        constants.POD_MANAGER_PORT_START
        + (1 + worker) * constants.POD_MANAGER_PORT_POOL)
    environ = {var: os.environ.get(var) for var in (
        constants.ENV_MEM_FRACTION, "XLA_PYTHON_CLIENT_MEM_FRACTION",
        "XLA_PYTHON_CLIENT_PREALLOCATE")}
    config = {name: getattr(jax.config, name) for name in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_compilation_cache_max_size")}
    yield
    for var, value in environ.items():
        if value is None:
            os.environ.pop(var, None)
        else:
            os.environ[var] = value
    for name, value in config.items():
        jax.config.update(name, value)

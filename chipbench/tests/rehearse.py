"""The builder's CPU rehearsal: drives ``chipbench.run``'s whole path — native
runtime, scheduler, configd, supervisor, gated engine, traffic, reference —
at a tiny size under ``JAX_PLATFORMS=cpu``.  It is reachable through no cell
of ``BENCHMARK.json`` and prints no metric: a number from a CPU run is never
a device number.  (``shared`` reads not correct here: over a 4 s window
tokend's 10 s ledger lets pod B burst past its limit; over the cells' 50 s it
reads 0.498-0.4995.)

    JAX_PLATFORMS=cpu python3 -m chipbench.tests.rehearse \
        [rate|shared|backlog] [tiny|tiny_moe|<another twin>]
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench import run, traffic  # noqa: E402

E2E = [{"name": "ttft_tail_ms", "unit": "ms"},
       {"name": "token_gap_mean_ms", "unit": "ms"},
       {"name": "tokens_per_s", "unit": "tokens/s"},
       {"name": "cotenant_tflops", "unit": "TFLOP/s"},
       {"name": "setup_s", "unit": "s"}]


def tiny_cell(kind: str, config: str = "tiny") -> dict:
    """A cell of ``configs/<config>.json`` here (a configuration's small
    twin) under the tiny mix of that kind."""
    with open(os.path.join(HERE, "configs", f"{config}.json")) as f:
        config_file = json.load(f)
    return {"name": f"{config}.{kind}", "config": config,
            "traffic": f"tiny.{kind}", "chips": 1,
            "config_file": config_file,
            "modules": run.config_modules(config_file),
            "mix": traffic.load_mix(f"tiny.{kind}",
                                    os.path.join(HERE, "traffic")),
            "params": {"rate_rps": 6.0}, "end_to_end": E2E, "per_layer": [],
            "metric_dir": os.path.join(os.path.dirname(HERE),
                                       "layer_metrics")}


def fake_inventory():
    """One chip of a kind the cell model knows; the CPU has none."""
    import socket

    from kubeshare_tpu.cell.topology import ChipInfo

    return [ChipInfo(uuid=f"{socket.gethostname()}-tpu-0", memory=16 << 30,
                     model="TPU-v5e", index=0, coords=None)]


def rehearse(kind: str, config: str = "tiny", seed: int = 3,
             seconds: float = 4.0) -> dict:
    result = run.run_cell(tiny_cell(kind, config), seed, seconds, trace=False,
                          require_tpu=False, inventory=fake_inventory())
    return {"rehearsal": f"{config}.{kind}",
            "platform": result["device"]["platform"],
            "correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"]}


if __name__ == "__main__":
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("rehearse: set JAX_PLATFORMS=cpu; the chip's runs "
                         "go through python3 -m chipbench.run")
    print(json.dumps(rehearse(*(sys.argv[1:3] or ["rate"]))))

"""By hand, on the chip: the knee of a rate cell, then what its bounds and the
limits of ``correct`` are read from — all in one set-up.

    chiprun -- python3 -m chipbench.tools.sweep --workload scb-1b.gen.rate \
        --start 2.5 --seconds 50 --windows 11,12,13,11,12,13 --controls 3

``--start`` looks for the knee: a window at that rate, then 1.25x up while
the rate is sustained (or 1.25x down until one is).  A rate is sustained when
the requests sent and still without a first token are no more at the window's
end than at its midpoint.  ``--windows`` then opens one window per seed at
``--rate`` (default: four fifths of the knee just found, at most
``--rate-cap``; else the cell's own ``rate_rps``), each with that seed's tokens under the set-up's weights, and
prints its end-to-end statistics and what the plain reference makes of its
sample; the first ``--controls`` of them also read the controls (the
reference in fp8, and with int8 weights).  Give every window another seed:
the engine keeps the prompts of earlier windows in its prefix cache, and a
seed that comes again skips its prefill.  The weights are never swapped
under the live engine: a window served after such a swap read up to 1.5
logits off the reference on one seed where a fresh process read 0.06
(PERF.md, Findings PR 23), so only a run of its own gives a seed its own
weights.  Every line also goes to
``chiprun_out/sweep/<workload>.jsonl``; the knee to ``<workload>.knee.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import time

from chipbench import run

STEP = 1.25


def window(session, cell, seconds, rate, seed):
    """One window and what it gave; the engine is left empty."""
    session.seed = seed  # the tokens; the weights stay the set-up's
    record = session.measure(seconds, rate)
    while session.engine.step():  # the unscored requests still in flight
        pass
    session.engine.pop_finished()
    e2e = run.end_to_end(cell, record, 0.0)
    notes = e2e["notes"]
    line = {"rate_rps": rate, "seed": seed,
            "scored": len(run.scored(record)),
            "served_in_full": len(session.finished(record)),
            "ended_s": record["ended_s"],
            "metrics": {k: v["value"] for k, v in e2e["metrics"].items()
                        if k != "setup_s"},
            **{k: v for k, v in notes.items() if not k.endswith("_by_due")},
            "compiles_in_window": record["compiles_in_window"],
            "a_share": record["stat"][session.cfg["pod"]["name"]]
            ["charged_total_ms"] / (record["seconds"] * 1e3)}
    mid, end = notes["unstarted_mid_end"]
    line["sustained"] = end <= mid
    return record, line


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--start", type=float)
    parser.add_argument("--max-sweep", type=int, default=4)
    parser.add_argument("--seconds", type=float, default=50.0)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--windows", default="")
    parser.add_argument("--rate", type=float)
    parser.add_argument("--rate-cap", type=float)
    parser.add_argument("--controls", type=int, default=0)
    parser.add_argument("--kinds", default="fp8,int8")
    args = parser.parse_args(argv)
    cell = run.load_cell(args.workload)
    out_dir = os.path.join(run.REPO, "chiprun_out", "sweep")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"{args.workload}.jsonl"), "a")

    def say(**fields) -> None:
        text = json.dumps(fields)
        print(text, flush=True)
        log.write(text + "\n")
        log.flush()

    session = run.Session(cell, args.seed)
    reference = run.cell_module(cell, "reference")
    try:
        say(setup=session.timeline, setup_s=run.process_age_s())
        rate = args.rate or cell["params"].get("rate_rps")
        if args.start:
            tried, r, knee = {}, args.start, None
            for i in range(args.max_sweep):
                _, line = window(session, cell, args.seconds, r,
                                 args.seed + i)
                say(sweep=line)
                tried[r] = line["sustained"]
                if line["sustained"]:
                    knee = max(knee or 0.0, r)
                    if tried.get(round(r * STEP, 6)) is False:
                        break
                    r = round(r * STEP, 6)
                else:
                    if tried.get(round(r / STEP, 6)):
                        break
                    r = round(r / STEP, 6)
            if knee is None:
                raise SystemExit(f"no sustained rate among {sorted(tried)}")
            rate = args.rate or round(knee / STEP, 4)
            if args.rate_cap:
                rate = min(rate, args.rate_cap)
            say(knee_rps=knee, rate_rps=rate, tried=sorted(tried.items()))
            with open(os.path.join(out_dir, f"{args.workload}.knee.json"),
                      "w") as f:
                json.dump({"knee_rps": knee, "rate_rps": rate}, f)
        seeds = [int(s) for s in args.windows.split(",") if s]
        for i, seed in enumerate(seeds):
            record, line = window(session, cell, args.seconds, rate, seed)
            sample = session.sample(record)
            t0 = time.monotonic()
            line["program"] = reference.summarize([
                reference.served_gaps(session.params, session.tc,
                                      e["request"].prompt, e["result"].tokens)
                for e in sample])
            line["reference_s"] = round(time.monotonic() - t0, 1)
            line["sample_rows"] = [len(e["request"].prompt)
                                   + e["request"].max_new for e in sample]
            for kind in args.kinds.split(",") if i < args.controls else ():
                t0 = time.monotonic()
                line[f"control_{kind}"] = reference.summarize([
                    reference.control_gaps(session.params, session.tc,
                                           e["request"].prompt,
                                           e["result"].tokens, kind)
                    for e in sample])
                line[f"control_{kind}_s"] = round(time.monotonic() - t0, 1)
            say(window=line)
    finally:
        session.close()
        log.close()


if __name__ == "__main__":
    main()

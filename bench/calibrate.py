#!/usr/bin/env python3
"""Readings that set a cell's limits and its offered rate, in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1,2,...  [--control-seeds 7,8,9] [--rates 100,200,...]

Sets the cell up once, then for each ``--seeds`` seed makes that seed's
traffic, drives a window and compares the served answers with the plain
reference (the program's readings: the lower ends of the limits). For
each ``--control-seeds`` seed it drives a window too, and compares the
reference computed in bfloat16, put in the program's place, with the
float32 reference over the same sampled documents (the control's
readings: the upper ends). ``--rates`` (an open-loop cell) drives one
window per offered rate instead: the sweep that finds the knee. One JSON
object per line; runs only on a TPU, like ``run.py``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]
from bench import run  # noqa: E402
from bench.reference import answers, compare  # noqa: E402

#: a sustained rate's backlog at the window's end may exceed its backlog
#: at the first quarter by this many batches of requests (those in flight)
BACKLOG_SLACK_BATCHES = 1


def ints(text: str) -> list[int]:
    return [int(x) for x in text.split(",") if x]


def emit(row: dict) -> None:
    print(json.dumps(row), flush=True)


def readings(job, seeds, seconds, ref, low=None) -> list[dict]:
    rows = []
    for seed in seeds:
        job.make_traffic(seed, seconds)
        win = job.window(seconds)
        job.sample()
        if low is None:
            got = job.check(ref)
        else:
            served = list(answers(low, job.check_docs).items())
            got = compare(served, answers(ref, job.check_docs))
        row = {"kind": "program" if low is None else "control",
               "seed": seed, **got, "steady": win.steady,
               "metrics": win.metrics, "failed": win.failed}
        emit(row)
        rows.append(row)
    return rows


def sweep(job, rates, seconds, seed) -> None:
    slack = BACKLOG_SLACK_BATCHES * job.rows
    for rate in rates:
        job.make_traffic(seed, seconds, rate)
        win = job.window(seconds)
        st = win.steady
        sustained = (win.failed == 0 and
                     st["backlog_end"] <= st["backlog_quarter"] + slack)
        emit({"kind": "sweep", "rate_per_s": rate, "sustained": sustained,
              "failed": win.failed,
              "served_in_window_per_s": (st["requests"] - st["backlog_end"])
              / seconds, **st, **win.metrics})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--rates", type=lambda t: [float(x) for x in t.split(",")],
                    default=[])
    args = ap.parse_args(argv)
    cell = run.load_cell(run.ROOT, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"[calibrate] needs {cell.chips} TPU chips", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    first = (args.seeds + args.control_seeds + [1])[0]
    job = run.load_entry(cell.config["entry"]).setup(run.Context(
        cell=cell, seed=first, seconds=args.seconds,
        devices=devices[:cell.chips]))
    emit({"kind": "setup", "plan": job.plan_desc, "plan_s": job.plan_s,
          "setup_s": time.monotonic() - T_START})
    if args.rates:
        sweep(job, args.rates, args.seconds, first)
    ref = job.reference("float32")
    prog = readings(job, args.seeds, args.seconds, ref)
    ctrl = readings(job, args.control_seeds, args.seconds, ref,
                    low=job.reference("bfloat16"))
    job.close()
    summary = {"kind": "summary"}
    for k in ("set_diff", "score_gap"):
        if prog:
            summary[f"{k}_lower"] = max(r[k] for r in prog)
        if ctrl:
            summary[f"{k}_upper"] = min(r[k] for r in ctrl)
    emit(summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())

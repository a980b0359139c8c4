#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (its ``file``), a
traffic mix (``bench/traffic/<traffic>.json``) and the metrics it
reports; the configuration names its entry (``bench/entries/<entry>.py``)
and each per-layer metric has a reader (``bench/metrics/<metric>.py``).
So a cell, a mix or a metric is added by adding files.

One run: set-up (the deployment's dictionary from its own seed, the
plan search and prepare, the traffic from ``--seed``, a warm-up of every
shape the window uses) is timed as ``setup_s`` from the start of the
process; then the window measures for ``--seconds``; then the answers
are compared with the plain reference (``bench/reference.py``). With
``--trace 1`` the window runs under the profiler and the line carries
the per-layer metrics instead of the end-to-end ones.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``; last,
``compared``: each number compared with its limit). The last lines of
stderr repeat the compared numbers. Without a TPU, or with fewer chips
than the cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is timed from the start of the process

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = BENCH / ".work" / "jax_cache"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

# run as a script, sys.path[0] is bench/: drop it so that bench/trace.py
# cannot shadow the standard library's ``trace``
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # ... and with --trace 1
    bench_dir: Path  # where its traffic and metric readers live


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``, found by name."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {root / 'BENCHMARK.json'}"
                       f" (have {sorted(cells)})")
    w = cells[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / confs[w["config"]]["file"])
    bench_dir = root / spec["paths"][0]
    if not NAME.match(w["traffic"]):
        raise ValueError(f"bad traffic name {w['traffic']!r}")
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(name=workload, config=config, traffic=traffic,
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer,
                bench_dir=bench_dir)


def load_reader(bench_dir: Path, metric: str):
    """``read(record) -> float | None`` of ``metrics/<metric>.py``."""
    if not NAME.match(metric):
        raise ValueError(f"bad metric name {metric!r}")
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_entry(name: str):
    if not re.match(r"^[a-z_]+$", name):
        raise ValueError(f"bad entry name {name!r}")
    return importlib.import_module(f"bench.entries.{name}")


def enable_compile_cache() -> str:
    """JAX's persistent cache for this process: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``bench/.work/jax_cache``; every program is kept,
    however fast it compiled, so a warm run loads the eager path's many
    small programs too."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs this process compiled or loaded from the persistent cache
    (JAX's backend-compile event covers both), and the cache loads."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.cache_hits = 0
        self.names: list[str] = []

        def on_duration(event: str, secs: float, **kw) -> None:
            if event == self.COMPILE:
                self.compiles += 1
                self.names.append(str(kw.get("fun_name")))

        def on_event(event: str, **_kw) -> None:
            if event == self.CACHE_HIT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.cache_hits


def span(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


@dataclasses.dataclass
class Context:
    """What an entry gets: the cell, the seed and the devices it runs on."""

    cell: Cell
    seed: int
    seconds: float
    devices: list
    span: object = span


def device_report(devices) -> dict:
    """The devices as JAX reports them; the peak memory of the fullest."""
    stats = [dev.memory_stats() or {} for dev in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(max(s.get("peak_bytes_in_use", 0)
                                         for s in stats))}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float) -> dict:
    """Set-up, window and check of one cell on ``devices``; the result
    line as a dict (``compared`` last)."""
    counter = CompileCounter()
    entry = load_entry(cell.config["entry"])
    job = entry.setup(Context(cell=cell, seed=seed, seconds=seconds,
                              devices=devices))
    setup_compiles = counter.snapshot()
    log(f"plan {job.plan_desc} (plan search + prepare {job.plan_s:.3f}s)")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.monotonic() - t_start
    try:
        with span("bench.window"):
            win = job.window(seconds)
    finally:
        if trace:
            import jax

            jax.profiler.stop_trace()
    window_compiles = counter.snapshot()
    device = device_report(devices)
    compiles = {"setup": setup_compiles[0],
                "setup_cache_loads": setup_compiles[1],
                "window": window_compiles[0] - setup_compiles[0],
                "window_cache_loads": window_compiles[1] - setup_compiles[1],
                "window_programs": counter.names[setup_compiles[0]:
                                                 window_compiles[0]][:10]}
    log(f"compiles: {json.dumps(compiles)}")
    log(f"steady state: {json.dumps(win.steady)}")
    record = {"clock": {"plan_s": job.plan_s, "setup_s": setup_s},
              "counters": compiles, "trace": None, "window_s": win.elapsed_s,
              **win.record}
    breakdown = None
    if trace:
        from bench import trace as btrace

        tr = btrace.load(trace_dir)
        lo, hi = tr.window()
        record["trace"] = {"trace": tr, "lo": lo, "hi": hi,
                           "peaks": peaks_for(device["kind"], cell.bench_dir)}
        busy = btrace.busy_ns(tr, lo, hi)
        device["busy_s"] = busy / 1e9
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": btrace.top_programs(tr, lo, hi),
                     "idle_gaps": btrace.idle_gaps(tr, lo, hi)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    job.sample()
    job.close()
    compared = job.check()
    limits = cell.config["limits"]
    correct = all(compared[k] <= limits[k] for k in limits)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(cell.bench_dir, m["name"])(record)
            if v is None:
                raise LookupError(f"per-layer metric {m['name']} found "
                                  "nothing to read in this run")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s, **win.metrics}
        bad = [k for k, v in values.items() if not math.isfinite(v)]
        if bad:
            raise RuntimeError(f"{bad} not finite: too many requests failed")
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    out = {"correct": bool(correct and win.failed == 0),
           "attempted": win.attempted, "failed": win.failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {k: {"value": compared[k], "limit": limits[k]}
                       for k in limits}
    return out


def peaks_for(kind: str, bench_dir: Path) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    table = load_json(bench_dir / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"[bench] no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    cell = load_cell(ROOT, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[bench] no TPU: JAX sees {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"[bench] {args.workload} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    log(f"compile cache {enable_compile_cache()}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices[:cell.chips], T_START)
    for k, v in out["compared"].items():
        print(f"[bench] compared {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

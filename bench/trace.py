"""From a profiler trace to device metrics.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
``Trace``: per device (each ``/device:TPU:<n>`` plane) the operations it
ran (its ``XLA Ops`` line, named by HLO instruction: ``%fusion.3 = ...``)
and the programs they belong to (its ``XLA Modules`` line:
``jit_scan(<hash>)``), and the host spans whose names start with one of
``SPAN_PREFIXES`` (the harness's own ``bench.*`` spans and the program's
``eejoin.*`` stage spans), all on one clock in nanoseconds. The
reductions below read only that, so a test can build a ``Trace`` by hand
(``Trace.from_json``).

* busy time: the union of a device's operation intervals inside the
  window, averaged over the devices; the idle share is 1 - busy / window;
* kernel time: the summed durations of the operations named after the
  kernel's jitted wrapper (a Pallas call's instruction is named after
  it: ``%fused_probe_stream_pallas.1 = ... custom-call(...)``). A
  kernel that is not in the trace raises ``KernelNotFound``: no reading
  is ever a default 0;
* breakdown: the programs that took most device time, and the idle gaps
  grouped by the innermost host span open over each.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import sys

SPAN_PREFIXES = ("bench.", "eejoin.")
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class KernelNotFound(LookupError):
    """The trace holds no operation of the named kernel."""


@dataclasses.dataclass
class Trace:
    #: per device: [(name, start_ns, duration_ns)] of its operations
    devices: list[list[tuple[str, float, float]]]
    #: host spans [(name, start_ns, duration_ns)]
    spans: list[tuple[str, float, float]]
    #: per device: [(name, start_ns, duration_ns)] of its programs
    modules: list[list[tuple[str, float, float]]] = dataclasses.field(
        default_factory=list)

    def to_json(self) -> str:
        return json.dumps({"devices": self.devices, "spans": self.spans,
                           "modules": self.modules})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        rows = lambda key: [[tuple(e) for e in dev] for dev in d.get(key, [])]
        return cls(devices=rows("devices"), modules=rows("modules"),
                   spans=[tuple(s) for s in d["spans"]])

    def window(self, name: str = WINDOW_SPAN) -> tuple[float, float]:
        """``(start, end)`` of the one host span called ``name``."""
        found = [(s, s + d) for n, s, d in self.spans if n == name]
        if len(found) != 1:
            raise LookupError(f"{len(found)} spans named {name!r} in the "
                              "trace; expected one")
        return found[0]


def xplane_file(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise LookupError(f"{len(found)} .xplane.pb files under {log_dir}")
    return found[0]


def load(log_dir: str) -> Trace:
    """The ``Trace`` of the one profile written under ``log_dir``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_file(log_dir))
    devices, modules, spans = [], [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            devices.append(lines.get(OPS_LINE, []))
            modules.append(lines.get(MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIXES)]
    return Trace(devices=devices, modules=modules, spans=spans)


def _clip(events, lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Device-busy nanoseconds in ``[lo, hi]``, averaged over devices."""
    if not trace.devices:
        raise LookupError("the trace holds no device plane")
    per = [sum(b - a for a, b in _union((a, b) for _, a, b in
                                          _clip(dev, lo, hi)))
           for dev in trace.devices]
    return sum(per) / len(per)


def op_name(event_name: str) -> str:
    """``fusion.3`` of ``%fusion.3 = s32[8]{0} fusion(...)``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def program_name(event_name: str) -> str:
    """``jit_scan`` of ``jit_scan(9896877873494515665)``."""
    return event_name.split("(", 1)[0]


def kernel_ns(trace: Trace, kernel: str, lo: float, hi: float) -> float:
    """Summed device time of the operations named ``kernel`` (or
    ``kernel.<n>``), over all devices, in ``[lo, hi]``."""
    total, hits = 0.0, 0
    for dev in trace.devices:
        for name, a, b in _clip(dev, lo, hi):
            op = op_name(name)
            if op == kernel or op.startswith(kernel + "."):
                total += b - a
                hits += 1
    if not hits:
        raise KernelNotFound(f"no device operation named after {kernel!r} "
                             "in the traced window")
    return total


def top_programs(trace: Trace, lo: float, hi: float, k: int = 10):
    """``[[program, seconds]]`` of the ``k`` programs with most device
    time in ``[lo, hi]``, averaged per device."""
    by: dict[str, float] = {}
    for dev in trace.modules:
        for name, a, b in _clip(dev, lo, hi):
            name = program_name(name)
            by[name] = by.get(name, 0.0) + (b - a)
    n = max(len(trace.modules), 1)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in top]


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10):
    """``[[span, seconds]]``: device-idle time in ``[lo, hi]`` grouped by
    the innermost host span open at each gap's midpoint (``idle`` where
    none is), the ``k`` largest groups, on the first device."""
    busy = _union((a, b) for _, a, b in _clip(trace.devices[0], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [(n, s, s + d) for n, s, d in trace.spans]
    by: dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        open_ = [(e - s, n) for n, s, e in spans
                 if s <= mid <= e and n != WINDOW_SPAN]
        name = min(open_)[1] if open_ else "idle"
        by[name] = by.get(name, 0.0) + (b - a)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in top]


def dump(log_dir: str, k: int = 25) -> None:
    """Print a profile's planes and lines, and each line's ``k`` heaviest
    event names: the look by hand before code reads a trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_file(log_dir))
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            by: dict[str, list] = {}
            first = last = None
            for e in line.events:
                s = by.setdefault(e.name, [0, 0.0])
                s[0] += 1
                s[1] += e.duration_ns
                first = e.start_ns if first is None else min(first, e.start_ns)
                last = e.start_ns if last is None else max(last, e.start_ns)
            print(f"  line {line.name!r}: {sum(v[0] for v in by.values())} "
                  f"events, {first} .. {last} ns")
            for name, (n, ns) in sorted(by.items(), key=lambda kv: -kv[1][1])[:k]:
                print(f"    {ns / 1e6:12.3f} ms {n:7d}x {name[:160]}")


if __name__ == "__main__":
    dump(sys.argv[1])

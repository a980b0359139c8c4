"""Host spans in the profiler's trace.

``span(name)`` opens a ``jax.profiler.TraceAnnotation``: while a
profile is being captured (``jax.profiler.start_trace`` with
``host_tracer_level >= 1``) the span lands on the host planes of the
trace, on the device trace's clock; otherwise it costs a flag check.
Spans are opened only in eager host code, never inside a function that
``jit`` or ``lax.scan`` traces (there they would time the tracing, once).

A span's name is fixed, with no ``key=value`` arguments: a reader groups
spans by name, so an identifier in the name would give every batch a
group of its own. Sibling spans do not nest, so each one's duration is
its self time. Every name the program opens is listed in ``SPANS``.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

SPANS = (
    # serving probe stage: a micro-batch's tiles to per-side lanes
    "eejoin.serve.probe",
    # serving verify stage: lanes to merged matches on the local pool
    "eejoin.serve.verify",
    # serving verify stage behind the fabric: lanes framed and shipped
    "eejoin.serve.verify_remote",
    # one EEJoinOperator.execute_corpus call, every plan side
    "eejoin.execute_corpus",
    # a spill shard's file region through the staging buffer to the device
    "eejoin.spill.stage",
    # a spill shard's probe (shard_lane: megakernel and lane reduction)
    "eejoin.spill.probe",
    # shard or batch lanes merged into one candidate selection
    "eejoin.lanes.merge",
    # host reads of the selected flat indices, waiting on the device
    "eejoin.windows.sync",
    # the candidate windows gathered from the documents
    "eejoin.windows.gather",
    # the host-gathered candidate fields copied to the device
    "eejoin.windows.upload",
    # one side's probe + verify join dispatched (side_matches)
    "eejoin.verify",
    # the plan sides' matches merged (and tombstones masked)
    "eejoin.matches.merge",
)


def span(name: str) -> TraceAnnotation:
    """A host span called ``name`` (one of ``SPANS``), as a context
    manager."""
    return TraceAnnotation(name)

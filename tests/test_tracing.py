"""Host spans: every name the program opens is declared in
``repro.tracing.SPANS``, and a captured profile carries them."""
import ast
import glob
import os
from pathlib import Path

import jax

from repro import tracing

SRC = Path(tracing.__file__).resolve().parent


def _opened_names() -> dict[str, list[str]]:
    """``{name: [file:line, ...]}`` of every ``span("<name>")`` call in
    the program's sources."""
    found: dict[str, list[str]] = {}
    for path in sorted(SRC.rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            fname = f.id if isinstance(f, ast.Name) else getattr(f, "attr", "")
            if fname != "span" or not node.args:
                continue
            arg = node.args[0]
            assert isinstance(arg, ast.Constant) and isinstance(arg.value, str), (
                f"{path}:{node.lineno}: span names are string literals")
            found.setdefault(arg.value, []).append(
                f"{path.relative_to(SRC)}:{node.lineno}")
    return found


def test_every_opened_span_is_declared():
    opened = _opened_names()
    undeclared = {n: where for n, where in opened.items()
                  if n not in tracing.SPANS}
    assert not undeclared, undeclared


def test_every_declared_span_is_opened():
    opened = _opened_names()
    assert [n for n in tracing.SPANS if n not in opened] == []
    assert len(set(tracing.SPANS)) == len(tracing.SPANS)
    # one prefix, with no key=value arguments in the name
    assert all(n.startswith("eejoin.") and "=" not in n and " " not in n
               for n in tracing.SPANS)


def test_span_is_a_trace_annotation():
    with tracing.span("eejoin.verify") as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)


def test_corpus_spans_reach_a_captured_profile(small_corpus, tmp_path):
    """``execute_corpus`` under the profiler: its spans are on the host
    planes, the three window steps do not nest, and each shard is staged
    and probed under a span of its own."""
    from repro.core.cost_model import OBJ_JOB, SideCost
    from repro.core.eejoin import EEJoinConfig, EEJoinOperator
    from repro.core.plan import Plan, PlanSide
    from repro.extraction.sharded import MemmapCorpus

    c = small_corpus
    op = EEJoinOperator(c.dictionary, EEJoinConfig(
        gamma=0.8, max_candidates=4096, result_capacity=8192,
        use_kernel=True))
    z = SideCost(0, 0, 0, 0, 0, 0, 0, 0, 0)
    plan = Plan(6, PlanSide("index", "prefix"), PlanSide("ssjoin", "variant"),
                OBJ_JOB, 0.0, z, z, 0)
    prepared = op.prepare(plan)
    corpus = MemmapCorpus.write(str(tmp_path / "corpus"), c.doc_tokens)
    run = lambda: jax.block_until_ready(op.execute_corpus(
        prepared, corpus, shard_docs=4, tile_docs=2))
    run()  # compile outside the profile
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(os.path.join(tmp_path, "prof", "**",
                                       "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(xplane)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name in tracing.SPANS]
    names = [n for n, _, _ in spans]
    shards = -(-c.doc_tokens.shape[0] // 4)
    assert names.count("eejoin.execute_corpus") == 1
    assert names.count("eejoin.spill.stage") == 2 * shards  # per side
    assert names.count("eejoin.spill.probe") == 2 * shards
    for n in ("eejoin.windows.sync", "eejoin.windows.gather",
              "eejoin.windows.upload", "eejoin.verify"):
        assert names.count(n) == 2, n
    # each side's lanes, and the variant side's keys after its windows
    assert names.count("eejoin.lanes.merge") == 3
    assert names.count("eejoin.matches.merge") == 1
    (outer,) = [(a, b) for n, a, b in spans if n == "eejoin.execute_corpus"]
    assert all(outer[0] <= a <= b <= outer[1] for _, a, b in spans)
    steps = sorted((a, b) for n, a, b in spans
                   if n.startswith("eejoin.windows."))
    assert all(b0 <= a1 for (_, b0), (a1, _) in zip(steps, steps[1:]))

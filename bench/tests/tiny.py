"""Tiny copies of the benchmark's cells, for the CPU (Pallas in
interpret mode): the same entries, traffic kinds and metric readers, at
sizes a test run holds."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: per configuration, what a tiny copy changes
TINY = {
    "corpus-zipf16k": {"num_entities": 64, "vocab_size": 1024,
                       "stats_docs": 16, "split_docs": 32, "corpus_splits": 2,
                       "result_capacity": 1 << 14, "check_docs_per_split": 8,
                       "documents": {"doc_len": 64}},
    "screen-watchlist16k": {"num_entities": 64, "vocab_size": 1024,
                            "stats_docs": 16, "result_capacity": 4096,
                            "check_requests": 16, "queue_capacity": 64,
                            "batcher": {"max_batch_docs": 2,
                                        "buckets": [32]},
                            "documents": {"len_min": 8, "len_max": 32,
                                          "mention_share": 0.5}},
}


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(base[k], v) if isinstance(v, dict) else v
    return out


def write_tiny_root(dest: Path, rate_per_s: float = 40.0) -> Path:
    """A benchmark root at ``dest``: ``BENCHMARK.json`` with the real
    cells, tiny copies of their configurations, and the real traffic
    mixes and metric readers."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = dest / "bench"
    (bench / "configs").mkdir(parents=True)
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        (dest / c["file"]).write_text(json.dumps(merge(cfg, TINY[c["name"]])))
    shutil.copytree(BENCH / "traffic", bench / "traffic")
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    poisson = json.loads((bench / "traffic" / "poisson.json").read_text())
    poisson["rate_per_s"] = rate_per_s
    (bench / "traffic" / "poisson.json").write_text(json.dumps(poisson))
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest

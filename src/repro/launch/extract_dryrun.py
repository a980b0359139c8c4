import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The paper-representative dry-run cell (§Perf pick #3): the EE-Join
# extraction job itself, lowered + compiled on a production-scale worker
# mesh with abstract document shards (ShapeDtypeStruct), exactly like the
# LM cells. Records the same roofline JSON under results/dryrun/.
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from jax import set_mesh  # noqa: E402
from repro.core.cost_model import ALGO_INDEX, ALGO_SSJOIN, CostParams  # noqa: E402
from repro.core.eejoin import EEJoinConfig, EEJoinOperator  # noqa: E402
from repro.core.plan import PlanSide  # noqa: E402
from repro.core.cost_model import SideCost, OBJ_JOB  # noqa: E402
from repro.core.plan import Plan  # noqa: E402
from repro.data.synth import make_corpus  # noqa: E402
from repro.launch import roofline as RL  # noqa: E402
from repro.launch.dryrun import OUT_DIR, _mem_dict  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=256)
    ap.add_argument("--docs-per-worker", type=int, default=64)
    ap.add_argument("--doc-len", type=int, default=512)
    ap.add_argument("--entities", type=int, default=8192)
    ap.add_argument("--scheme", default="variant",
                    choices=("word", "prefix", "lsh", "variant"))
    ap.add_argument("--max-candidates", type=int, default=8192)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    n = args.workers
    mesh = jax.make_mesh((n,), ("workers",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    # host-side dictionary/structures are REAL (they're the broadcast
    # side); only the document stream is abstract.
    corpus = make_corpus(
        num_docs=8, doc_len=args.doc_len, vocab_size=32768,
        num_entities=args.entities, mention_dist="zipf", seed=1,
    )
    op = EEJoinOperator(
        corpus.dictionary,
        EEJoinConfig(gamma=0.8, max_candidates=args.max_candidates,
                     result_capacity=args.max_candidates),
    )
    z = SideCost(0, 0, 0, 0, 0, 0, 0, 0, 0)
    plan = Plan(0, PlanSide(ALGO_INDEX, "prefix"),
                PlanSide(ALGO_SSJOIN, args.scheme), OBJ_JOB, 0.0, z, z, 0)
    prepared = op.prepare_distributed(plan, n, CostParams(num_devices=n))
    side = prepared.sides[0]

    D = n * args.docs_per_worker
    docs = jax.ShapeDtypeStruct((D, args.doc_len), jnp.int32)
    docs_sh = NamedSharding(mesh, P("workers"))

    from repro.extraction.distributed import distributed_extract_ssjoin

    def job(doc_tokens):
        m, diag = distributed_extract_ssjoin(
            mesh, ("workers",), doc_tokens, side, prepared.max_entity_len
        )
        return m.count, diag.bytes_shuffled, diag.max_received

    t0 = time.time()
    with set_mesh(mesh):
        lowered = jax.jit(job, in_shardings=(docs_sh,)).lower(docs)
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()

    cfg = op  # roofline.model_flops not meaningful here; report terms only
    stats = RL.derive(ca, hlo, _FakeCfg(), _FakeShape(), n)
    mem = _mem_dict(compiled.memory_analysis())
    rec = {
        "arch": f"eejoin-extract-{args.scheme}",
        "shape": f"docs{D}x{args.doc_len}_E{args.entities}",
        "mesh": f"{n}workers", "chips": n, "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "memory": mem,
        "device_live_bytes": (
            mem.get("argument_size_in_bytes", 0)
            + mem.get("temp_size_in_bytes", 0)
            + mem.get("output_size_in_bytes", 0)
            - mem.get("alias_size_in_bytes", 0)
        ),
        "roofline": stats.to_dict(),
        "tag": args.tag,
    }
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    name = f"eejoin-extract__{args.scheme}{('_' + args.tag) if args.tag else ''}.json"
    (OUT_DIR / name).write_text(json.dumps(rec, indent=1))
    r = rec["roofline"]
    print(f"eejoin-extract[{args.scheme}] {n}w: compute={r['compute_s']:.4f}s "
          f"memory={r['memory_s']:.4f}s collective={r['collective_s']:.4f}s "
          f"-> {r['bottleneck']}; live={rec['device_live_bytes']/1e9:.2f}GB")
    hh = r["hlo"]
    print("  collective bytes:", {k: f"{v/1e6:.1f}MB"
                                  for k, v in hh["collective_bytes"].items()})


@dataclasses.dataclass
class _FakeCfg:
    d_model: int = 0
    num_layers: int = 0
    padded_vocab: int = 0
    num_heads: int = 1
    num_kv_heads: int = 1
    d_ff: int = 0
    head_dim: int = 1
    act: str = "gelu"
    num_experts: int = 0
    top_k: int = 0
    encoder_layers: int = 0

    @property
    def resolved_head_dim(self):
        return 1


@dataclasses.dataclass
class _FakeShape:
    mode: str = "prefill"
    global_batch: int = 1
    seq_len: int = 1


if __name__ == "__main__":
    main()

"""Distributed-extraction self-test: runs on N fake CPU devices.

Executed as a subprocess by tests/test_distributed.py (the device-count
flag must be set before jax initialises, so this cannot run inside the
main pytest process):

    python -m repro.launch.selftest_distributed [n_devices]

Prints one JSON line with pass/fail per check.
"""
import os
import sys

N_DEV = int(sys.argv[1]) if len(sys.argv) > 1 else 8
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N_DEV} "
    + os.environ.get("XLA_FLAGS", "")
)

import json  # noqa: E402

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.cost_model import CostParams  # noqa: E402
from repro.core.eejoin import EEJoinConfig, EEJoinOperator  # noqa: E402
from repro.core.plan import Plan, PlanSide  # noqa: E402
from repro.core.cost_model import OBJ_JOB  # noqa: E402
from repro.data.synth import make_corpus  # noqa: E402
from repro.extraction.oracle import oracle_extract  # noqa: E402


def forced_plan(E: int, split: int, head: PlanSide, tail: PlanSide) -> Plan:
    from repro.core.cost_model import SideCost

    z = SideCost(0, 0, 0, 0, 0, 0, 0, 0, 0)
    return Plan(split, head, tail, OBJ_JOB, 0.0, z, z, 0)


def main() -> None:
    gamma = 0.8
    checks: dict[str, bool | float] = {"n_devices": len(jax.devices())}
    c = make_corpus(
        num_docs=16, doc_len=64, vocab_size=512, num_entities=32, seed=7
    )
    docs = jnp.asarray(c.doc_tokens)
    # the extraction code is written for automatic (not explicit) mesh axes
    mesh = jax.make_mesh((N_DEV,), ("workers",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    axes = ("workers",)
    E = c.dictionary.num_entities

    truth_extra = oracle_extract(c.doc_tokens, c.dictionary, gamma, "extra")
    truth_var = oracle_extract(c.doc_tokens, c.dictionary, gamma, "variant_exact")

    op = EEJoinOperator(
        c.dictionary,
        EEJoinConfig(gamma=gamma, max_candidates=2048, result_capacity=8192),
    )

    # 1) pure distributed index plan == oracle
    plan = forced_plan(E, E, PlanSide("index", "prefix"), PlanSide("index", "prefix"))
    prepared = op.prepare_distributed(plan, N_DEV, CostParams(num_devices=N_DEV))
    with mesh:
        ms, _ = op.execute_distributed(prepared, docs, mesh, axes)
    got = set().union(*[m.to_set() for m in ms])
    checks["index_prefix_exact"] = got == truth_extra

    # 2) pure distributed ssjoin (prefix sigs) == oracle
    plan = forced_plan(E, 0, PlanSide("index", "prefix"), PlanSide("ssjoin", "prefix"))
    prepared = op.prepare_distributed(plan, N_DEV, CostParams(num_devices=N_DEV))
    with mesh:
        ms, diags = op.execute_distributed(prepared, docs, mesh, axes)
    got = set().union(*[m.to_set() for m in ms])
    checks["ssjoin_prefix_exact"] = got == truth_extra
    d = diags[0]
    checks["shuffle_bytes_positive"] = int(d.bytes_shuffled) > 0
    checks["no_send_overflow"] = int(d.send_overflow) == 0
    checks["skew_measured"] = float(d.max_received) >= float(d.mean_received)

    # 3) distributed ssjoin variant == variant oracle
    plan = forced_plan(E, 0, PlanSide("index", "prefix"), PlanSide("ssjoin", "variant"))
    prepared = op.prepare_distributed(plan, N_DEV, CostParams(num_devices=N_DEV))
    with mesh:
        ms, _ = op.execute_distributed(prepared, docs, mesh, axes)
    got = set().union(*[m.to_set() for m in ms])
    checks["ssjoin_variant_exact"] = got == truth_var

    # 4) hybrid plan: head index:variant + tail ssjoin:prefix
    split = E // 2
    plan = forced_plan(E, split, PlanSide("index", "variant"), PlanSide("ssjoin", "prefix"))
    prepared = op.prepare_distributed(plan, N_DEV, CostParams(num_devices=N_DEV))
    with mesh:
        ms, _ = op.execute_distributed(prepared, docs, mesh, axes)
    got = set().union(*[m.to_set() for m in ms])
    want = {t for t in truth_var if t[3] < split} | {
        t for t in truth_extra if t[3] >= split
    }
    checks["hybrid_exact"] = got == want

    # 5) sharded streaming driver (kernel path, 1-doc shards -> 16
    # shards over 8 devices = 2 waves, exercising the wave queue) ==
    # unsharded fused execute
    opk = EEJoinOperator(
        c.dictionary,
        EEJoinConfig(
            gamma=gamma, max_candidates=2048, result_capacity=8192, use_kernel=True
        ),
    )
    plan = forced_plan(E, 0, PlanSide("index", "prefix"), PlanSide("ssjoin", "prefix"))
    prepared = opk.prepare(plan, CostParams(num_devices=N_DEV))
    want = opk.execute(prepared, docs).to_set()
    with mesh:
        got = opk.execute_sharded(
            prepared, docs, mesh=mesh, shard_docs=1, tile_docs=1
        ).to_set()
    checks["sharded_driver_exact"] = got == want

    # 6) distributed token histogram == numpy histogram
    from repro.extraction.distributed import distributed_token_histogram

    with mesh:
        h = distributed_token_histogram(mesh, axes, docs, c.dictionary.vocab_size)
    hn = np.bincount(c.doc_tokens.reshape(-1), minlength=c.dictionary.vocab_size)
    checks["histogram_exact"] = bool((np.asarray(h) == hn).all())

    checks["ok"] = all(v for k, v in checks.items() if isinstance(v, bool))
    print(json.dumps(checks))


if __name__ == "__main__":
    main()

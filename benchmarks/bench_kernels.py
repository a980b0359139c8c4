"""Pallas-kernel microbench: interpret-mode correctness vs the pure-jnp
oracle plus wall-time of the jnp path (the kernels target TPU; interpret
mode timing is meaningless for per-kernel numbers, so we report oracle
timing + max|Δ|).

The ``fused`` section is the exception: it times the *whole*
filter→compact→signature pipeline, fused megakernel vs unfused jnp, both
jitted end-to-end on the same backend. Methodology: interpret-mode
pallas lowers the kernel body through XLA like any jnp code, so the
CPU wall-clock comparison measures the pipeline restructuring (one
streaming pass, packed survival bitmap, no [D,T,L] base materialisation,
two-stage compaction off the bitmap) rather than TPU memory-system
effects; the analytic HBM byte counts (``fused_probe.hbm_bytes_*``)
carry the device-traffic claim.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels import fused_probe as fp

from benchmarks.common import emit, timeit


def run_fused(smoke: bool = False) -> list[dict]:
    """Fused megakernel pipeline vs the unfused jnp pipeline.

    Both sides produce identical (asserted) candidate buffers and
    window signatures; rows record wall-clock and the analytic HBM
    bytes each variant moves per document scale.
    """
    from repro.core.dictionary import PAD
    from repro.core.signatures import LshParams, window_signatures
    from repro.extraction import engine as E

    rows = []
    rng = np.random.default_rng(7)
    L, NC = 8, 4096
    lshp = LshParams()
    # ~5% bit density: the regime the ISH filter targets (sparse survivors)
    w = (rng.random(((1 << 18) // 32, 32)) < 0.05).astype(np.uint32)
    bits = (w << np.arange(32, dtype=np.uint32)).sum(axis=1).astype(np.uint32)
    flt = (jnp.asarray(bits), 1 << 18, 3)
    scales = ((16, 128),) if smoke else ((64, 256), (128, 512), (256, 512))
    for D, T in scales:
        docs = jnp.asarray(rng.integers(1, 65536, size=(D, T)), jnp.int32)
        for scheme in ("prefix", "lsh", "variant"):
            params = E.ExtractParams(
                gamma=0.8, scheme=scheme, max_candidates=NC, use_kernel=True
            )

            def unfused(d):
                base, surv = E.survival_mask(d, L, flt, False)
                c = E.compact_candidates(base, surv, NC)
                s, m = window_signatures(
                    scheme, c["win_tokens"], c["win_tokens"] != PAD, 0.8, lshp
                )
                return c, s, m

            def fused(d):
                c = E.fused_filter_compact(d, L, flt, params)
                s, m = E.window_sigs_for(c, params)
                return c, s, m

            ju, jf = jax.jit(unfused), jax.jit(fused)
            cu, cf = ju(docs), jf(docs)
            assert (np.asarray(cu[1]) == np.asarray(cf[1])).all(), "sig parity"
            assert (
                np.asarray(cu[0]["win_tokens"]) == np.asarray(cf[0]["win_tokens"])
            ).all(), "candidate parity"
            tu, tf = timeit(ju, docs, iters=7), timeit(jf, docs, iters=7)
            S = {"prefix": L, "lsh": lshp.bands, "variant": 1}[scheme]
            rows.append({
                "kernel": "fused_pipeline", "shape": f"D{D}xT{T}/{scheme}",
                "unfused_s": tu, "fused_s": tf, "speedup": tu / tf,
                "hbm_bytes_unfused": fp.hbm_bytes_unfused(D, T, L, NC, S),
                # lsh=False: at these densities resolve_sig_mode picks
                # post-compaction signatures for every scheme, so the
                # model must charge the [N, S] sig store, not the dense
                # in-kernel tensor (the variant key-lane model lives in
                # the variant_adaptive rows)
                "hbm_bytes_fused": fp.hbm_bytes_fused(
                    D, T, L, NC, lshp.bands, False, sig_width=S
                ),
            })
    return rows


def run_variant_adaptive(smoke: bool = False) -> list[dict]:
    """Fused variant scheme + adaptive two-pass lane compaction.

    Two row kinds per document scale, parity asserted before timing:

    * ``variant_fused`` — the fused variant pipeline (in-kernel set-hash
      keys riding the candidate lanes) vs the unfused jnp pipeline
      (survival_mask -> compact -> window_signatures), keys asserted
      bit-identical to ``window_variant_key``.
    * ``adaptive_lanes`` — two-pass (count pass sizes the emit lanes)
      vs the fixed worst-case [G, NC] lanes: bit parity asserted, the
      measured emit width and lane bytes reported next to the HBM
      model's numbers, and the two-pass lane bytes asserted strictly
      below the fixed lane bytes at the measured density.
    """
    from repro.core.cost_model import lane_plan
    from repro.core.dictionary import PAD
    from repro.core.signatures import window_signatures
    from repro.core.variants import window_variant_key
    from repro.extraction import engine as E

    rows = []
    rng = np.random.default_rng(23)
    L, NC = 8, 4096
    # denser filter at the tiny smoke scale so the parity assertions
    # cover real survivors there too (full scales survive at 5%)
    w = (rng.random(((1 << 18) // 32, 32)) < (0.15 if smoke else 0.05))
    w = w.astype(np.uint32)
    bits = (w << np.arange(32, dtype=np.uint32)).sum(axis=1).astype(np.uint32)
    flt = (jnp.asarray(bits), 1 << 18, 3)
    scales = ((16, 128),) if smoke else ((64, 256), (128, 512), (256, 512))
    for D, T in scales:
        docs = jnp.asarray(rng.integers(1, 65536, size=(D, T)), jnp.int32)
        fixed = E.ExtractParams(gamma=0.8, scheme="variant",
                                max_candidates=NC, use_kernel=True)
        adaptive = E.ExtractParams(gamma=0.8, scheme="variant",
                                   max_candidates=NC, use_kernel=True,
                                   adaptive_lanes=True)

        def unfused(d):
            base, surv = E.survival_mask(d, L, flt, False)
            c = E.compact_candidates(base, surv, NC)
            s, m = window_signatures(
                "variant", c["win_tokens"], c["win_tokens"] != PAD, 0.8
            )
            return c, s, m

        f_unf = jax.jit(unfused)
        f_fix = jax.jit(lambda d: E.fused_filter_compact(d, L, flt, fixed))
        f_ad = lambda d: E.fused_filter_compact(d, L, flt, adaptive)
        cu, cf, ca = f_unf(docs), f_fix(docs), f_ad(docs)
        assert int(cf["n_survive"]) > 0, "parity must cover real survivors"
        # fused-vs-unfused parity: candidates, sigs, and raw key pairs
        assert (np.asarray(cu[0]["win_tokens"])
                == np.asarray(cf["win_tokens"])).all(), "candidate parity"
        assert (np.asarray(cu[1]) == np.asarray(cf["sigs"])).all(), "sig parity"
        toks = cu[0]["win_tokens"]
        k1, k2 = window_variant_key(toks, toks != PAD, xp=jnp)
        assert (np.asarray(k1) == np.asarray(cf["variant_keys"][0])).all()
        assert (np.asarray(k2) == np.asarray(cf["variant_keys"][1])).all()
        # two-pass vs one-pass bit identity
        for k in ("win_tokens", "doc", "pos", "length", "n_survive"):
            assert (np.asarray(cf[k]) == np.asarray(ca[k])).all(), (
                f"adaptive parity drift: {k}"
            )
        for a, b in zip(cf["variant_keys"], ca["variant_keys"]):
            assert (np.asarray(a) == np.asarray(b)).all(), "key parity"
        # measured lane geometry
        counts = ops.fused_probe_count(docs, flt, L, NC)
        width = fp.round_lane_width(int(np.asarray(counts).max()), NC)
        bd = fp.compact_tile_height(D, T, NC)
        G = -(-D // bd)
        lane_fixed = 2 * G * (1 + NC) * 4 + 2 * G * NC * 8
        lane_two = 2 * G * (1 + width) * 4 + 2 * G * width * 8
        assert lane_two < lane_fixed, (
            f"two-pass lanes must undercut fixed lanes (W={width}, NC={NC})"
        )
        density = float(int(cf["n_survive"])) / (D * T * L)
        plan = lane_plan(D, T, L, NC, density, variant_keys=True)
        # ~10 ms medians are noisy on small CPU hosts: use wide medians
        iters = 5 if smoke else 15
        tu = timeit(f_unf, docs, iters=iters)
        tf = timeit(f_fix, docs, iters=iters)
        ta = timeit(f_ad, docs, iters=iters)
        rows.append({
            "kernel": "variant_fused", "shape": f"D{D}xT{T}",
            "unfused_s": tu, "fused_s": tf, "speedup": tu / tf,
            "hbm_bytes_unfused": fp.hbm_bytes_unfused(D, T, L, NC, 1),
            "hbm_bytes_fused": fp.hbm_bytes_fused(
                D, T, L, NC, 4, False, sig_width=1, kernel_compact=True,
                variant_keys=True,
            ),
            "width": "", "planned_width": "", "density": "",
            "lane_bytes_fixed": "", "lane_bytes_two_pass": "",
        })
        rows.append({
            "kernel": "adaptive_lanes", "shape": f"D{D}xT{T}",
            "unfused_s": tf, "fused_s": ta, "speedup": tf / ta,
            "hbm_bytes_unfused": fp.hbm_bytes_fused(
                D, T, L, NC, 4, False, sig_width=1, kernel_compact=True,
                variant_keys=True,
            ),
            "hbm_bytes_fused": fp.hbm_bytes_fused(
                D, T, L, NC, 4, False, sig_width=1, kernel_compact=True,
                lane_width=width, two_pass=True, variant_keys=True,
            ),
            "width": width, "planned_width": plan["width"],
            "density": density,
            "lane_bytes_fixed": lane_fixed, "lane_bytes_two_pass": lane_two,
        })
    return rows


def run_variant_calibration() -> list[dict]:
    """Recalibrate c_sig_per_window["variant"] against the fused path.

    Builds a small synthetic corpus, runs ``core.calibrate`` with a
    ``use_kernel=True`` operator (so the ssjoin timing exercises the
    fused variant pipeline end to end), and reports the before/after
    signature constants, the measured lane density, and whether the §5
    plan choice flips under the recalibrated constants.
    """
    from repro.core.calibrate import calibrate, measured_lane_density
    from repro.core.cost_model import CostParams
    from repro.core.eejoin import EEJoinConfig, EEJoinOperator
    from repro.data.synth import make_corpus

    c = make_corpus(num_docs=24, doc_len=96, vocab_size=1024,
                    num_entities=48, seed=3)
    op = EEJoinOperator(
        c.dictionary,
        EEJoinConfig(gamma=0.8, max_candidates=4096, result_capacity=8192,
                     use_kernel=True),
    )
    before = CostParams(num_devices=1)
    after = calibrate(op, np.asarray(c.doc_tokens), before, scheme="variant")
    stats = op.gather_statistics(np.asarray(c.doc_tokens),
                                 total_docs=len(c.doc_tokens))
    plan_before = op.choose_plan(stats, before)
    plan_after = op.choose_plan(stats, after)
    fmt = lambda p: (f"{p.head.algo}:{p.head.scheme}@{p.split}/"
                     f"{p.tail.algo}:{p.tail.scheme}")
    return [{
        "kernel": "variant_calibration", "shape": "D24xT96",
        "c_sig_variant_before": before.sig_cost("variant"),
        "c_sig_variant_after": after.sig_cost("variant"),
        "lane_density": measured_lane_density(stats),
        "plan_before": fmt(plan_before), "plan_after": fmt(plan_after),
        "plan_flipped": fmt(plan_before) != fmt(plan_after),
    }]


def run_sharded(smoke: bool = False) -> list[dict]:
    """Sharded streaming driver + in-kernel compaction epilogue.

    Two comparisons per document scale, parity asserted field-for-field
    before any timing (CI fails on drift):

    * ``compact`` rows: the fused single-call pipeline with the
      in-kernel compaction epilogue vs the legacy XLA bitmap compaction
      (``kernel_compact=False``) — the "last full-bitmap pass" the
      epilogue removes, with the modeled HBM bytes for both.
    * ``driver`` rows: the sharded streaming driver (shards + double-
      buffered tile stream + lane merge) vs the unsharded fused call.
    """
    from repro.extraction import engine as E
    from repro.extraction import sharded as SH

    rows = []
    rng = np.random.default_rng(11)
    L, NC = 8, 4096
    w = (rng.random(((1 << 18) // 32, 32)) < 0.05).astype(np.uint32)
    bits = (w << np.arange(32, dtype=np.uint32)).sum(axis=1).astype(np.uint32)
    flt = (jnp.asarray(bits), 1 << 18, 3)
    scales = (
        ((16, 128, 4, 2),)
        if smoke
        else ((64, 256, 16, 8), (128, 512, 32, 8), (256, 512, 32, 16))
    )
    for D, T, shard_docs, tile_docs in scales:
        docs = jnp.asarray(rng.integers(1, 65536, size=(D, T)), jnp.int32)
        epi = E.ExtractParams(gamma=0.8, scheme="prefix", max_candidates=NC,
                              use_kernel=True)
        xla = E.ExtractParams(gamma=0.8, scheme="prefix", max_candidates=NC,
                              use_kernel=True, kernel_compact=False)

        f_epi = jax.jit(lambda d: E.fused_filter_compact(d, L, flt, epi))
        f_xla = jax.jit(lambda d: E.fused_filter_compact(d, L, flt, xla))
        f_drv = lambda d: SH.sharded_filter_compact(
            d, L, flt, epi, shard_docs=shard_docs, tile_docs=tile_docs
        )
        c_epi, c_xla, c_drv = f_epi(docs), f_xla(docs), f_drv(docs)
        for name, c in (("xla-compact", c_xla), ("sharded-driver", c_drv)):
            for k in ("win_tokens", "doc", "pos", "length", "n_survive"):
                assert (np.asarray(c_epi[k]) == np.asarray(c[k])).all(), (
                    f"parity drift: {name}/{k}"
                )
        t_epi, t_xla = timeit(f_epi, docs), timeit(f_xla, docs)
        t_drv = timeit(f_drv, docs)
        rows.append({
            "kernel": "compact_epilogue", "shape": f"D{D}xT{T}",
            "baseline": "xla-compact", "baseline_s": t_xla,
            "variant": "epilogue", "variant_s": t_epi,
            "speedup": t_xla / t_epi,
            "hbm_bytes_baseline": fp.hbm_bytes_fused(D, T, L, NC, 4, False,
                                                     sig_width=L),
            "hbm_bytes_variant": fp.hbm_bytes_fused(D, T, L, NC, 4, False,
                                                    sig_width=L,
                                                    kernel_compact=True),
            "shards": "", "tiles_per_shard": "",
        })
        rows.append({
            "kernel": "sharded_driver",
            "shape": f"D{D}xT{T}/s{shard_docs}t{tile_docs}",
            "baseline": "unsharded", "baseline_s": t_epi,
            "variant": "sharded-stream", "variant_s": t_drv,
            "speedup": t_epi / t_drv,
            "hbm_bytes_baseline": "", "hbm_bytes_variant": "",
            "shards": -(-D // shard_docs),
            "tiles_per_shard": -(-shard_docs // tile_docs),
        })
    return rows


def run() -> list[dict]:
    rows = []
    rng = np.random.default_rng(0)

    # ---- jaccard_verify: [N, K] pair verification
    for N, K, L in ((256, 8, 8), (1024, 16, 8)):
        V = 4096
        win = jnp.asarray(rng.integers(0, V, size=(N, L)), jnp.int32)
        ent = jnp.asarray(rng.integers(0, V, size=(N, K, L)), jnp.int32)
        w = jnp.asarray(rng.random(V), jnp.float32)
        win_w = w[win]
        ent_w = w[ent] * (ent != 0)
        for mode in ("extra", "missing"):
            got = np.asarray(ops.jaccard_scores(win, win_w, ent, ent_w, mode))
            want = np.asarray(ref.jaccard_verify_ref(win, win_w, ent, ent_w, mode))
            t = timeit(jax.jit(
                lambda a, b, c, d: ref.jaccard_verify_ref(a, b, c, d, mode)
            ), win, win_w, ent, ent_w)
            rows.append({
                "kernel": "jaccard_verify", "shape": f"N{N}xK{K}xL{L}/{mode}",
                "max_abs_err": float(np.abs(got - want).max()),
                "oracle_jit_s": t,
            })

    # ---- minhash: banded signatures
    for N, L in ((512, 8), (2048, 16)):
        toks = jnp.asarray(rng.integers(1, 1 << 20, size=(N, L)), jnp.int32)
        valid = jnp.asarray(rng.random((N, L)) < 0.8)
        got = np.asarray(ops.minhash(toks, valid, bands=4, rows=2))
        want = np.asarray(ref.minhash_ref(toks, valid, bands=4, rows=2))
        t = timeit(jax.jit(lambda a, b: ref.minhash_ref(a, b, 4, 2)), toks, valid)
        rows.append({
            "kernel": "minhash", "shape": f"N{N}xL{L}",
            "max_abs_err": float((got != want).sum()),  # exact-match count
            "oracle_jit_s": t,
        })

    # ---- window_filter: fused Bloom probe over all windows
    for D, T in ((4, 128), (8, 256)):
        docs = jnp.asarray(rng.integers(1, 4096, size=(D, T)), jnp.int32)
        bits = jnp.asarray(rng.integers(0, 2, size=(1 << 14,)), jnp.uint8)
        got = np.asarray(ops.window_filter(docs, bits, 1 << 14, 3, 6))
        want = np.asarray(ref.window_filter_ref(docs, bits, 1 << 14, 3, 6))
        t = timeit(jax.jit(
            lambda a, b: ref.window_filter_ref(a, b, 1 << 14, 3, 6)), docs, bits)
        rows.append({
            "kernel": "window_filter", "shape": f"D{D}xT{T}",
            "max_abs_err": float((got != want).sum()),
            "oracle_jit_s": t,
        })
    return rows


def main(smoke: bool = False) -> None:
    # smoke rows go to a separate artifact so CI never clobbers the
    # published full-scale kernels_fused.json / sharded.json evidence
    emit("kernels_smoke" if smoke else "kernels_fused", run_fused(smoke=smoke))
    emit("sharded_smoke" if smoke else "sharded", run_sharded(smoke=smoke))
    # variant-scheme + adaptive-lane leg: fused variant pipeline parity
    # and the two-pass lane model vs measured lane bytes (CI smoke runs
    # the small scale; the full run adds the calibration study)
    emit("variant_smoke" if smoke else "variant_adaptive",
         run_variant_adaptive(smoke=smoke))
    if not smoke:
        emit("variant_calibration", run_variant_calibration())
        emit("kernels", run())


if __name__ == "__main__":
    main()

"""Pallas TPU kernels for the extraction hot path (jnp oracles in ref.py).

Single-pass streaming architecture
----------------------------------
``fused_probe`` is the map-side front end: ONE ``pallas_call`` streams
each [Bd, T] document tile HBM->VMEM, keeps the Bloom bitmap
VMEM-resident, and emits (a) the window-survival mask *packed* as a
[D, T] uint32 bitmap (bit l = survive(pos, len=l+1)) and (b), in dense
regimes, per-window MinHash band signatures — all as running
and/or/min recurrences over the in-register token stream. Downstream,
``extraction.engine.fused_filter_compact`` compacts candidates straight
off the packed bitmap and gathers their tokens from the [D, T] array;
the L-times-expanded [D, T, L] window base of the unfused pipeline is
never materialised.

HBM-traffic accounting (per token; L = max window length, K = Bloom
hashes, B = LSH bands): the unfused pipeline moves ~4 + 8L + 2L bytes
(docs read, int32 base write+re-read, int8 mask write+re-read) while the
fused pass moves 4 + 8 bytes (+4LB when emitting signatures in-kernel) —
see ``fused_probe.hbm_bytes_unfused`` / ``hbm_bytes_fused``, reported by
``benchmarks/bench_kernels.py``. Each token is also hashed K times
instead of K*L.

Standalone kernels (pre-fusion stages, kept for comparison + fallback):
``window_filter`` (survival mask only, [D,T,L] int8 output),
``minhash`` (banded signatures over compacted windows),
``jaccard_verify`` (weighted-containment verification).

``ops.py`` is the dispatch layer the engine calls with
``use_kernel=True``: kernels compile for the TPU there, run in
interpret mode on the CPU backend (the test path; the kernel body
lowers through XLA), and refuse any other backend.
``tests/test_tpu_compile.py`` compiles the main-path kernels for a
described v5e chip.
"""

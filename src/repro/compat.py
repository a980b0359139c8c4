"""JAX helpers the installed release (JAX 0.9) lacks.

``optimization_barrier`` adds the differentiation rule the primitive
lacks. ``shard_map``, ``set_mesh`` and ``axis_size`` are imported from
``jax`` where they are used.
"""
from __future__ import annotations


def _make_barrier():
    # module-scope one-time custom_jvp registration (a per-call wrapper
    # would defeat jax's function-identity caches and re-register on
    # every retrace of a scanned layer body)
    import jax

    @jax.custom_jvp
    def _barrier(v):
        return jax.lax.optimization_barrier(v)

    @_barrier.defjvp
    def _barrier_jvp(primals, tangents):
        (v,), (t,) = primals, tangents
        return jax.lax.optimization_barrier(v), t

    return _barrier


_BARRIER = _make_barrier()


def optimization_barrier(x):
    """Differentiable ``jax.lax.optimization_barrier``.

    Older jax ships the primitive without a differentiation rule, which
    breaks ``grad`` through remat'd scan bodies that use the barrier as a
    scheduling hint. The hint never changes values, so the JVP barriers
    the primal and passes the tangent through untouched (linear, hence
    transposable for reverse mode).
    """
    return _BARRIER(x)


__all__ = ["optimization_barrier"]

"""jit'd public wrapper: fused DPPF consensus over worker-stacked pytrees.

``pullpush_fused(stacked, alpha, lam)`` mirrors
``repro.core.pullpush.pullpush`` but routes the math through the flat
ConsensusEngine (one ``fused_round`` Pallas call, or the Gram+GEMM jnp
path with ``use_kernel=False``).

This is the convenience entry point for a one-off call on a pytree — it
flattens per call. The training hot path does NOT go through here: the
trainer holds the engine's persistent flat view and calls
``consensus.apply_round(..., engine=...)`` directly, so the flatten happens
once per run (DESIGN.md §Consensus-engine).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.engine import ConsensusEngine


@functools.partial(jax.jit,
                   static_argnames=("eps", "interpret", "use_kernel"))
def pullpush_fused(stacked, alpha, lam, *, eps=1e-12, interpret=None,
                   use_kernel=True):
    """Eq. 5 over a worker-stacked pytree via the consensus engine.
    Returns (new_stacked, per-worker distances).

    The jnp branch uses the engine's exact gap-space stages (this wrapper
    flattens per call anyway, so the fast path's persistent-buffer economy
    doesn't apply — keep plain Eq. 5 semantics at every scale)."""
    engine = ConsensusEngine.from_stacked(
        stacked, use_kernel=use_kernel, interpret=interpret, eps=eps,
        precise=True)
    flat = engine.flatten(stacked)
    M = engine.layout.M
    T = jnp.broadcast_to(engine.uniform, (M, M))
    alpha = jnp.broadcast_to(jnp.asarray(alpha, jnp.float32), (M,))
    lam = jnp.broadcast_to(jnp.asarray(lam, jnp.float32), (M,))
    new, r, _, _ = engine.stage(flat, T, alpha, -lam)
    return engine.unflatten(new), r

"""Mixture-of-Experts MLP: top-k capacity routing with dispatch/combine
einsums (Switch/Mesh-TF style — the GSPMD-friendly formulation: the expert
dimension shards over the "model" axis and XLA inserts the all-to-all).

Supports llama4-scout (16e top-1 + shared expert) and dbrx (16e top-4).
Aux load-balance loss follows Switch Transformer: E * sum(importance * load).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import act_fn, dense_init, init_mlp, mlp


def _constrain(x, spec_axes):
    """Pin the routing tensors' expert dim to the ambient mesh's model axis
    (if one is active) so GSPMD keeps them expert-sharded instead of
    all-reducing the full (T, E, C) tensor across the TP group — found to be
    the dominant collective in the train_4k dry-run (§Perf iteration 2).
    No-op unless ``jax.set_mesh`` has set a mesh with a 'model' axis (CPU
    tests)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return x
    from jax.sharding import PartitionSpec
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*spec_axes))


def init_moe(key, cfg, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),  # router in fp32
        "w_gate": dense_init(ks[1], (e, d, f), dtype, fan_in=d),
        "w_up": dense_init(ks[2], (e, d, f), dtype, fan_in=d),
        "w_down": dense_init(ks[3], (e, f, d), dtype, fan_in=f),
    }
    if cfg.shared_expert:
        p["shared"] = init_mlp(ks[4], d, f, dtype)
    return p


def _capacity(tokens_per_group: int, cfg) -> int:
    c = int(tokens_per_group * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(c, cfg.top_k)


def moe_mlp(p, x, cfg):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar).

    Tokens are grouped per batch row (decode: one group over the batch) so
    the dispatch tensor stays (Tg, E, C)-sized.
    """
    B, S, D = x.shape
    if S == 1:  # decode: group over batch
        xg = x.reshape(1, B, D)
    else:
        xg = x
    G, Tg, _ = xg.shape
    E, K = cfg.n_experts, cfg.top_k
    C = _capacity(Tg, cfg)

    logits = (xg.astype(jnp.float32) @ p["router"])            # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, K)                        # (G, Tg, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    oh_e = jax.nn.one_hot(ids, E, dtype=jnp.float32)            # (G, Tg, K, E)
    # position of each (token, k) entry within its expert queue, token-major
    flat = oh_e.reshape(G, Tg * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat                       # (G, Tg*K, E)
    pos_own = jnp.sum(pos * flat, axis=-1).reshape(G, Tg, K).astype(jnp.int32)
    keep = (pos_own < C).astype(jnp.float32)
    oh_c = jax.nn.one_hot(pos_own, C, dtype=jnp.float32)        # (G, Tg, K, C)

    combine = jnp.einsum("gtke,gtkc->gtec",
                         oh_e * (gates * keep)[..., None], oh_c)  # (G, Tg, E, C)
    combine = _constrain(combine, (None, None, "model", None))
    dispatch = (combine > 0).astype(xg.dtype)

    ein = jnp.einsum("gtec,gtd->gecd", dispatch, xg)            # (G, E, C, D)
    ein = _constrain(ein, (None, "model", None, None))
    h = jnp.einsum("gecd,edf->gecf", ein, p["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", ein, p["w_up"])
    h = act_fn(cfg.act)(h) * u
    eout = jnp.einsum("gecf,efd->gecd", h, p["w_down"])         # (G, E, C, D)
    eout = _constrain(eout, (None, "model", None, None))
    # combine contraction dtype: bf16 halves the dispatch/combine collective
    # payload on the expert-parallel axis (§Perf); accumulate in fp32.
    cdt = jnp.dtype(cfg.moe_combine_dtype)
    out = jnp.einsum("gecd,gtec->gtd", eout.astype(cdt), combine.astype(cdt),
                     preferred_element_type=jnp.float32).astype(x.dtype)

    # Switch aux loss: E * sum_e importance_e * load_e
    importance = probs.mean(axis=(0, 1))                        # (E,)
    load = oh_e[:, :, 0, :].mean(axis=(0, 1))                   # first-choice
    aux = E * jnp.sum(importance * load)

    out = out.reshape(B, S, D)
    if cfg.shared_expert:
        out = out + mlp(p["shared"], x, cfg.act)
    return out, aux

"""Decoder-only LM assembly, config-driven over the block pattern.

Layers with identical parameter structure are stacked and executed with
``lax.scan`` (per-layer window sizes ride along as a scanned array), so an
80-layer config lowers to a compact HLO. Heterogeneous patterns (zamba2's
mamba+shared-attn, xlstm's mlstm+slstm) are executed as a scan over pattern
*cycles* with the pattern unrolled inside the body; shared blocks close over
a single parameter set but keep per-occurrence KV caches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import embed_init, init_mlp, mlp, rms_norm, softcap


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------

def init_attn_block(key, cfg, dtype):
    ks = jax.random.split(key, 2)
    d = cfg.d_model
    p = {
        "ln1": jnp.zeros((d,), dtype),
        "attn": attn.init_attention(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, dtype, cfg.qkv_bias),
        "ln2": jnp.zeros((d,), dtype),
    }
    if cfg.d_ff:
        p["mlp"] = init_mlp(ks[1], d, cfg.d_ff, dtype)
    if cfg.post_block_norm:
        p["post1"] = jnp.zeros((d,), dtype)
        p["post2"] = jnp.zeros((d,), dtype)
    return p


def init_moe_block(key, cfg, dtype):
    ks = jax.random.split(key, 2)
    d = cfg.d_model
    return {
        "ln1": jnp.zeros((d,), dtype),
        "attn": attn.init_attention(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, dtype, cfg.qkv_bias),
        "ln2": jnp.zeros((d,), dtype),
        "moe": moe_lib.init_moe(ks[1], cfg, dtype),
    }


def _self_attention(p, h, cfg, window, cache, index):
    """Shared attention plumbing. Returns (attn output, new cache)."""
    B, S, _ = h.shape
    q, k, v = attn.qkv_proj(p, h, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
    if cache is None:
        pos = jnp.arange(S, dtype=jnp.int32)
        q = attn.rope(q, pos, cfg.rope_theta)
        k = attn.rope(k, pos, cfg.rope_theta)
        o = attn.attend(q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                        window=window, cap=cfg.attn_logit_softcap)
        return attn.out_proj(p, o), None
    pos = index + jnp.arange(S, dtype=jnp.int32)
    q = attn.rope(q, pos, cfg.rope_theta)
    k = attn.rope(k, pos, cfg.rope_theta)
    cache = attn.cache_update(cache, k, v, index)
    o = attn.attend(q, cache["k"], cache["v"], q_pos=pos, kv_pos=cache["pos"],
                    causal=True, window=window, cap=cfg.attn_logit_softcap)
    return attn.out_proj(p, o), cache


def attn_block(p, x, cfg, window=None, cache=None, index=0):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    o, cache = _self_attention(p["attn"], h, cfg, window, cache, index)
    if "post1" in p:
        o = rms_norm(o, p["post1"], cfg.norm_eps)
    x = x + o
    if "mlp" in p:
        m = mlp(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg.act)
        if "post2" in p:
            m = rms_norm(m, p["post2"], cfg.norm_eps)
        x = x + m
    return x, cache, jnp.float32(0.0)


def moe_block(p, x, cfg, window=None, cache=None, index=0):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    o, cache = _self_attention(p["attn"], h, cfg, window, cache, index)
    x = x + o
    m, aux = moe_lib.moe_mlp(p["moe"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
    return x + m, cache, aux


def _seq_constrain(x, cfg):
    """Sequence-parallel activations (§Perf seqshard plan): pin the residual
    stream's sequence dim to the model axis between blocks, so norms and
    element-wise ops run on S/TP tokens and the TP all-reduces lower to
    reduce-scatter + all-gather pairs. No-op unless ``jax.set_mesh`` has
    set a mesh with a model axis."""
    if not cfg.seq_shard_acts or x.ndim != 3:
        return x
    mesh = jax.sharding.get_abstract_mesh()     # the one set by jax.set_mesh
    if mesh.empty or "model" not in mesh.axis_names:
        return x
    if x.shape[1] % mesh.shape["model"]:
        return x
    from jax.sharding import PartitionSpec
    return jax.lax.with_sharding_constraint(
        x, PartitionSpec(None, "model", None))


def _apply_block(kind, p, x, cfg, window, state, index):
    """Dispatch. Returns (x, new_state, aux). With cfg.remat the block body
    is rematerialized in the backward pass (activation checkpointing)."""
    x = _seq_constrain(x, cfg)
    if cfg.remat and state is None:
        fn = jax.checkpoint(
            lambda pp, xx, ww: _apply_block_inner(kind, pp, xx, cfg, ww,
                                                  None, index))
        return fn(p, x, window if window is not None else 0)
    return _apply_block_inner(kind, p, x, cfg, window, state, index)


def _apply_block_inner(kind, p, x, cfg, window, state, index):
    if kind in ("attn", "shared_attn"):
        return attn_block(p, x, cfg, window=window, cache=state, index=index)
    if kind == "moe":
        return moe_block(p, x, cfg, window=window, cache=state, index=index)
    if kind == "mamba":
        out, st = ssm_lib.mamba_forward(p, x, cfg, state)
        return x + out, st, jnp.float32(0.0)
    if kind == "mlstm":
        out, st = xlstm_lib.mlstm_forward(p, x, cfg, state)
        return x + out, st, jnp.float32(0.0)
    if kind == "slstm":
        out, st = xlstm_lib.slstm_forward(p, x, cfg, state)
        return x + out, st, jnp.float32(0.0)
    raise ValueError(kind)


_INIT = {
    "attn": init_attn_block,
    "shared_attn": init_attn_block,
    "moe": init_moe_block,
    "mamba": ssm_lib.init_mamba,
    "mlstm": xlstm_lib.init_mlstm,
    "slstm": xlstm_lib.init_slstm,
}


def _block_state(kind, cfg, batch, buf_len, dtype):
    """Fresh decode/prefill state for one block."""
    if kind in ("attn", "shared_attn", "moe"):
        return attn.init_cache(batch, cfg.n_kv_heads, buf_len, cfg.head_dim, dtype)
    if kind == "mamba":
        return ssm_lib.init_mamba_state(cfg, batch, dtype)
    if kind in ("mlstm", "slstm"):
        d_in, H, P = xlstm_lib.dims(cfg)
        if kind == "mlstm":
            return (jnp.zeros((batch, H, P, P), jnp.float32),
                    jnp.zeros((batch, H, P), jnp.float32),
                    jnp.full((batch, H), -1e30, jnp.float32))
        zero = jnp.zeros((batch, H, P), jnp.float32)
        return (zero, zero + 1e-6, zero, zero - 1e30)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Pattern machinery
# ---------------------------------------------------------------------------

def _merged_pattern(cfg):
    """Pattern positions as (kind, window); local_attn folds into attn."""
    out = []
    for k in cfg.layer_pattern:
        if k == "local_attn":
            out.append(("attn", cfg.sliding_window))
        else:
            out.append((k, 0))
    return out


def _layout(cfg):
    """Decide the execution layout.

    uniform: all pattern positions share one structure -> one scan of L.
    cycle:   scan over full pattern cycles + unrolled remainder.
    """
    pat = _merged_pattern(cfg)
    kinds = {k for k, _ in pat}
    if kinds <= {"attn"} or kinds == {"moe"}:
        return "uniform"
    return "cycle"


def _windows(cfg):
    pat = _merged_pattern(cfg)
    return jnp.asarray([pat[i % len(pat)][1] for i in range(cfg.n_layers)],
                       jnp.int32)


def init_blocks(cfg, key, dtype):
    """Returns a pure array pytree; layout metadata is derived from cfg."""
    pat = _merged_pattern(cfg)
    L = cfg.n_layers
    if _layout(cfg) == "uniform":
        kind = pat[0][0]
        keys = jax.random.split(key, L)
        stacked = jax.vmap(lambda k: _INIT[kind](k, cfg, dtype))(keys)
        return {"stack": stacked}
    # cycle layout
    p_len = len(pat)
    n_cycles, rem = divmod(L, p_len)
    params = {}
    keys = iter(jax.random.split(key, (n_cycles + 2) * p_len + 1))
    cyc = {}
    for j, (kind, _) in enumerate(pat):
        if kind == "shared_attn":
            continue  # weights shared, init once below
        ks = jnp.stack([jax.random.fold_in(next(keys), c) for c in range(n_cycles)])
        cyc[f"b{j}"] = jax.vmap(lambda k: _INIT[kind](k, cfg, dtype))(ks)
    params["cycle"] = cyc
    if any(k == "shared_attn" for k, _ in pat):
        params["shared"] = _INIT["shared_attn"](next(keys), cfg, dtype)
    if rem:
        rem_p = {}
        for j in range(rem):
            kind = pat[j][0]
            if kind == "shared_attn":
                continue
            rem_p[f"b{j}"] = _INIT[kind](next(keys), cfg, dtype)
        params["remainder"] = rem_p
    return params


def init_states(cfg, blocks, batch, buf_len, dtype):
    """Fresh stacked states matching ``run_blocks`` expectations."""
    del blocks
    pat = _merged_pattern(cfg)
    if _layout(cfg) == "uniform":
        one = _block_state(pat[0][0], cfg, batch, buf_len, dtype)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape),
                            one)
    n_cycles, rem = divmod(cfg.n_layers, len(pat))
    st = {"cycle": {}, "remainder": {}}
    for j, (kind, _) in enumerate(pat):
        one = _block_state(kind, cfg, batch, buf_len, dtype)
        st["cycle"][f"b{j}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_cycles,) + a.shape), one)
    for j in range(rem):
        st["remainder"][f"b{j}"] = _block_state(pat[j][0], cfg, batch, buf_len, dtype)
    return st


def run_blocks(blocks, x, cfg, states=None, index=0, serve_window=0):
    """Execute the block stack. Returns (x, new_states, aux)."""
    pat = _merged_pattern(cfg)

    def eff_window(w):
        if serve_window:
            return jnp.int32(serve_window) if not isinstance(w, int) else serve_window
        return w

    if _layout(cfg) == "uniform":
        kind = pat[0][0]
        windows = _windows(cfg)
        if serve_window:
            windows = jnp.minimum(jnp.where(windows == 0, serve_window, windows),
                                  serve_window)

        def body(carry, xs):
            h, aux = carry
            p, w, st = xs
            h, st, a = _apply_block(kind, p, h, cfg, w, st, index)
            return (h, aux + a), st

        xs = (blocks["stack"], windows, states)
        (x, aux), new_states = jax.lax.scan(body, (x, jnp.float32(0.0)), xs)
        return x, new_states, aux

    # cycle layout ----------------------------------------------------------
    n_cycles, rem = divmod(cfg.n_layers, len(pat))
    shared = blocks.get("shared")
    aux0 = jnp.float32(0.0)

    def cycle_body(carry, xs):
        h, aux = carry
        cyc_params, cyc_states = xs
        new_states = {}
        for j, (kind, w) in enumerate(pat):
            p = shared if kind == "shared_attn" else cyc_params[f"b{j}"]
            st = None if cyc_states is None else cyc_states[f"b{j}"]
            h, st, a = _apply_block(kind, p, h, cfg, eff_window(w), st, index)
            aux = aux + a
            new_states[f"b{j}"] = st
        return (h, aux), (new_states if cyc_states is not None else None)

    cyc_states = None if states is None else states["cycle"]
    xs = (blocks["cycle"], cyc_states)
    (x, aux), new_cyc = jax.lax.scan(cycle_body, (x, aux0), xs)

    new_rem = {}
    for j in range(rem):
        kind, w = pat[j]
        p = shared if kind == "shared_attn" else blocks["remainder"][f"b{j}"]
        st = None if states is None else states["remainder"].get(f"b{j}")
        x, st, a = _apply_block(kind, p, x, cfg, eff_window(w), st, index)
        aux = aux + a
        new_rem[f"b{j}"] = st
    new_states = None if states is None else {"cycle": new_cyc, "remainder": new_rem}
    return x, new_states, aux


# ---------------------------------------------------------------------------
# Full LM
# ---------------------------------------------------------------------------

def init_lm(cfg, key):
    dtype = jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "embed": embed_init(k1, (cfg.vocab_size, cfg.d_model), dtype),
        "blocks": init_blocks(cfg, k2, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = embed_init(k3, (cfg.d_model, cfg.vocab_size), dtype)
    return p


def _embed(params, cfg, tokens, prefix=None):
    x = jnp.take(params["embed"], tokens, axis=0)
    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if prefix is not None:
        x = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
    return x


def _head(params, cfg, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    return softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)


def lm_logits(cfg, params, tokens, prefix=None):
    """Teacher-forced logits over the token positions only."""
    x = _embed(params, cfg, tokens, prefix)
    x, _, aux = run_blocks(params["blocks"], x, cfg)
    if prefix is not None:
        x = x[:, prefix.shape[1]:]
    return _head(params, cfg, x), aux


def cross_entropy(logits, labels):
    """labels < 0 are masked out."""
    valid = labels >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                                 axis=-1)[..., 0]
    nll = jnp.where(valid, lse - picked, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def lm_loss(cfg, params, batch):
    logits, aux = lm_logits(cfg, params, batch["tokens"],
                            batch.get("prefix"))
    loss = cross_entropy(logits, batch["labels"])
    total = loss + cfg.router_aux_coef * aux
    return total, {"loss": loss, "aux": aux}


def lm_prefill(cfg, params, tokens, buf_len, prefix=None, serve_window=0):
    """Run the prompt through the stack, filling caches.
    Returns (last-token logits, states)."""
    x = _embed(params, cfg, tokens, prefix)
    B = x.shape[0]
    dtype = jnp.dtype(cfg.dtype)
    states = init_states(cfg, params["blocks"], B, buf_len, dtype)
    x, states, _ = run_blocks(params["blocks"], x, cfg, states=states, index=0,
                              serve_window=serve_window)
    return _head(params, cfg, x[:, -1:])[:, 0], states


def lm_make_state(cfg, params, batch_size, buf_len, prefix=None,
                  serve_window=0):
    """Blank decode states for ``batch_size`` sequences plus the stream
    start index (serving slot-reset / chunked-prefill entry point).

    Without a prefix this is just ``init_states`` and start 0. With a
    prefix (vlm/audio decoder-only) the prefix embeddings are run through
    the stack first — they occupy absolute positions ``0..P-1`` — and the
    returned start index is ``P``, so the caller streams raw TOKENS only
    (chunked prefill never needs to re-split the modality stub)."""
    dtype = jnp.dtype(cfg.dtype)
    states = init_states(cfg, params["blocks"], batch_size, buf_len, dtype)
    if prefix is None:
        return states, 0
    x = prefix.astype(dtype)
    _, states, _ = run_blocks(params["blocks"], x, cfg, states=states,
                              index=0, serve_window=serve_window)
    return states, prefix.shape[1]


def lm_prefill_chunk(cfg, params, states, tokens, index, serve_window=0):
    """Run ``tokens`` (B, C) through the stack at absolute positions
    ``index..index+C-1``, updating the (possibly ring) caches / recurrent
    states in place. Returns (last-token logits (B, V), new states) —
    exactly ``lm_prefill`` restricted to one stream chunk, so feeding a
    prompt chunk-by-chunk reproduces the one-shot prefill."""
    x = _embed(params, cfg, tokens)
    x, states, _ = run_blocks(params["blocks"], x, cfg, states=states,
                              index=index, serve_window=serve_window)
    return _head(params, cfg, x[:, -1:])[:, 0], states


def lm_decode_step(cfg, params, states, token, index, serve_window=0):
    """One decode step. token: (B, 1) int32; index: scalar int32 absolute
    position. Returns (logits (B, V), new states)."""
    x = _embed(params, cfg, token)
    x, states, _ = run_blocks(params["blocks"], x, cfg, states=states,
                              index=index, serve_window=serve_window)
    return _head(params, cfg, x)[:, 0], states

"""The dense decoder: the architecture module of every configuration file
that names no other under ``"reference"`` (``bench.reference.architecture``).

Nothing here imports the program under test. A decoder layer as the
program's model computes it: pre-norm residual blocks, zero-centred
RMSNorm gains ``x * (1 + w)``, embeddings scaled by ``sqrt(hidden_size)``,
rotary embeddings on halves, grouped-query causal attention, SwiGLU, an
untied head, mean token cross-entropy. The first two conventions
(zero-centred gains, scaled embeddings) are the program's and depart from
the published Yi and InternLM2 descriptions; PERF.md lists them.

Every matrix product runs at ``Precision.HIGHEST`` in float32. With
``precision="float8_e4m3fn"`` every matrix product's operands are rounded
to float8 first, forward and backward: the control that must fail.

What an architecture module provides (see PERF.md): ``param_shapes``,
``init_leaf``, ``loss_fn``, ``flops_per_token`` and ``program_fields``.
The pieces below them (``matmuls``, ``rms``, ``attention``, ``swiglu``,
``head_loss``) are there for other modules to build on.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 512
# the leaves that multiply every token: the layers' projections and the
# head, not the embedding lookup and not the norm gains
MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                 "lm_head")


def dims(cfg):
    """(d, heads, kv heads, head dim, ffn, vocab, layers) of a config file."""
    d = cfg["hidden_size"]
    nq = cfg["num_attention_heads"]
    return (d, nq, cfg["num_key_value_heads"], d // nq,
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def param_shapes(cfg):
    """The parameter tree the program's decoder takes, as shapes."""
    d, nq, nkv, hd, f, V, L = dims(cfg)
    return {
        "blocks": {"stack": {
            "attn": {"wk": (L, d, nkv * hd), "wo": (L, nq * hd, d),
                     "wq": (L, d, nq * hd), "wv": (L, d, nkv * hd)},
            "ln1": (L, d), "ln2": (L, d),
            "mlp": {"w_down": (L, f, d), "w_gate": (L, d, f),
                    "w_up": (L, d, f)}}},
        "embed": (V, d),
        "final_norm": (d,),
        "lm_head": (d, V),
    }


def init_leaf(key, path, shape):
    """One leaf's bfloat16 weights, by the leaf's name (``path[-1]``)."""
    name = path[-1]
    if name in ("ln1", "ln2", "final_norm"):
        return jnp.zeros(shape, jnp.bfloat16)
    if name in ("embed", "lm_head"):
        std = 0.02
    else:
        std = shape[-2] ** -0.5          # fan-in scaling
    return (jax.random.normal(key, shape, jnp.float32) * std) \
        .astype(jnp.bfloat16)


def matmul_params(cfg):
    """Parameters that multiply every token (``MATMUL_LEAVES``)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    return sum(math.prod(s) for p, s in flat if p[-1].key in MATMUL_LEAVES)


def flops_per_token(cfg, seq_len):
    """Training FLOPs of one token, forward and backward: 6 per matmul
    parameter, plus 12 x layers x attention width x sequence for the
    attention scores and values (causal, not halved; PaLM's count).
    Recomputation is not counted."""
    d, nq, _, hd, _, _, L = dims(cfg)
    return 6 * matmul_params(cfg) + 12 * L * nq * hd * seq_len


def program_fields(cfg):
    """The program's ``ModelConfig`` fields that the file fixes."""
    d, nq = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=nq,
                n_kv_heads=cfg["num_key_value_heads"], head_dim=d // nq,
                d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
                rope_theta=float(cfg["rope_theta"]),
                norm_eps=float(cfg["rms_norm_eps"]),
                qkv_bias=bool(cfg["attention_bias"]),
                tie_embeddings=bool(cfg["tie_word_embeddings"]),
                act=cfg["hidden_act"], dtype=cfg["torch_dtype"])


# ---------------------------------------------------------------------------
# The forward pass
# ---------------------------------------------------------------------------

def matmuls(precision):
    """``(q8, mm)``: the operand rounding of ``precision`` and a matrix
    product at HIGHEST on rounded operands."""
    if precision == "float32":
        def q8(x):
            return x
    else:
        dt = jnp.dtype(precision)

        def q8(x):
            return x.astype(dt).astype(jnp.float32)

    def mm(a, b):
        return jnp.matmul(q8(a), q8(b), precision=HIGHEST)
    return q8, mm


def rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope(x, theta):
    """Rotary embeddings on the two halves of each head. x: (B, S, H, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = np.exp(-np.log(theta) * np.arange(half, dtype=np.float64) / half)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def embed(params, tokens, cfg):
    return jnp.take(params["embed"], tokens, axis=0) \
        * math.sqrt(cfg["hidden_size"])


def attention(q, k, v, cfg, q8):
    """Grouped-query causal attention of the projections q (B, S, heads *
    hd) and k, v (B, S, kv heads * hd), rotary embeddings applied here;
    (B, S, heads * hd)."""
    _, nq, nkv, hd, _, _, _ = dims(cfg)
    B, S = q.shape[:2]
    q = rope(q.reshape(B, S, nq, hd), cfg["rope_theta"])
    k = rope(k.reshape(B, S, nkv, hd), cfg["rope_theta"])
    v = v.reshape(B, S, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    causal = np.tril(np.ones((S, S), bool))

    @jax.checkpoint
    def attend(qc, mask):
        s = jnp.einsum("bqhd,bkhd->bhqk", q8(qc), q8(k),
                       precision=HIGHEST) / math.sqrt(hd)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", q8(w), q8(v),
                          precision=HIGHEST)

    # queries in blocks, recomputed in the backward pass, so that one
    # (heads, S, S) score matrix never has to be held whole
    o = jnp.concatenate(
        [attend(q[:, a:a + Q_CHUNK], causal[a:a + Q_CHUNK])
         for a in range(0, S, Q_CHUNK)], axis=1)
    return o.reshape(B, S, nq * hd)


def swiglu(h, p, mm):
    """The gated MLP of one layer's ``mlp`` leaves."""
    g = jax.nn.silu(mm(h, p["w_gate"]))
    return mm(g * mm(h, p["w_up"]), p["w_down"])


def head_loss(x, params, labels, cfg, mm):
    """Final norm, head and the mean token cross-entropy."""
    x = rms(x, params["final_norm"], cfg["rms_norm_eps"])
    logits = mm(x, params["lm_head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def loss_fn(params, tokens, labels, cfg, precision="float32"):
    """Mean next-token cross-entropy of one worker's (B, S) batch."""
    eps = cfg["rms_norm_eps"]
    q8, mm = matmuls(precision)
    x = embed(params, tokens, cfg)
    stack = params["blocks"]["stack"]
    for layer in range(cfg["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[layer], stack)
        h = rms(x, p["ln1"], eps)
        a = p["attn"]
        o = attention(mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"]), cfg, q8)
        x = x + mm(o, a["wo"])
        x = x + swiglu(rms(x, p["ln2"], eps), p["mlp"], mm)
    return head_loss(x, params, labels, cfg, mm)

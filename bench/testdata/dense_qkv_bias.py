"""A second architecture module, for the harness's tests only: the dense
decoder of ``bench.dense`` with biases on the query, key and value
projections, as the program adds them under ``qkv_bias``
(``repro.models.attention.qkv_proj``: ``x @ w + b`` before the rotary
embeddings). A configuration file selects it with
``"reference": "bench.testdata.dense_qkv_bias"``.
"""
import jax
import jax.numpy as jnp

from bench import dense

BIASES = ("bq", "bk", "bv")
# the biases add no matrix product, and the program's fields are the
# dense decoder's (``attention_bias`` sets ``qkv_bias``)
flops_per_token = dense.flops_per_token
matmul_params = dense.matmul_params
program_fields = dense.program_fields


def param_shapes(cfg):
    d, nq, nkv, hd, _, _, L = dense.dims(cfg)
    shapes = dense.param_shapes(cfg)
    shapes["blocks"]["stack"]["attn"].update(
        bq=(L, nq * hd), bk=(L, nkv * hd), bv=(L, nkv * hd))
    return shapes


def init_leaf(key, path, shape):
    if path[-1] in BIASES:
        return (jax.random.normal(key, shape, jnp.float32) * 0.1) \
            .astype(jnp.bfloat16)
    return dense.init_leaf(key, path, shape)


def loss_fn(params, tokens, labels, cfg, precision="float32"):
    eps = cfg["rms_norm_eps"]
    q8, mm = dense.matmuls(precision)
    x = dense.embed(params, tokens, cfg)
    stack = params["blocks"]["stack"]
    for layer in range(cfg["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[layer], stack)
        h = dense.rms(x, p["ln1"], eps)
        a = p["attn"]
        o = dense.attention(mm(h, a["wq"]) + a["bq"], mm(h, a["wk"]) + a["bk"],
                            mm(h, a["wv"]) + a["bv"], cfg, q8)
        x = x + mm(o, a["wo"])
        x = x + dense.swiglu(dense.rms(x, p["ln2"], eps), p["mlp"], mm)
    return dense.head_loss(x, params, labels, cfg, mm)

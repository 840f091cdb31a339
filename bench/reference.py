"""The plain reference of a training cell, and the weights both sides start from.

Nothing here imports the program under test. ``make_weights`` draws the
weights from the seed, in bfloat16 (the type the configurations state), as
one jitted call; the benchmark hands the same tree to the program as its
initial model, so both sides start from the same numbers without the
reference reading anything the program made.

``ReferenceRun`` follows the first rounds of a DPPF run in plain
``jax.numpy``: a decoder layer as the program's model computes it
(pre-norm residual blocks, zero-centred RMSNorm gains ``x * (1 + w)``,
embeddings scaled by ``sqrt(hidden_size)``, rotary embeddings on halves,
grouped-query causal attention, SwiGLU, an untied head, mean token
cross-entropy), SGD with momentum and the weight decay added to the
gradient (``g + wd * p``), a cosine learning rate, and the paper's Eq. 5 consensus
over the workers: ``r_i = ||x_i - mean||``, ``c_i = alpha - lam_t / r_i``,
``x_i <- mean + (1 - c_i) (x_i - mean)``. The first two conventions
(zero-centred gains, scaled embeddings) are the program's and depart from
the published Yi and InternLM2 descriptions; PERF.md lists them.

Every matrix product runs at ``Precision.HIGHEST`` in float32. With
``precision="float8_e4m3fn"`` every matrix product's operands are rounded
to float8 first, forward and backward: the control that must fail.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-12
Q_CHUNK = 512


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


def seed_key(seed):
    """A PRNG key from any non-negative whole number (wider than 32 bits)."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf_init(key, path, shape):
    name = path[-1]
    if name in ("ln1", "ln2", "final_norm"):
        return jnp.zeros(shape, jnp.bfloat16)
    if name in ("embed", "lm_head"):
        std = 0.02
    else:
        std = shape[-2] ** -0.5          # fan-in scaling
    return (jax.random.normal(key, shape, jnp.float32) * std) \
        .astype(jnp.bfloat16)


def make_weights(cfg):
    """A jitted ``key -> tree`` of bfloat16 weights for ``cfg``."""
    shapes = param_shapes(cfg)
    paths = [tuple(k.key for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]
    treedef = jax.tree_util.tree_structure(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def init(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf_init(jax.random.fold_in(key, i), path, shape)
            for i, (path, shape) in enumerate(zip(paths, leaves))])
    return init


# ---------------------------------------------------------------------------
# The forward pass
# ---------------------------------------------------------------------------

def _quantizer(precision):
    if precision == "float32":
        return lambda x: x
    dt = jnp.dtype(precision)
    return lambda x: x.astype(dt).astype(jnp.float32)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """Rotary embeddings on the two halves of each head. x: (B, S, H, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = np.exp(-np.log(theta) * np.arange(half, dtype=np.float64) / half)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(params, tokens, labels, cfg, precision="float32"):
    """Mean next-token cross-entropy of one worker's (B, S) batch."""
    d, nq, nkv, hd, f, V, L = dims(cfg)
    eps = cfg["rms_norm_eps"]
    q8 = _quantizer(precision)

    def mm(a, b):
        return jnp.matmul(q8(a), q8(b), precision=HIGHEST)

    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0) * math.sqrt(d)
    stack = params["blocks"]["stack"]
    causal = np.tril(np.ones((S, S), bool))
    for layer in range(L):
        p = jax.tree.map(lambda a: a[layer], stack)
        h = _rms(x, p["ln1"], eps)
        q = _rope(mm(h, p["attn"]["wq"]).reshape(B, S, nq, hd),
                  cfg["rope_theta"])
        k = _rope(mm(h, p["attn"]["wk"]).reshape(B, S, nkv, hd),
                  cfg["rope_theta"])
        v = mm(h, p["attn"]["wv"]).reshape(B, S, nkv, hd)
        k = jnp.repeat(k, nq // nkv, axis=2)
        v = jnp.repeat(v, nq // nkv, axis=2)

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
        x = x + mm(o.reshape(B, S, nq * hd), p["attn"]["wo"])
        h = _rms(x, p["ln2"], eps)
        g = jax.nn.silu(mm(h, p["mlp"]["w_gate"]))
        x = x + mm(g * mm(h, p["mlp"]["w_up"]), p["mlp"]["w_down"])
    x = _rms(x, params["final_norm"], eps)
    logits = mm(x, params["lm_head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


# ---------------------------------------------------------------------------
# DPPF rounds
# ---------------------------------------------------------------------------

def cosine_lr(base, t, total):
    return base / 2.0 * (1.0 + math.cos(math.pi * min(max(t / total, 0.0),
                                                       1.0)))


def increasing_lam(lam, r, rounds):
    if rounds <= 1:
        return lam
    return lam / 2.0 * (1.0 - math.cos(math.pi * min(r / (rounds - 1),
                                                     1.0)))


class ReferenceRun:
    """The first rounds of a DPPF run, plainly, one worker at a time.

    ``fault`` plants one of the faults the comparison has to catch in the
    reference itself (it then stands in the program's place):
    ``"half_batch"`` takes the loss over the first half of each sequence
    only, ``"no_exchange"`` leaves the consensus out, ``"no_push"`` keeps
    the pull and leaves the push out (``lam_t = 0``), ``"push_sign"``
    pushes toward the mean instead of away (``c_i = alpha + lam_t / r_i``).
    """

    def __init__(self, cfg, train, traffic, *, precision="float32",
                 fault=None):
        self.cfg, self.train, self.traffic = cfg, train, traffic
        self.precision, self.fault = precision, fault
        self.M = train["workers"]
        self.tau = traffic["tau"]
        self.total_steps = traffic["plan_rounds"] * self.tau
        wd, mom = train["weight_decay"], train["momentum"]
        half = fault == "half_batch"

        def one_loss(p, tok, lab):
            if half:
                s = tok.shape[-1] // 2
                tok, lab = tok[..., :s], lab[..., :s]
            return loss_fn(p, tok, lab, cfg, precision)

        def local(p, mu, tok, lab, lrs):
            losses = []
            for s in range(tok.shape[0]):
                loss, g = jax.value_and_grad(one_loss)(p, tok[s], lab[s])
                mu = jax.tree.map(lambda m, gg, pp: mom * m + gg + wd * pp,
                                  mu, g, p)
                p = jax.tree.map(lambda pp, m: pp - lrs[s] * m, p, mu)
                losses.append(loss)
            return p, mu, jnp.stack(losses)
        self._local = jax.jit(local, donate_argnums=(0, 1))

        def sq_to_mean(*rows):
            mean = sum(rows) / len(rows)
            return jnp.stack([jnp.sum(jnp.square(x - mean)) for x in rows])
        self._sq_to_mean = jax.jit(sq_to_mean)

        def mix(coef, *rows):
            mean = sum(rows) / len(rows)
            return tuple(mean + (1.0 - c) * (x - mean)
                         for c, x in zip(coef, rows))
        self._mix = jax.jit(mix, donate_argnums=tuple(range(1, self.M + 1)))

        def change(x, w0):
            return jnp.sqrt(jnp.sum(jnp.square(x - w0.astype(jnp.float32))))
        self._change = jax.jit(change)

    def run(self, weights, batches, rounds, start=0):
        """Run the plan's rounds ``start .. start + rounds`` from
        ``weights`` (a bf16 tree) on ``batches(r) -> (tokens, labels)``
        shaped (tau, M, B, S).

        Returns per-round mean losses and mean worker distances before the
        consensus, the per-(worker, leaf) momentum norms after the first
        round, and the per-(worker, leaf) norms of the change over the
        rounds.
        The consensus goes leaf by leaf, so that at most one leaf's worker
        mean exists at a time.
        """
        M, tau, tr = self.M, self.tau, self.train
        treedef = jax.tree.structure(weights)
        xs = [jax.tree.map(lambda a: a.astype(jnp.float32), weights)
              for _ in range(M)]
        mus = [jax.tree.map(jnp.zeros_like, x) for x in xs]
        losses, dists, mu_norms = [], [], None
        for r in range(start, start + rounds):
            tok, lab = batches(r)
            lrs = jnp.asarray([cosine_lr(tr["lr"], r * tau + s,
                                         self.total_steps)
                               for s in range(tau)], jnp.float32)
            round_losses = []
            for m in range(M):
                xs[m], mus[m], lm = self._local(xs[m], mus[m], tok[:, m],
                                                lab[:, m], lrs)
                round_losses.append(lm)
            losses.append(float(jnp.mean(jnp.stack(round_losses))))
            if r == start:
                mu_norms = _leaf_norms(mus)
            cols = [jax.tree.leaves(x) for x in xs]
            sq = sum(np.asarray(self._sq_to_mean(*leaf), np.float64)
                     for leaf in zip(*cols))
            dist = np.sqrt(sq)
            dists.append(float(np.mean(dist)))
            if self.fault == "no_exchange":
                continue
            lam_t = increasing_lam(tr["lam"], r, self.traffic["plan_rounds"])
            push = {"no_push": 0.0, "push_sign": -1.0}.get(self.fault, 1.0)
            coef = jnp.asarray([tr["alpha"] - push * lam_t / max(d, EPS)
                                for d in dist], jnp.float32)
            del xs
            mixed = [self._mix(coef, *leaf) for leaf in zip(*cols)]
            del cols
            xs = [jax.tree.unflatten(treedef, [leaf[m] for leaf in mixed])
                  for m in range(M)]
            del mixed
        w0 = jax.tree.leaves(weights)
        change = np.asarray([[float(self._change(a, b)) for a, b in zip(
            jax.tree.leaves(x), w0)] for x in xs])
        return {"losses": losses, "dists": dists, "mu_norms": mu_norms,
                "change_norms": change}


def _leaf_norms(trees):
    """(workers, leaves) float64 array of leaf norms."""
    return np.asarray([[float(jnp.sqrt(jnp.sum(jnp.square(leaf))))
                        for leaf in jax.tree.leaves(t)] for t in trees])


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------

def compare(prog, ref):
    """The four gaps that decide ``correct``, each a float.

    ``loss_gap`` and ``dist_gap``: the worst round's relative gap of the
    mean loss and of the mean worker distance to the worker mean before
    the consensus. ``grad_gap``: after the first round, the worst (worker,
    leaf) gap between the momentum norms (the gradients as the optimizer holds
    them), against the larger of the reference leaf's norm and the median
    leaf's. ``change_gap``: the same for the norm of each leaf's change
    over the checked rounds, leaving out leaves whose reference momentum
    is under a thousandth of the median leaf's (they move by round-off).
    """
    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.max(np.abs(a - b) / np.abs(b)))

    def leaf_gap(p, r, keep):
        p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
        scale = np.maximum(r, np.median(r))
        return float(np.max(np.where(keep, np.abs(p - r) / scale, 0.0)))

    mu_ref = np.asarray(ref["mu_norms"], np.float64)
    keep = mu_ref >= 1e-3 * np.median(mu_ref)
    out = {
        "loss_gap": rel(prog["losses"], ref["losses"]),
        "dist_gap": rel(prog["dists"], ref["dists"]),
        "grad_gap": leaf_gap(prog["mu_norms"], mu_ref, np.ones_like(keep)),
        "change_gap": leaf_gap(prog["change_norms"], ref["change_norms"],
                               keep),
    }
    return {k: (v if math.isfinite(v) else float("inf"))
            for k, v in out.items()}

"""ConsensusEngine: flat, one-pass consensus for every DPPF method.

The round-boundary consensus update (paper §5, Eq. 5; Appendix D.1) is the
system's hottest communication path. The tree implementation in
``repro.core.pullpush``/``repro.core.consensus`` walks the full parameter
pytree 2–4 times per round; the original kernel wrapper additionally
re-materialized a flat copy via ``jnp.concatenate`` on every call.

This engine keeps ONE persistent flat view for the whole training run:

* ``flatten`` is called once at ``init_train_state`` — an ``(R, n)`` fp32
  matrix whose first ``M`` rows are the workers and whose optional aux rows
  carry row-shaped consensus state (EASGD's elastic center lives in row
  ``M``). The treedef/shapes/offsets are cached in a static ``FlatLayout``.
* Between rounds the buffer is donated (``jax.jit(..., donate_argnums)``),
  so the round update runs in place — no per-round ``concatenate``.
* Every consensus method lowers to at most two *stages*, each
  ``x <- W @ x`` with ``W = I + diag(coef) (T - I)`` for a row-stochastic
  target-weight matrix ``T`` and ``coef = c0 + c1 / max(r, eps)``:

    method      target weights T (worker rows)     c0       c1
    ----------  ---------------------------------  -------  ------
    simple_avg  uniform 1/M                        alpha    -lam   (Eq. 5, fused)
    hard        uniform 1/M                        1        0
    easgd       beta*u + (1-beta)*e_z  (z = aux)   alpha    0      (+push stage)
    parle       like easgd; pull ramps with lam_t  alpha*s  0      (no push)
    lsgd        one_hot(argmin losses)             alpha    0      (+push stage)
    mgrawa      w_m ∝ 1/||grad_m||                 alpha    0      (+push stage)
    lpf_sgd     uniform 1/M                        alpha    0      (+vec stage)
    entropy_sgd uniform 1/M (inner/outer plan)     alpha*s  0      (no push)
    push stage  uniform 1/M (or leader)            0        -lam
    vec stage   external field (filtered grad)     0        -lam   (vec_stage)
    ddp         (identity; metrics only)

  The per-method table rows are registry entries (`repro.core.methods`);
  the engine itself only ever sees generic stages.

* All distances are zero-sum quadratic forms of the Gram matrix
  ``G = X X^T``: ``||x_i - T_i x||^2 = v^T G v`` with ``v = e_i - T_i``,
  ``sum(v) = 0``. One Gram (one read of X, MXU-friendly) prices every
  worker's distance for any target at once; the apply is one more GEMM.
  The Pallas path (`kernels.pullpush.fused_round`) runs both phases in a
  single ``pallas_call`` with a *block-centered* Gram, which makes the
  zero-sum forms cancellation-free everywhere. The fast jnp path uses the
  uncentered Gram, whose fp32 forms resolve r only down to
  ~sqrt(eps32) * ||x||: stage distances are floored at that resolution
  (GRAM_NOISE_FACTOR), so a collapsed fleet under-pushes, escaping the
  window geometrically instead of pushing along rounding noise — the one
  documented deviation from the tree oracle, transient and only below
  ~0.4% of the parameter norm.
  ``precise=True`` selects exact gap-space stages instead (one extra
  (R, n) buffer per round) for bit-level parity at every scale.

  Kernel vs precise: both exact modes differ only in fp32 rounding order.
  The kernel adds the Gram chunk by chunk inside a column block and block
  by block across the view (``kernels.pullpush.kernel_block``); the
  precise path contracts the whole width in one fp32 matmul, whose
  rounding is the larger. With ``rho = (n / VIEW_QUANTUM + 64) * eps32``
  (``VIEW_QUANTUM`` = 2048 columns) a stage's ``r_i`` agree to relative
  ``rho``, and each output element to
  ``|c1_i| / r_i * 2 rho * |x - tx| + 4 R eps32 (1 + |1 - coef_i|)
  (|x| + |tx|)`` (the coefficient's error times the gap, plus the
  rounding of the mix; ``tx = T x``). ``chip_smoke.py`` checks the
  compiled kernel against this on the chip. Every (R, n) contraction asks
  for ``Precision.HIGHEST``: a TPU's default f32 matmul would round the
  view to bf16.

  On the kernel path the view's columns are padded once, at ``flatten``,
  to a multiple of ``pullpush.VIEW_QUANTUM`` (``FlatLayout.width``); zero
  columns are inert in every stage and ``unflatten`` drops them.

Method semantics (incl. push-from-recomputed-center ordering) mirror
``repro.core.consensus.apply_round``'s tree path, which remains the parity
oracle. See DESIGN.md §Consensus-engine.

Sharded execution: under ``jax.shard_map`` the same stages run on a
``(R, n_local)`` column shard — set ``engine.shard`` (a ``ShardedLayout``)
and every column contraction (Gram, gap Gram, distances) completes with a
``psum`` over ``shard.col_axes``, while the tiny (R, R) coefficient math
and the mixing GEMM stay shard-local. `train.trainer.
make_sharded_round_step` owns the row all-gather at the round boundary;
DESIGN.md §Sharded-execution has the layout and collective placement.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class ShardedLayout:
    """Mesh partitioning of the flat view under ``jax.shard_map``.

    Inside a mapped round every engine method receives the full-R rows of
    the LOCAL column shard, shape ``(R, n_local)``; worker rows are
    all-gathered over ``row_axes`` at the round boundary by the trainer
    (`make_sharded_round_step`), never inside the engine. Any contraction
    over the column (parameter) dimension — the Gram, gap Grams, distances
    to the mean — is completed with a ``psum`` over ``col_axes``; the
    mixing GEMM is column-local and needs no collective. ``col_axes`` may
    name MULTIPLE mesh axes — on a hierarchical ``workers x fsdp x model``
    mesh it is the whole ``("fsdp", "model")`` group and the one psum
    reduces over all ``fsdp x model`` column shards (DESIGN.md
    §Hierarchical-mesh). Hashable, so a sharded engine stays valid
    jit-static metadata (DESIGN.md §Sharded-execution).
    """
    row_axes: Tuple[str, ...] = ()
    col_axes: Tuple[str, ...] = ()
    rows: int = 1     # number of row (worker-axis) shards
    cols: int = 1     # number of column shards


@dataclass(frozen=True)
class FlatLayout:
    """Static description of the flat view (hashable; safe as jit aux data)."""
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]   # per-leaf shapes WITHOUT worker dim
    dtypes: Tuple[str, ...]
    offsets: Tuple[int, ...]
    n: int            # parameters per worker
    M: int            # workers
    # columns of the view: n, padded on the kernel path to a multiple of
    # pullpush.VIEW_QUANTUM (zero columns are inert in every stage);
    # unflatten drops them
    width: int
    aux: int = 0      # extra state rows (easgd center)

    @property
    def R(self) -> int:
        return self.M + self.aux


# The uncentered Gram resolves squared distances only down to
# ~eps32 * max||x_i||^2. The fast jnp path floors every stage distance at
# GRAM_NOISE_FACTOR times that resolution (r_floor ~ 0.4% of the parameter
# norm): sub-resolution distances are treated as at-resolution, so a
# collapsed fleet under-pushes — escaping the window geometrically
# (|1 - coef| per round, O(log(r_floor/r0)) rounds) instead of pushing
# along rounding noise. Above r_floor the path is accurate; ``precise=
# True`` (gap-space) and the kernel path (block-centered Gram) are exact
# at every scale.
GRAM_NOISE_FACTOR = 256.0
_EPS32 = float(jnp.finfo(jnp.float32).eps)
# the view's (R, n) contractions in full fp32: a TPU's default f32 matmul
# rounds operands to bf16 (on the CPU this is the default anyway)
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


def _eye(R):
    """(R, R) identity as a host constant. R is static, so the small
    (R, R) matrices are literals rather than traced iotas (the TPU compiler
    aborts on a tiny iota fused into an elementwise op, e.g. at R = 2)."""
    return np.eye(R, dtype=np.float32)


@dataclass(frozen=True)
class ConsensusEngine:
    layout: FlatLayout
    use_kernel: bool = False      # Pallas fused_round vs jnp Gram+GEMM
    interpret: Optional[bool] = None  # Pallas interpret mode; None = off
                                      # on a TPU, on everywhere else
    precise: bool = False         # jnp path: exact gap-space stages
    eps: float = 1e-12
    # set (dataclasses.replace) inside a shard_map'd round: inputs are then
    # (R, n_local) column shards and column contractions psum over
    # shard.col_axes. None = single-shard (whole (R, n) view) execution.
    shard: Optional[ShardedLayout] = None

    # -- construction -------------------------------------------------------

    @classmethod
    def from_stacked(cls, stacked, *, method: str = "simple_avg", **kw):
        """Build the layout from a worker-stacked pytree (leaves (M, ...))."""
        leaves, treedef = jax.tree_util.tree_flatten(stacked)
        M = leaves[0].shape[0]
        shapes = tuple(tuple(l.shape[1:]) for l in leaves)
        dtypes = tuple(str(l.dtype) for l in leaves)
        sizes = [math.prod(s) for s in shapes]
        offsets, o = [], 0
        for s in sizes:
            offsets.append(o)
            o += s
        from repro.core.methods import get_method
        aux = get_method(method).aux_rows
        # the fused kernel is TPU-targeted: compile it there, interpret it
        # when explicitly requested elsewhere (tests); CPU/GPU default to
        # the jnp Gram+GEMM path
        if "use_kernel" not in kw:
            kw["use_kernel"] = jax.default_backend() == "tpu"
        width = o
        if kw["use_kernel"]:
            from repro.kernels.pullpush import pullpush as pk
            width = pk.padded_width(o)
        layout = FlatLayout(treedef=treedef, shapes=shapes, dtypes=dtypes,
                            offsets=tuple(offsets), n=o, M=M, width=width,
                            aux=aux)
        return cls(layout=layout, **kw)

    # -- flat view management (flatten happens ONCE per training run) -------

    def flatten(self, stacked):
        """Stacked pytree -> (R, width) fp32. Aux rows are initialized here
        (easgd/parle: elastic center = worker mean); padding columns are
        zero."""
        leaves = jax.tree_util.tree_leaves(stacked)
        L = self.layout
        M = L.M
        cols = [l.reshape(M, -1).astype(jnp.float32) for l in leaves]
        if L.width > L.n:
            cols.append(jnp.zeros((M, L.width - L.n), jnp.float32))
        flat = jnp.concatenate(cols, axis=1)
        if self.layout.aux:
            flat = jnp.concatenate(
                [flat, jnp.mean(flat, axis=0, keepdims=True)], axis=0)
        return flat

    def unflatten(self, flat):
        """Worker rows of the flat view -> stacked pytree (original dtypes).
        Padding columns past ``layout.n`` are dropped here."""
        L = self.layout
        rows = flat[:L.M]
        out = [rows[:, off:off + math.prod(shape)]
               .reshape((L.M,) + shape).astype(dtype)
               for shape, dtype, off in zip(L.shapes, L.dtypes, L.offsets)]
        return jax.tree_util.tree_unflatten(L.treedef, out)

    def unflatten_row(self, row, *, cast=True):
        """One (n,) row -> parameter pytree without the worker dim.
        ``cast=False`` keeps the engine's fp32 leaves (e.g. the averaged
        final model, matching the tree path's fp32 ``tree_mean0``)."""
        L = self.layout
        out = [row[off:off + math.prod(shape)].reshape(shape)
               .astype(dtype if cast else jnp.float32)
               for shape, dtype, off in zip(L.shapes, L.dtypes, L.offsets)]
        return jax.tree_util.tree_unflatten(L.treedef, out)

    def workers(self, flat):
        return flat[:self.layout.M]

    def with_workers(self, flat, rows):
        """Write updated worker rows back into the (donated) flat buffer."""
        if not self.layout.aux:
            return rows
        return jax.lax.dynamic_update_slice(flat, rows, (0, 0))

    # -- flat math primitives ------------------------------------------------

    @property
    def uniform(self):
        """(R,) uniform weights over worker rows (zeros on aux rows), a
        host constant like ``_eye``."""
        L = self.layout
        u = np.zeros((L.R,), np.float32)
        u[:L.M] = 1.0 / L.M
        return u

    def _colsum(self, partial):
        """Complete a column-dimension contraction. Single-shard: identity.
        Sharded: psum of the per-shard partial over the column axes — the
        (R, R)-sized reduction is the only collective the engine itself
        ever issues. The device trace names it ``dppf.exchange``."""
        if self.shard is not None and self.shard.col_axes:
            with jax.named_scope("dppf.exchange"):
                return jax.lax.psum(partial, self.shard.col_axes)
        return partial

    def gram(self, flat):
        """(R, R) uncentered Gram. Only zero-sum quadratic forms of it are
        meaningful; their fp32 noise floor is ~eps32 * max diag (see
        GRAM_NOISE_FACTOR and the module docstring). Sharded: per-shard
        partial Gram psum'd over the column axes."""
        f = flat.astype(jnp.float32)
        return self._colsum(_mm(f, f.T))

    @staticmethod
    def sq_forms(G, V):
        """r2_i = V_i^T G V_i for each row of V. For an uncentered or
        block-centered Gram the rows must sum to 0 (shift invariance); for
        a gap Gram any V is valid."""
        return jnp.maximum(jnp.sum(_mm(V, G) * V, axis=1), 0.0)

    def mix(self, flat, W):
        """x <- W @ x (one GEMM over the flat view)."""
        return _mm(W.astype(jnp.float32), flat)

    def stage_comm(self, chunk, T):
        """The stage-1 column contraction over a COLUMN CHUNK of the flat
        view, psum-completed — the piece of a stage that the double-
        buffered overlap dispatches mid-scan (DESIGN.md §Overlap).
        Mode-matched to ``stage``: gap Gram (``precise``), plain Gram
        (fast), block-centered partial Gram (kernel). Contributions from
        disjoint column chunks ADD to the full-width contraction (the
        Gram is a sum over columns; the kernel path's per-block centering
        shift cancels in every zero-sum form), so
        ``sum_j stage_comm(x[:, j], T)`` feeds ``stage(x, T, c0, c1,
        gram=...)``. With ONE chunk the ops are identical to the ones
        ``stage`` itself would run — bit-for-bit the un-overlapped stage.
        """
        f = chunk.astype(jnp.float32)
        if self.use_kernel:
            from repro.kernels.pullpush import pullpush as pk
            return self._colsum(pk.partial_gram(
                f, interpret=self.interpret))
        if self.precise:
            g = _mm(T.astype(jnp.float32), f) - f
            return self._colsum(_mm(g, g.T))
        return self._colsum(_mm(f, f.T))

    def _gap_stage(self, flat, T, c0, c1, *, gram=None):
        """Exact (``precise=True``) stage: materialize the targets
        ``tx = T x`` and work in gap space — distances are
        ``diag((tx - x)(tx - x)^T)`` (cancellation-free by construction),
        the apply is the uniform form ``tx + (1 - c)(x - tx)`` (exact both
        for c = 1, reproducing the target bitwise, and for huge |c|, which
        scales a difference of nearby values), and the pre/post metrics are
        forms over the gap Gram. One extra (R, n) buffer + read vs the fast
        path. ``gram`` (a precomputed gap Gram from ``stage_comm`` chunks)
        skips the column contraction — the overlap path.

        Requires (true of every lowering) that all worker rows of T share
        one weight vector w, so d_m = x_m - mean = (e_m - u)^T g.
        """
        R, M = self.layout.R, self.layout.M
        eye = _eye(R)
        u = self.uniform
        # T @ x then subtract — NOT (T - I) @ x: the row-stochastic dot is
        # clean (collapsed identical rows reproduce exactly, e.g. after a
        # hard pull) and the subtraction of nearby values is exact, so a
        # degenerate gap is a true zero, matching the tree path's d = x - a
        tx = _mm(T, flat)
        Gg = gram
        if Gg is None:
            g = tx - flat
            Gg = self._colsum(_mm(g, g.T))
        r = jnp.sqrt(jnp.maximum(jnp.sum(Gg * eye, axis=1), 0.0))
        coef = c0 + c1 / jnp.maximum(r, self.eps)
        new = tx + (1.0 - coef)[:, None] * (flat - tx)
        # d_m = (u - e_m)^T g;  new_m - mean(new) = ((coef_m - 1) e_m
        #   + u * (1 - coef))^T g  — both exact forms over the gap Gram
        V_pre = np.broadcast_to(u, (R, R)) - eye
        pre = jnp.mean(jnp.sqrt(self.sq_forms(Gg, V_pre)[:M]))
        V_post = eye * (coef - 1.0)[:, None] \
            + jnp.broadcast_to(u * (1.0 - coef), (R, R))
        post = jnp.mean(jnp.sqrt(self.sq_forms(Gg, V_post)[:M]))
        return new, r, pre, post

    def stage(self, flat, T, c0, c1, *, gram=None):
        """One fused consensus stage.

        Per row i: ``r_i = ||x_i - T_i x||``, ``coef_i = c0_i + c1_i /
        max(r_i, eps)``, ``x_i <- x_i + coef_i (T_i x - x_i)``.
        Returns ``(new_flat, r, pre_dist, post_dist)`` — pre/post are the
        mean worker distance to the worker mean before/after the stage.

        Fast jnp path: one Gram + one mixing GEMM, with every distance
        floored at the Gram's fp32 resolution (module docstring — the only
        divergence from the tree oracle, transient and geometrically
        escaped). ``precise=True``: exact gap-space stages. Kernel path:
        one two-phase ``pallas_call``, block-centered Gram, exact.

        ``gram`` (the summed ``stage_comm`` chunks, mode-matched) skips
        the column contraction entirely: only the (R, R) coefficient math
        and the mixing GEMM/kernel run — the round-boundary epilogue of
        the double-buffered overlap, whose gather/psum already happened
        mid-scan (DESIGN.md §Overlap).
        """
        R, M = self.layout.R, self.layout.M
        eye = _eye(R)
        Vu = eye - np.broadcast_to(self.uniform, (R, R))

        if self.use_kernel:
            from repro.kernels.pullpush import pullpush as pk
            if gram is not None:
                # gather-free epilogue: coef from the psum-completed Gram,
                # one mixing kernel pass (kernels.pullpush.mix_from_gram)
                new, r, G = pk.mix_from_gram(
                    flat, T, c0, c1, gram, eps=self.eps,
                    interpret=self.interpret)
            elif self.shard is not None and self.shard.col_axes:
                # column shard: partial-Gram kernel + host-side psum
                # epilogue + mixing kernel (pullpush.fused_round_sharded)
                new, r, G = pk.fused_round_sharded(
                    flat, T, c0, c1, axis=self.shard.col_axes, eps=self.eps,
                    interpret=self.interpret)
            else:
                new, r, G = pk.fused_round(flat, T, c0, c1, eps=self.eps,
                                           interpret=self.interpret)
            coef = c0 + c1 / jnp.maximum(r, self.eps)
            W = eye + coef[:, None] * (T - eye)
            pre = jnp.mean(jnp.sqrt(self.sq_forms(G, Vu)[:M]))
            post = jnp.mean(jnp.sqrt(self.sq_forms(G, _mm(Vu, W))[:M]))
            return new, r, pre, post

        if self.precise:
            return self._gap_stage(flat, T, c0, c1, gram=gram)

        G = self.gram(flat) if gram is None else gram
        # the floor guards coef only — metrics report the (clamped) forms
        floor = GRAM_NOISE_FACTOR * _EPS32 * jnp.max(G * eye)
        r = jnp.sqrt(jnp.maximum(self.sq_forms(G, eye - T), floor))
        coef = c0 + c1 / jnp.maximum(r, self.eps)
        W = eye + coef[:, None] * (T - eye)
        pre = jnp.mean(jnp.sqrt(self.sq_forms(G, Vu)[:M]))
        post = jnp.mean(jnp.sqrt(self.sq_forms(G, _mm(Vu, W))[:M]))
        return self.mix(flat, W), r, pre, post

    def exact_stage(self, flat, lam_r):
        """Exact two-term push (Appendix E.1): x_m += (lam_r / M)
        (u_m - mean u), u_m = (x_m - mean x)/r_m. Gap-space (exact);
        ablation path, not the round hot path.
        Returns ``(new_flat, r, pre_dist, post_dist)``.
        """
        R, M = self.layout.R, self.layout.M
        eye = _eye(R)
        u = self.uniform
        T = np.broadcast_to(u, (R, R))
        if self.layout.aux:
            T = np.concatenate([T[:M], eye[M:]], axis=0)
        g = _mm(T, flat) - flat                   # worker rows: mean - x_m
        Gg = self._colsum(_mm(g, g.T))
        r = jnp.sqrt(jnp.maximum(jnp.sum(Gg * eye, axis=1), 0.0))
        inv = 1.0 / jnp.maximum(r, self.eps)
        units = -g[:M] * inv[:M, None]            # (x_m - mean)/r_m
        mean_unit = jnp.mean(units, axis=0, keepdims=True)
        upd = (lam_r / M) * (units - mean_unit)
        new = flat.at[:M].add(upd) if self.layout.aux else flat + upd
        # pre = r (target IS the worker mean). The push preserves the mean,
        # so new_m - mean(new) = (-(1 + (lam_r/M) inv_m) e_m
        #   + (lam_r/M)(u * inv))^T g — an exact form over the gap Gram.
        pre = jnp.mean(r[:M])
        iv = jnp.where(np.arange(R) < M, inv, 0.0)
        V_post = (-eye * (1.0 + (lam_r / M) * iv)[:, None]
                  + (lam_r / M) * jnp.broadcast_to(u * iv, (R, R)))
        post = jnp.mean(jnp.sqrt(self.sq_forms(Gg, V_post)[:M]))
        return new, r, pre, post

    def vec_stage(self, flat, vec, cvec):
        """Push along an EXTERNAL per-worker direction field (LPF-SGD's
        EMA-filtered gradient): row m moves by
        ``(cvec_m / max(r_m, eps)) * vec_m`` with ``r_m = ||vec_m||`` —
        the same normalized-force form as the Eq. 5 push, but the
        direction comes from ``vec`` (shape ``(M, n[_local])``), not from
        the gap to the mean. ``cvec`` is the full ``(R,)`` coefficient
        vector (aux entries 0; the elastic gate zeroes inactive workers,
        whose frozen rows also have a zero delta).
        Returns ``(new_flat, r, pre_dist, post_dist)`` like ``stage``.
        Sharded: the norm's column contraction psums over the column
        axes; the update itself is column-local.
        """
        M = self.layout.M
        v = vec.astype(jnp.float32)
        r = jnp.sqrt(jnp.maximum(
            self._colsum(jnp.sum(jnp.square(v), axis=1)), 0.0))
        upd = (cvec[:M] / jnp.maximum(r, self.eps))[:, None] * v
        pre = jnp.mean(self.dists_to_mean(flat))
        new = flat.at[:M].add(upd) if self.layout.aux else flat + upd
        post = jnp.mean(self.dists_to_mean(new))
        return new, r, pre, post

    def dists_to_mean(self, flat):
        """Exact per-worker distances to the worker mean (gap-space).
        Row-wise sum of squares — O(Mn), no (R, R) Gram for a diagonal
        (the ddp metrics branch hits this every round)."""
        M = self.layout.M
        w = flat[:M].astype(jnp.float32)
        g = jnp.mean(w, axis=0, keepdims=True) - w
        d2 = self._colsum(jnp.sum(g * g, axis=1))
        return jnp.sqrt(jnp.maximum(d2, 0.0))

"""Pallas TPU kernels for the DPPF consensus hot path.

DPPF's consensus is memory-bound: it touches every parameter of every
worker once for the distance and once for the update. Two generations of
kernels live here (DESIGN.md §Consensus-engine):

* ``sq_dist`` / ``apply_update`` — the original per-vector pair: a blockwise
  sum-of-squares reduction and a separate fused read-modify-write pass.
  Kept as the minimal reference kernels (and for their tests).

* ``fused_round`` — the ConsensusEngine kernel: ONE ``pallas_call`` whose
  grid runs two phases over the same column blocks of the flat ``(R, n)``
  worker matrix. Phase 0 accumulates a block-centered Gram matrix (distances
  for *all* rows in one read); phase 1 derives the per-row pull/push
  coefficients from the Gram in-kernel and applies the row-mixing update in
  one read-modify-write pass. This replaces the per-worker
  ``sq_dist`` + ``apply_update`` pair and their duplicated padding logic.

Block shape (rows, LANE)/(rows, block_cols) keeps the working set in VMEM
and the lane dimension hardware-aligned. The engine kernels take the worker
count R itself as the row block (a block equal to the full dimension is
always legal), so no row is ever padded; columns are padded only when the
caller's width does not tile into blocks — the engine pads its persistent
view once (``core.engine.FlatLayout.width``), so the round never copies.

``interpret=None`` resolves to ``jax.default_backend() != "tpu"``: the
kernels compile on a TPU and are interpreted everywhere else.

The engine kernels pass their own function's name as the ``pallas_call``
``name``: the custom call, and so the device trace's instruction, is
named after it (``%fused_round.<n>``, ``%partial_gram.<n>``,
``%mix_shard.<n>``), whatever function encloses the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
BLOCK_ROWS = 256  # 256*128*4B*2 tensors = 256 KiB of VMEM per step
# fp32 matmuls in full precision: Mosaic's default contraction may round
# the operands to bf16, which would round every parameter each round
_HIGHEST = jax.lax.Precision.HIGHEST


def _round_up(x, m):
    return -(-x // m) * m


def _interpret(interpret):
    """``None`` -> interpret everywhere but on a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


# ---------------------------------------------------------------------------
# Padding helpers of the reference pair
# ---------------------------------------------------------------------------

def _pad_view(x):
    """(n,) -> lane-aligned (rows, LANE) view. Returns (view, n)."""
    n = x.shape[0]
    rows = _round_up(n, LANE) // LANE
    pad = rows * LANE - n
    xp = jnp.pad(x, (0, pad)) if pad else x
    return xp.reshape(rows, LANE), n


def _pad_grid(views, block_rows=BLOCK_ROWS):
    """Pad (rows, LANE) views to a whole number of row blocks.

    Returns (padded_views, grid) — the grid/padding arithmetic shared by
    ``sq_dist`` and ``apply_update``.
    """
    rows = views[0].shape[0]
    grid = _round_up(rows, block_rows) // block_rows
    pad_r = grid * block_rows - rows
    if pad_r:
        views = [jnp.pad(v, ((0, pad_r), (0, 0))) for v in views]
    return views, grid


# ---------------------------------------------------------------------------
# Reference pair: separate distance + apply kernels
# ---------------------------------------------------------------------------

def _sq_dist_kernel(x_ref, a_ref, o_ref):
    # the (1,) output block maps to the same slot every grid step, so it
    # acts as the cross-step accumulator (standard revisiting pattern).
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[0] = jnp.float32(0.0)

    d = x_ref[...].astype(jnp.float32) - a_ref[...].astype(jnp.float32)
    o_ref[0] += jnp.sum(d * d)


def _apply_kernel(coef_ref, x_ref, a_ref, o_ref):
    xf = x_ref[...].astype(jnp.float32)
    af = a_ref[...].astype(jnp.float32)
    o_ref[...] = (xf + (af - xf) * coef_ref[0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sq_dist(x, a, *, interpret=None):
    """||x - a||^2 via the blockwise reduction kernel. x, a: (n,)."""
    xv, _ = _pad_view(x)
    av, _ = _pad_view(a)
    (xv, av), grid = _pad_grid([xv, av])
    out = pl.pallas_call(
        _sq_dist_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.float32),
        interpret=_interpret(interpret),
    )(xv, av)
    return out[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_update(x, a, coef, *, interpret=None):
    """out = x + (a - x) * coef in one fused pass. x, a: (n,)."""
    xv, n = _pad_view(x)
    av, _ = _pad_view(a)
    (xv, av), grid = _pad_grid([xv, av])
    coef = jnp.asarray(coef, jnp.float32).reshape(1)
    out = pl.pallas_call(
        _apply_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xv.shape, x.dtype),
        interpret=_interpret(interpret),
    )(coef, xv, av)
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# ConsensusEngine kernel: one pallas_call, two phases over one grid
# ---------------------------------------------------------------------------

def _eye(n, dtype=jnp.float32):
    """2D-iota identity (TPU requires >=2D iota inside kernels)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (r == c).astype(dtype)


def _col_block(n, block_cols):
    """Column block for an operand of width ``n``: ``n`` itself when it
    fits one block (a full-dimension block is always legal), else
    ``block_cols`` rounded to the lane width."""
    bc = _round_up(block_cols, LANE)
    return n if n <= bc else bc


def padded_width(n, block_cols=2048):
    """The width an (R, n) operand tiles into blocks at: the engine pads
    its persistent view to this once, so no kernel call pads or copies."""
    return _round_up(n, _col_block(n, block_cols))


def _pad_cols(flat, width):
    """(R, n) -> fp32 (R, width) with zero columns. A zero column is inert:
    it adds nothing to any Gram and mixes to zero. Only callers whose
    width does not tile into blocks pay this copy (column shards and
    overlap chunks); the engine's persistent view is padded once."""
    f = flat.astype(jnp.float32)
    n = f.shape[1]
    return jnp.pad(f, ((0, 0), (0, width - n))) if width > n else f


def _row_vec(c, R):
    """Scalar or (R,) coefficients -> (R, 1) fp32."""
    return jnp.broadcast_to(jnp.asarray(c, jnp.float32), (R,)).reshape(R, 1)


def _fused_round_kernel(x_ref, t_ref, c0_ref, c1_ref,
                        o_ref, r_ref, g_ref, g_acc, coef_scr, *, eps):
    phase = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((phase == 0) & (j == 0))
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)

    @pl.when(phase == 0)
    def _gram():
        x = x_ref[...]
        # Block-centered Gram: shifting every column by its row-0 value is
        # free (loaded block is in VMEM) and removes the catastrophic
        # cancellation of an uncentered x @ x.T — entries are O(spread^2),
        # not O(||x||^2). Any zero-sum quadratic form of G is exact.
        e = x - x[0:1, :]
        g_acc[...] += jnp.dot(e, e.T, precision=_HIGHEST,
                              preferred_element_type=jnp.float32)
        # o_ref is not written: its block index stays (0, 0) through
        # phase 0 and phase 1's first step, so nothing is written back
        # until phase 1 has filled block 0

    @pl.when((phase == 1) & (j == 0))
    def _coef():
        G = g_acc[...]
        T = t_ref[...]
        R = G.shape[0]
        eye = _eye(R)
        # r^2_i = (e_i - T_i)^T G (e_i - T_i), vectorized over rows.
        tg = jnp.dot(T, G, precision=_HIGHEST,
                     preferred_element_type=jnp.float32)
        diag_g = jnp.sum(G * eye, axis=1, keepdims=True)
        diag_tg = jnp.sum(T * G, axis=1, keepdims=True)       # G symmetric
        diag_tgt = jnp.sum(tg * T, axis=1, keepdims=True)
        r2 = diag_g - 2.0 * diag_tg + diag_tgt
        r = jnp.sqrt(jnp.maximum(r2, 0.0))
        coef_scr[...] = c0_ref[...] + c1_ref[...] / jnp.maximum(r, eps)
        r_ref[...] = r
        g_ref[...] = G

    @pl.when(phase == 1)
    def _apply():
        # uniform gap form tx + (1-c)(x - tx): the row-stochastic dot
        # accumulates O(||x||) terms (no |c| amplification), c = 1
        # reproduces the target bitwise (hard pull), and a huge |c| scales
        # a difference of nearby values — exact in every regime, unlike a
        # single W @ x GEMM whose rounding grows with |c| * ||x||
        x = x_ref[...]
        c = coef_scr[...]
        tx = jnp.dot(t_ref[...], x, precision=_HIGHEST,
                     preferred_element_type=jnp.float32)
        o_ref[...] = tx + (1.0 - c) * (x - tx)


@functools.partial(jax.jit,
                   static_argnames=("eps", "block_cols", "interpret"))
def fused_round(flat, T, c0, c1, *, eps=1e-12, block_cols=2048,
                interpret=None):
    """One consensus stage over the flat (R, n) worker matrix, fused.

    Per row i: ``r_i = ||x_i - T_i @ x||``, ``coef_i = c0_i + c1_i /
    max(r_i, eps)``, ``out_i = x_i + coef_i * (T_i @ x - x_i)`` — i.e. one
    row-mixing ``W @ x`` with ``W = I + diag(coef) (T - I)``. ``T`` must be
    row-stochastic (rows sum to 1); that makes every distance a zero-sum
    quadratic form of the Gram, which the block-centering computes exactly.

    Single ``pallas_call``, grid (2, n_blocks): phase 0 accumulates the
    Gram (one HBM read of x), phase 1 applies the mixing (one more read +
    the only write). The row block is R itself; the output aliases the
    input, so under a donated caller the stage updates the view in place.
    Returns ``(out (R, n) f32, r (R,), G (R, R))`` — G is the
    *block-centered* Gram: only zero-sum quadratic forms of it are
    meaningful (see repro/core/engine.py).
    """
    R, n = flat.shape
    bc = _col_block(n, block_cols)
    width = padded_width(n, block_cols)
    out, r, G = pl.pallas_call(
        functools.partial(_fused_round_kernel, eps=eps),
        grid=(2, width // bc),
        in_specs=[
            pl.BlockSpec((R, bc), lambda p, j: (0, j)),
            pl.BlockSpec((R, R), lambda p, j: (0, 0)),
            pl.BlockSpec((R, 1), lambda p, j: (0, 0)),
            pl.BlockSpec((R, 1), lambda p, j: (0, 0)),
        ],
        out_specs=[
            # phase 0 parks on block 0 (never written there); phase 1
            # walks the blocks
            pl.BlockSpec((R, bc), lambda p, j: (0, j * p)),
            pl.BlockSpec((R, 1), lambda p, j: (0, 0)),
            pl.BlockSpec((R, R), lambda p, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, width), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, R), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, R), jnp.float32),   # Gram accumulator
            pltpu.VMEM((R, 1), jnp.float32),   # per-row coefficients
        ],
        input_output_aliases={0: 0},
        interpret=_interpret(interpret),
        name="fused_round",
    )(_pad_cols(flat, width), T.astype(jnp.float32), _row_vec(c0, R),
      _row_vec(c1, R))
    return (out if width == n else out[:, :n]), r[:, 0], G


# ---------------------------------------------------------------------------
# Sharded variant: split phases with a host-side psum epilogue
# ---------------------------------------------------------------------------
#
# Under shard_map each device holds a COLUMN shard (R, n_local) of the flat
# view, so the two phases of ``fused_round`` cannot live in one pallas_call:
# the Gram must be completed across shards before any coefficient exists.
# ``partial_gram`` and ``mix_shard`` are the two phases as standalone
# kernels; ``fused_round_sharded`` chains them around a trace-level
# ``lax.psum`` (the "host-side" epilogue — it lowers to the mesh collective,
# not to kernel code). Block-centering still applies per column block, and
# partial Grams ADD across shards: each block's centering shift is a rank-2
# perturbation that cancels in every zero-sum quadratic form, which is the
# only way the Gram is ever read.


def _partial_gram_kernel(x_ref, g_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    x = x_ref[...]
    e = x - x[0:1, :]                      # block-centered (see fused_round)
    g_ref[...] += jnp.dot(e, e.T, precision=_HIGHEST,
                          preferred_element_type=jnp.float32)


def _mix_kernel(c_ref, x_ref, t_ref, o_ref):
    x = x_ref[...]
    tx = jnp.dot(t_ref[...], x, precision=_HIGHEST,
                 preferred_element_type=jnp.float32)
    o_ref[...] = tx + (1.0 - c_ref[...]) * (x - tx)


@functools.partial(jax.jit, static_argnames=("block_cols", "interpret"))
def partial_gram(flat, *, block_cols=2048, interpret=None):
    """Block-centered Gram of a (R, n_local) column shard — phase 0 of
    ``fused_round`` as its own kernel. Zero-sum quadratic forms of the
    summed per-shard outputs equal those of the full-width Gram."""
    R, n = flat.shape
    bc = _col_block(n, block_cols)
    width = padded_width(n, block_cols)
    return pl.pallas_call(
        _partial_gram_kernel,
        grid=(width // bc,),
        in_specs=[pl.BlockSpec((R, bc), lambda j: (0, j))],
        out_specs=pl.BlockSpec((R, R), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, R), jnp.float32),
        interpret=_interpret(interpret),
        name="partial_gram",
    )(_pad_cols(flat, width))


@functools.partial(jax.jit, static_argnames=("block_cols", "interpret"))
def mix_shard(flat, T, coef, *, block_cols=2048, interpret=None):
    """Apply ``out_i = x_i + coef_i (T_i x - x_i)`` to a (R, n_local)
    column shard with PRECOMPUTED coefficients — phase 1 of ``fused_round``
    (same uniform gap form, exact at c = 1 and for huge |c|). The output
    aliases the input, like ``fused_round``."""
    R, n = flat.shape
    bc = _col_block(n, block_cols)
    width = padded_width(n, block_cols)
    out = pl.pallas_call(
        _mix_kernel,
        grid=(width // bc,),
        in_specs=[
            pl.BlockSpec((R, 1), lambda j: (0, 0)),
            pl.BlockSpec((R, bc), lambda j: (0, j)),
            pl.BlockSpec((R, R), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((R, bc), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((R, width), jnp.float32),
        input_output_aliases={1: 0},
        interpret=_interpret(interpret),
        name="mix_shard",
    )(_row_vec(coef, R), _pad_cols(flat, width), T.astype(jnp.float32))
    return out if width == n else out[:, :n]


def _coef_from_gram(T, c0, c1, G, eps):
    """(r, coef) of one stage from a completed Gram (zero-sum forms)."""
    R = T.shape[0]
    # a host-constant identity: as a traced iota the TPU compiler aborts
    # on the tiny (R, R) subtraction (seen at R = 2)
    V = np.eye(R, dtype=np.float32) - T.astype(jnp.float32)
    r = jnp.sqrt(jnp.maximum(jnp.sum(
        jnp.matmul(V, G, precision=_HIGHEST) * V, axis=1), 0.0))
    coef = (jnp.broadcast_to(jnp.asarray(c0, jnp.float32), (R,))
            + jnp.asarray(c1, jnp.float32) / jnp.maximum(r, eps))
    return r, coef


def mix_from_gram(flat, T, c0, c1, G, *, eps=1e-12, block_cols=2048,
                  interpret=None):
    """Gather-free mixing epilogue: one consensus stage whose column
    contraction ALREADY happened — ``G`` is a completed (block-centered or
    plain) Gram, e.g. the psum'd sum of per-chunk ``partial_gram`` calls
    the double-buffered overlap dispatches mid-scan (one emission per
    column chunk; chunk boundaries only re-anchor the block centering,
    which cancels in every zero-sum form). Derives ``r``/``coef`` at trace
    level from ``G`` and applies the ``mix_shard`` kernel — the only work
    left at the round boundary. Returns ``(out, r, G)`` like
    ``fused_round``.
    """
    r, coef = _coef_from_gram(T, c0, c1, G, eps)
    out = mix_shard(flat, T, coef, block_cols=block_cols,
                    interpret=interpret)
    return out, r, G


def fused_round_sharded(flat, T, c0, c1, *, axis, eps=1e-12,
                        block_cols=2048, interpret=None):
    """``fused_round`` for a column shard under shard_map.

    ``flat`` is the local (R, n_local) shard; ``axis`` names the mesh
    axis/axes the columns are sharded over. Runs the partial-Gram kernel,
    completes the Gram with ``lax.psum(G, axis)`` (the round's only
    engine-level collective — (R, R) bytes), derives r/coef at trace
    level, and applies the mixing kernel shard-locally. Returns ``(out, r,
    G)`` with the same meaning as ``fused_round`` (G is the global
    block-centered Gram: zero-sum forms only). Must be called inside a
    ``shard_map`` that binds ``axis``.
    """
    G = partial_gram(flat, block_cols=block_cols, interpret=interpret)
    G = jax.lax.psum(G, axis)
    r, coef = _coef_from_gram(T, c0, c1, G, eps)
    out = mix_shard(flat, T, coef, block_cols=block_cols,
                    interpret=interpret)
    return out, r, G

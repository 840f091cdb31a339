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

The reference pair walks (BLOCK_ROWS, LANE) blocks. The engine kernels
take the worker count R itself as the row block (a block equal to the
full dimension is always legal), so no row is ever padded, and a column
block from ``kernel_block``: as wide as fast memory holds at that R, with
a ragged last block whose columns past the operand's end the Gram masks
out. No kernel pads or copies its operand, whatever its width.

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


# The engine kernels' column blocks. A grid step costs about a third of a
# microsecond whatever it moves, so the (R, bc) block is as wide as fast
# memory allows; the body walks it CHUNK_COLS columns at a time in a loop,
# so the kernel's code (which Mosaic unrolls over a block's vector
# registers) stays the size of one chunk's whatever the block. Of 2048,
# 8192 and 32768 columns, 8192 took the least time over R = 2 and R = 4
# on a v5e.
CHUNK_COLS = 8192
# fast memory for the pipelined blocks: the input and the aliased output,
# each double-buffered (v5e has 128 MiB a core)
VMEM_BLOCK_BUDGET = 32 * 2 ** 20
# the blocks, one chunk's temporaries and Mosaic's own scratch
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=48 * 2 ** 20)


def kernel_block(R, width):
    """``(bc, n_blocks)``: the column block the engine kernels walk an
    ``(R, width)`` fp32 operand in, and how many blocks cover it.

    A width of at most ``CHUNK_COLS`` is one full-width block (always a
    legal block). Wider operands take the widest multiple of
    ``CHUNK_COLS`` whose four pipelined ``(R, bc)`` buffers, rows counted
    in whole 8-row sublane tiles, fit ``VMEM_BLOCK_BUDGET``, and no wider
    than the width; the last block may run past the width (ragged)."""
    if width <= CHUNK_COLS:
        return width, 1
    per_col = 4 * _round_up(R, 8) * 4
    fit = VMEM_BLOCK_BUDGET // per_col // CHUNK_COLS * CHUNK_COLS
    bc = min(max(fit, CHUNK_COLS), width // CHUNK_COLS * CHUNK_COLS)
    return bc, -(-width // bc)


# The engine pads its persistent view's columns to a multiple of this
# (``core.engine.FlatLayout.width``); zero columns are inert in every stage.
VIEW_QUANTUM = 2048


def padded_width(n):
    """The width the engine pads its persistent view to: ``n`` rounded up
    to a multiple of ``VIEW_QUANTUM``, or ``n`` when it is no wider. The
    kernels take any width; the padding keeps the view's width a whole
    number of lane tiles."""
    return n if n <= VIEW_QUANTUM else _round_up(n, VIEW_QUANTUM)


def _chunk_gram(x):
    """Block-centered Gram of one chunk: shifting every column by its
    row-0 value removes the catastrophic cancellation of an uncentered
    x @ x.T (entries are O(spread^2), not O(||x||^2)); any zero-sum
    quadratic form of the sum of such Grams is exact. On the vector unit:
    column k is the rows' products with row k summed over the lanes, R
    fp32 vector products a lane tile, where a full-precision MXU
    contraction pays several passes a tile whatever R."""
    R = x.shape[0]
    e = x - x[0:1, :]
    col = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
    g = jnp.zeros((R, R), jnp.float32)
    for k in range(R):
        s = jnp.sum(e * e[k:k + 1, :], axis=1, keepdims=True)
        g = g + jnp.where(col == k, s, 0.0)
    return g


def _block_gram(x_ref, valid):
    """Sum of the chunk Grams over the first ``valid`` (static) columns of
    the (R, bc) block. A chunk that holds the end of the operand is masked:
    what a ragged last block holds past the end is undefined, and must add
    nothing to the Gram."""
    R, bc = x_ref.shape
    cb = min(CHUNK_COLS, bc)
    full, tail = divmod(valid, cb)

    def body(k, g):
        return g + _chunk_gram(x_ref[:, pl.ds(pl.multiple_of(k * cb, cb),
                                              cb)])

    if full == 1:      # a block of one chunk, maybe not of whole lane tiles
        g = _chunk_gram(x_ref[...] if cb == bc else x_ref[:, :cb])
    else:
        g = jax.lax.fori_loop(0, full, body, jnp.zeros((R, R), jnp.float32))
    if tail:
        x = x_ref[:, pl.ds(full * cb, cb)]
        col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        g = g + _chunk_gram(jnp.where(col < tail, x, 0.0))
    return g


def _mix(x, t, c):
    """``tx + (1 - c)(x - tx)``, ``tx = t @ x`` as R broadcast
    multiply-adds on the vector unit, in fp32."""
    tx = t[:, 0:1] * x[0:1, :]
    for k in range(1, x.shape[0]):
        tx = tx + t[:, k:k + 1] * x[k:k + 1, :]
    return tx + (1.0 - c) * (x - tx)


def _mix_block(x_ref, t, c, o_ref):
    """``o = tx + (1 - c)(x - tx)``, ``tx = t @ x``, over the (R, bc) block
    a chunk at a time. Every output column depends on its own input column
    alone, so a ragged block needs no mask here: what it computes past the
    operand's end is not written back."""
    bc = x_ref.shape[1]
    if bc <= CHUNK_COLS:
        o_ref[...] = _mix(x_ref[...], t, c)
        return

    def body(k, carry):
        cols = pl.ds(pl.multiple_of(k * CHUNK_COLS, CHUNK_COLS), CHUNK_COLS)
        o_ref[:, cols] = _mix(x_ref[:, cols], t, c)
        return carry

    jax.lax.fori_loop(0, bc // CHUNK_COLS, body, 0)


def _add_gram(x_ref, g_ref, j, width, n_blocks):
    """``g_ref += `` the Gram of grid block ``j`` of an operand ``width``
    columns wide; only the last block can be ragged."""
    bc = x_ref.shape[1]
    last = width - (n_blocks - 1) * bc
    if last == bc:
        g_ref[...] += _block_gram(x_ref, bc)
        return

    @pl.when(j < n_blocks - 1)
    def _whole():
        g_ref[...] += _block_gram(x_ref, bc)

    @pl.when(j == n_blocks - 1)
    def _ragged():
        g_ref[...] += _block_gram(x_ref, last)


def _row_vec(c, R):
    """Scalar or (R,) coefficients -> (R, 1) fp32."""
    return jnp.broadcast_to(jnp.asarray(c, jnp.float32), (R,)).reshape(R, 1)


def _fused_round_kernel(x_ref, t_ref, c0_ref, c1_ref,
                        o_ref, r_ref, g_ref, g_acc, coef_scr, *, eps, width,
                        n_blocks):
    phase = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((phase == 0) & (j == 0))
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)

    @pl.when(phase == 0)
    def _gram():
        _add_gram(x_ref, g_acc, j, width, n_blocks)
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
        _mix_block(x_ref, t_ref[...], coef_scr[...], o_ref)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def fused_round(flat, T, c0, c1, *, eps=1e-12, interpret=None):
    """One consensus stage over the flat (R, n) worker matrix, fused.

    Per row i: ``r_i = ||x_i - T_i @ x||``, ``coef_i = c0_i + c1_i /
    max(r_i, eps)``, ``out_i = x_i + coef_i * (T_i @ x - x_i)`` — i.e. one
    row-mixing ``W @ x`` with ``W = I + diag(coef) (T - I)``. ``T`` must be
    row-stochastic (rows sum to 1); that makes every distance a zero-sum
    quadratic form of the Gram, which the block-centering computes exactly.

    Single ``pallas_call``, grid (2, n_blocks) over the column blocks of
    ``kernel_block``: phase 0 accumulates the Gram (one HBM read of x),
    phase 1 applies the mixing (one more read + the only write). The row
    block is R itself; the output aliases the input, so under a donated
    caller the stage updates the view in place. Returns ``(out (R, n) f32,
    r (R,), G (R, R))`` — G is the *block-centered* Gram: only zero-sum
    quadratic forms of it are meaningful (see repro/core/engine.py).
    """
    R, n = flat.shape
    bc, n_blocks = kernel_block(R, n)
    out, r, G = pl.pallas_call(
        functools.partial(_fused_round_kernel, eps=eps, width=n,
                          n_blocks=n_blocks),
        grid=(2, n_blocks),
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
            jax.ShapeDtypeStruct((R, n), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, R), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, R), jnp.float32),   # Gram accumulator
            pltpu.VMEM((R, 1), jnp.float32),   # per-row coefficients
        ],
        input_output_aliases={0: 0},
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(interpret),
        name="fused_round",
    )(flat.astype(jnp.float32), T.astype(jnp.float32), _row_vec(c0, R),
      _row_vec(c1, R))
    return out, r[:, 0], G


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


def _partial_gram_kernel(x_ref, g_ref, *, width, n_blocks):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    _add_gram(x_ref, g_ref, j, width, n_blocks)   # as in fused_round


def _mix_kernel(c_ref, x_ref, t_ref, o_ref):
    _mix_block(x_ref, t_ref[...], c_ref[...], o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def partial_gram(flat, *, interpret=None):
    """Block-centered Gram of a (R, n_local) column shard — phase 0 of
    ``fused_round`` as its own kernel. Zero-sum quadratic forms of the
    summed per-shard outputs equal those of the full-width Gram."""
    R, n = flat.shape
    bc, n_blocks = kernel_block(R, n)
    return pl.pallas_call(
        functools.partial(_partial_gram_kernel, width=n, n_blocks=n_blocks),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((R, bc), lambda j: (0, j))],
        out_specs=pl.BlockSpec((R, R), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, R), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(interpret),
        name="partial_gram",
    )(flat.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def mix_shard(flat, T, coef, *, interpret=None):
    """Apply ``out_i = x_i + coef_i (T_i x - x_i)`` to a (R, n_local)
    column shard with PRECOMPUTED coefficients — phase 1 of ``fused_round``
    (same uniform gap form, exact at c = 1 and for huge |c|). The output
    aliases the input, like ``fused_round``."""
    R, n = flat.shape
    bc, n_blocks = kernel_block(R, n)
    return pl.pallas_call(
        _mix_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((R, 1), lambda j: (0, 0)),
            pl.BlockSpec((R, bc), lambda j: (0, j)),
            pl.BlockSpec((R, R), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((R, bc), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((R, n), jnp.float32),
        input_output_aliases={1: 0},
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(interpret),
        name="mix_shard",
    )(_row_vec(coef, R), flat.astype(jnp.float32), T.astype(jnp.float32))


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


def mix_from_gram(flat, T, c0, c1, G, *, eps=1e-12, interpret=None):
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
    out = mix_shard(flat, T, coef, interpret=interpret)
    return out, r, G


def fused_round_sharded(flat, T, c0, c1, *, axis, eps=1e-12,
                        interpret=None):
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
    G = partial_gram(flat, interpret=interpret)
    G = jax.lax.psum(G, axis)
    r, coef = _coef_from_gram(T, c0, c1, G, eps)
    out = mix_shard(flat, T, coef, interpret=interpret)
    return out, r, G

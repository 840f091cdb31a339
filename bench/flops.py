"""Operations and bytes of the work a cell does, from shapes alone.

Nothing here reads the program: parameter counts come from
``jax.eval_shape`` of the reference's weight tree (``bench.reference``).
"""
from __future__ import annotations

import math

import jax

from bench import reference

# the column block of the consensus kernels: the flat view's width is the
# parameter count rounded up to a whole number of these
BLOCK_COLS = 2048
MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                 "lm_head")


def _shapes(cfg):
    tree = jax.eval_shape(reference.make_weights(cfg), reference.seed_key(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(p[-1].key, leaf.shape) for p, leaf in flat]


def n_params(cfg):
    """Parameters of one worker."""
    return sum(math.prod(s) for _, s in _shapes(cfg))


def matmul_params(cfg):
    """Parameters that multiply every token: the layers' projections and
    the head, not the embedding lookup and not the norm gains."""
    return sum(math.prod(s) for name, s in _shapes(cfg)
               if name in MATMUL_LEAVES)


def flops_per_token(cfg, seq_len):
    """Training FLOPs of one token, forward and backward: 6 per matmul
    parameter, plus 12 x layers x attention width x sequence for the
    attention scores and values (causal, not halved; PaLM's count).
    Recomputation is not counted."""
    d, nq, _, hd, _, _, L = reference.dims(cfg)
    return 6 * matmul_params(cfg) + 12 * L * nq * hd * seq_len


def view_width(cfg):
    """Columns of the flat (R, width) fp32 view the consensus works on."""
    return -(-n_params(cfg) // BLOCK_COLS) * BLOCK_COLS


def consensus_bytes(rows, width):
    """HBM bytes of one consensus stage on an (rows, width) fp32 view: two
    reads (the Gram pass and the mix pass) and one write, 12 * R * width."""
    return 3 * 4 * rows * width

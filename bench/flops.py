"""Operations and bytes of the work a cell does, from shapes alone.

Nothing here reads the program: parameter counts come from
``jax.eval_shape`` of the reference's weight tree (``bench.reference``);
what a token costs is its architecture module's count.
"""
from __future__ import annotations

import math

import jax

from bench import reference

# the column block of the consensus kernels: the flat view's width is the
# parameter count rounded up to a whole number of these
BLOCK_COLS = 2048


def n_params(cfg):
    """Parameters of one worker."""
    tree = jax.eval_shape(reference.make_weights(cfg), reference.seed_key(0))
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))


def matmul_params(cfg):
    """Parameters that multiply every token, as the architecture counts
    them."""
    return reference.architecture(cfg).matmul_params(cfg)


def flops_per_token(cfg, seq_len):
    """Training FLOPs of one token, forward and backward, as the
    configuration's architecture does them."""
    return reference.architecture(cfg).flops_per_token(cfg, seq_len)


def view_width(cfg):
    """Columns of the flat (R, width) fp32 view the consensus works on."""
    return -(-n_params(cfg) // BLOCK_COLS) * BLOCK_COLS


def consensus_bytes(rows, width):
    """HBM bytes of one consensus stage on an (rows, width) fp32 view: two
    reads (the Gram pass and the mix pass) and one write, 12 * R * width."""
    return 3 * 4 * rows * width

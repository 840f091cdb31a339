"""ConsensusEngine: flat-vs-tree parity for every method, flatten round
trips, donation semantics, metrics-schema stability, fused kernel oracle."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DPPFConfig
from repro.core import consensus, pullpush as pp
from repro.core.engine import ConsensusEngine
from repro.kernels.pullpush import fused_round, fused_round_ref
from repro.kernels.pullpush import pullpush as pk
from repro.kernels.pullpush.pullpush import kernel_block

METRIC_KEYS = {"consensus_dist", "pre_dist", "pull_force", "push_force"}


def _stacked(key, M=4, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return {"w": jax.random.normal(ks[0], (M, 33, 7), dtype),
            "b": jax.random.normal(ks[1], (M, 17), dtype),
            "s": jax.random.normal(ks[2], (M, 5, 3, 2), dtype)}


def _tol(dtype):
    # tree path round-trips through the leaf dtype between pull and push;
    # the flat engine stays fp32 — bf16 parity is bounded by bf16 rounding
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=5e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# parity: every method, both engine execution paths, fp32 + bf16
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", consensus.METHODS)
@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flat_engine_matches_tree(method, use_kernel, dtype):
    key = jax.random.PRNGKey(7)
    stacked = _stacked(key, M=4, dtype=dtype)
    losses = jnp.asarray([3.0, 1.0, 2.0, 4.0])
    gns = jnp.asarray([1.0, 2.0, 0.5, 1.0])
    dcfg = DPPFConfig(alpha=0.3, lam=0.4, consensus=method)

    eng = ConsensusEngine.from_stacked(stacked, method=method,
                                       use_kernel=use_kernel)
    flat = eng.flatten(stacked)
    new_t, _, m_t = consensus.apply_round(
        stacked, dcfg, 0.25, consensus.init_state(method, stacked),
        losses=losses, grad_norms=gns)
    new_f, _, m_f = consensus.apply_round(
        flat, dcfg, 0.25, consensus.init_state(method, stacked, engine=eng),
        losses=losses, grad_norms=gns, engine=eng)

    tree_f = eng.unflatten(new_f)
    for k in stacked:
        np.testing.assert_allclose(np.asarray(tree_f[k], np.float32),
                                   np.asarray(new_t[k], np.float32),
                                   **_tol(dtype))
    assert set(m_f) == set(m_t) == METRIC_KEYS
    np.testing.assert_allclose(float(m_f["consensus_dist"]),
                               float(m_t["consensus_dist"]),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-3,
                               atol=1e-4)  # hard collapses to exactly 0


@pytest.mark.parametrize("method", [m for m in consensus.METHODS
                                    if m != "ddp"])
def test_flat_engine_push_variants_match_tree(method):
    """push on/off, exact second term, push-from-leader."""
    key = jax.random.PRNGKey(11)
    stacked = _stacked(key, M=4)
    losses = jnp.asarray([3.0, 1.0, 2.0, 4.0])
    gns = jnp.asarray([1.0, 2.0, 0.5, 1.0])
    cases = [dict(push=False), dict(push=True),
             dict(push=True, exact_second_term=True)]
    froms = ["average"] + (["leader"] if method == "lsgd" else [])
    for case in cases:
        for push_from in froms:
            dcfg = DPPFConfig(alpha=0.3, lam=0.4, consensus=method, **case)
            eng = ConsensusEngine.from_stacked(stacked, method=method)
            flat = eng.flatten(stacked)
            new_t, _, m_t = consensus.apply_round(
                stacked, dcfg, 0.25, consensus.init_state(method, stacked),
                losses=losses, grad_norms=gns, push_from=push_from)
            new_f, _, m_f = consensus.apply_round(
                flat, dcfg, 0.25, {}, losses=losses, grad_norms=gns,
                push_from=push_from, engine=eng)
            tree_f = eng.unflatten(new_f)
            for k in stacked:
                np.testing.assert_allclose(
                    np.asarray(tree_f[k]), np.asarray(new_t[k]),
                    atol=5e-4, rtol=1e-4,
                    err_msg=f"{method} {case} push_from={push_from}")
            assert set(m_f) == METRIC_KEYS


def test_easgd_center_rides_in_aux_row():
    """The flat easgd state is the aux row; it must track the tree center."""
    key = jax.random.PRNGKey(3)
    stacked = _stacked(key, M=4)
    dcfg = DPPFConfig(alpha=0.2, lam=0.0, push=False, consensus="easgd")
    eng = ConsensusEngine.from_stacked(stacked, method="easgd")
    assert eng.layout.aux == 1
    flat = eng.flatten(stacked)
    st_t = consensus.init_state("easgd", stacked)
    for _ in range(3):
        stacked, st_t, _ = consensus.apply_round(stacked, dcfg, 0.0, st_t)
        flat, _, _ = consensus.apply_round(flat, dcfg, 0.0, {}, engine=eng)
    z_tree = st_t["center"]
    z_flat = eng.unflatten_row(flat[eng.layout.M])
    for k in z_tree:
        np.testing.assert_allclose(np.asarray(z_flat[k], np.float32),
                                   np.asarray(z_tree[k]), atol=1e-5)


# ---------------------------------------------------------------------------
# metrics schema: stable pytree across every branch (lax.scan-safe)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", consensus.METHODS)
@pytest.mark.parametrize("push", [False, True])
def test_metrics_schema_stable(method, push):
    key = jax.random.PRNGKey(0)
    stacked = _stacked(key, M=4)
    dcfg = DPPFConfig(alpha=0.3, lam=0.4, consensus=method, push=push)
    losses = jnp.arange(4.0)
    gns = jnp.ones((4,))
    _, _, m = consensus.apply_round(
        stacked, dcfg, 0.1, consensus.init_state(method, stacked),
        losses=losses, grad_norms=gns)
    assert set(m) == METRIC_KEYS
    assert all(jnp.asarray(v).dtype == jnp.float32 for v in m.values())


# ---------------------------------------------------------------------------
# flatten round trip + donation contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flatten_roundtrip_preserves_shapes_dtypes(dtype):
    key = jax.random.PRNGKey(5)
    stacked = _stacked(key, M=3, dtype=dtype)
    eng = ConsensusEngine.from_stacked(stacked)
    flat = eng.flatten(stacked)
    assert flat.shape == (3, eng.layout.n) and flat.dtype == jnp.float32
    back = eng.unflatten(flat)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(stacked)
    for k in stacked:
        assert back[k].shape == stacked[k].shape
        assert back[k].dtype == stacked[k].dtype
        np.testing.assert_allclose(np.asarray(back[k], np.float32),
                                   np.asarray(stacked[k], np.float32),
                                   rtol=1e-6, atol=1e-6)
    row = eng.unflatten_row(flat[1])
    for k in stacked:
        assert row[k].shape == stacked[k].shape[1:]
        assert row[k].dtype == stacked[k].dtype
    # cast=False keeps the fp32 master leaves (average_params contract:
    # the final model is fp32 on every engine, like tree_mean0)
    row32 = eng.unflatten_row(flat[1], cast=False)
    assert all(l.dtype == jnp.float32 for l in jax.tree.leaves(row32))


def test_donated_round_does_not_alias_stale_buffers():
    """The donated flat view must be consumed (stale handle dies) and the
    result must equal the undonated computation — no aliasing bugs."""
    key = jax.random.PRNGKey(9)
    stacked = _stacked(key, M=4)
    dcfg = DPPFConfig(alpha=0.1, lam=0.5)
    eng = ConsensusEngine.from_stacked(stacked)

    plain = jax.jit(lambda f: consensus.apply_round(
        f, dcfg, 0.3, {}, engine=eng)[0])
    donating = jax.jit(lambda f: consensus.apply_round(
        f, dcfg, 0.3, {}, engine=eng)[0], donate_argnums=0)

    want = np.asarray(plain(eng.flatten(stacked)))
    flat = eng.flatten(stacked)
    out = donating(flat)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)
    assert flat.is_deleted()  # input buffer really was donated
    # chaining rounds through the donated buffer stays self-consistent
    out2 = donating(out)
    want2 = plain(plain(eng.flatten(stacked)))
    np.testing.assert_allclose(np.asarray(out2), np.asarray(want2),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# trainer integration: flat engine end-to-end
# ---------------------------------------------------------------------------

def test_trainer_flat_engine_matches_tree_engine(monkeypatch):
    """Ten rounds of training on each engine. The exact flat stages
    (``precise=True``) track the tree oracle to fp32 rounding. The fast
    flat stages read distances from the uncentered Gram, which resolves
    r^2 only to ``GRAM_NOISE_FACTOR * eps32 * max||x||^2`` (the documented
    floor); each round's push then moves a row by at most ``lam * floor /
    (2 r^2)`` more than the oracle's, so the bound is that times the
    number of rounds, read from the tree run's own norms."""
    from benchmarks.common import default_data, run_distributed
    from repro.core.engine import GRAM_NOISE_FACTOR
    data = default_data()
    base = DPPFConfig(alpha=0.2, lam=0.8, tau=4, lam_schedule="fixed")
    steps = 40
    r_tree = run_distributed(data, dataclasses.replace(base, engine="tree"),
                             M=4, steps=steps)
    r_flat = run_distributed(data, dataclasses.replace(base, engine="flat"),
                             M=4, steps=steps)
    from_stacked = ConsensusEngine.from_stacked.__func__
    monkeypatch.setattr(ConsensusEngine, "from_stacked", classmethod(
        lambda cls, stacked, **kw: from_stacked(cls, stacked, precise=True,
                                                **kw)))
    r_exact = run_distributed(data, dataclasses.replace(base, engine="flat"),
                              M=4, steps=steps)
    for k in r_tree.params_avg:
        np.testing.assert_allclose(
            np.asarray(r_exact.params_avg[k]["w"]),
            np.asarray(r_tree.params_avg[k]["w"]), atol=1e-6, rtol=1e-6)

    eps32 = float(np.finfo(np.float32).eps)
    x2 = max(sum(float(np.sum(np.square(np.asarray(v, np.float64))))
                 for v in jax.tree.leaves(w)) for w in r_tree.workers)
    r2 = r_tree.consensus_dist ** 2
    bound = (steps // base.tau) * base.lam * GRAM_NOISE_FACTOR * eps32 \
        * x2 / (2.0 * r2)
    assert abs(r_flat.consensus_dist - r_tree.consensus_dist) < 1e-3
    for k in r_tree.params_avg:
        np.testing.assert_allclose(
            np.asarray(r_flat.params_avg[k]["w"]),
            np.asarray(r_tree.params_avg[k]["w"]), atol=bound, rtol=1e-4)


def test_trainer_flat_engine_easgd_and_lsgd_run():
    from benchmarks.common import default_data, run_distributed
    data = default_data()
    for method in ("easgd", "lsgd"):
        r = run_distributed(
            data, DPPFConfig(alpha=0.3, lam=0.2, tau=4, consensus=method,
                             engine="flat"), M=4, steps=16)
        assert np.isfinite(r.test_err)


# ---------------------------------------------------------------------------
# fused kernel vs oracle
# ---------------------------------------------------------------------------

def _widest_block(R):
    """The kernel's column block at R rows for an operand wider than it."""
    return kernel_block(R, 1 << 40)[0]


# small widths; a ragged last block (two whole blocks and three lane tiles)
# at R = 2, 4 and 5 (an aux row); a width of exactly one block
KERNEL_SHAPES = [(4, 300), (8, 4097), (3, 128)] + [
    (R, 2 * _widest_block(R) + 3 * 128) for R in (2, 4, 5)] + [
    (4, _widest_block(4))]


@pytest.mark.parametrize("shape", KERNEL_SHAPES)
def test_fused_round_kernel_vs_ref(shape):
    R, n = shape
    key = jax.random.PRNGKey(R * n)
    flat = jax.random.normal(key, (R, n)) * 2.0 + 1.0
    # a non-trivial row-stochastic target mix
    T = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (R, R)))
    c0 = jnp.linspace(0.1, 0.5, R)
    c1 = jnp.linspace(-0.4, -0.1, R)
    # interpreted, a block read past the operand's end holds NaN: an
    # unmasked ragged block would poison the Gram
    got, r_got, G = fused_round(flat, T, c0, c1)
    want, r_want = fused_round_ref(flat, T, c0, c1)
    np.testing.assert_allclose(np.asarray(r_got), np.asarray(r_want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


WIDTHS = [128, 1000, 2048, 2176, 10240, 262144, 262144 + 128, 238_563_328,
          532_236_288]


@pytest.mark.parametrize("R", range(1, 9))
def test_kernel_block_fits_fast_memory(R):
    """The column block: lane-aligned, no wider than the operand, its four
    pipelined (R, bc) fp32 buffers (rows in whole 8-row tiles) inside the
    budget, and the blocks cover the width with at most one ragged."""
    for width in WIDTHS:
        bc, n_blocks = kernel_block(R, width)
        assert bc <= width
        assert bc % 128 == 0 or (n_blocks == 1 and bc == width)
        assert 4 * 8 * -(-R // 8) * 4 * bc <= pk.VMEM_BLOCK_BUDGET
        assert (n_blocks - 1) * bc < width <= n_blocks * bc
        if width > pk.CHUNK_COLS:
            assert bc % pk.CHUNK_COLS == 0
    # a grid step's fixed cost is paid per block: the yi-6b view takes
    # hundreds of blocks, not the 116,486 of 2048 columns
    assert kernel_block(R, 238_563_328)[1] <= 1000


def test_padded_width_rounds_to_2048():
    """The engine's view stays padded to a multiple of 2048 columns (the
    benchmark's byte count reads that width), whatever block the kernels
    walk it in."""
    assert pk.VIEW_QUANTUM == 2048
    assert pk.padded_width(238_563_328) == 238_563_328
    assert pk.padded_width(238_563_328 - 5) == 238_563_328
    assert pk.padded_width(4097) == 6144
    assert pk.padded_width(300) == 300
    stacked = {"a": jnp.zeros((2, 3000)), "b": jnp.zeros((2, 100))}
    eng = ConsensusEngine.from_stacked(stacked, use_kernel=True)
    assert (eng.layout.n, eng.layout.width) == (3100, 4096)
    assert eng.flatten(stacked).shape == (2, 4096)


@pytest.mark.parametrize("mode", ["precise", "kernel"])
def test_near_consensus_push_matches_tree(mode):
    """Workers within 1e-4 of each other, fixed-lam push: the exact engine
    modes must restore the paper's width like the tree path does, even
    though r is far below the uncentered Gram's fp32 resolution."""
    key = jax.random.PRNGKey(0)
    M, n = 4, 10000
    base = jax.random.normal(key, (n,))
    stacked = {"w": base[None] + 1e-4 * jax.random.normal(
        jax.random.fold_in(key, 1), (M, n))}
    dcfg = DPPFConfig(alpha=0.1, lam=0.5)
    eng = ConsensusEngine.from_stacked(stacked,
                                       use_kernel=(mode == "kernel"),
                                       precise=(mode == "precise"))
    flat = eng.flatten(stacked)
    new_t, _, m_t = consensus.apply_round(stacked, dcfg, 0.5, {})
    new_f, _, m_f = consensus.apply_round(flat, dcfg, 0.5, {}, engine=eng)
    np.testing.assert_allclose(np.asarray(eng.unflatten(new_f)["w"]),
                               np.asarray(new_t["w"]), atol=5e-4)
    np.testing.assert_allclose(float(m_f["consensus_dist"]),
                               float(m_t["consensus_dist"]), rtol=1e-3)


def test_fast_path_floor_is_bounded_and_monotone():
    """The fast jnp path cannot resolve r below ~sqrt(eps32)*||x|| and
    floors it there (engine.GRAM_NOISE_FACTOR): inside that window the
    push is attenuated but must still move workers APART monotonically
    (never along rounding noise), and above the window it must agree with
    the tree path again."""
    from repro.core.engine import GRAM_NOISE_FACTOR, _EPS32
    key = jax.random.PRNGKey(0)
    M, n = 4, 10000
    base = jax.random.normal(key, (n,))
    stacked = {"w": base[None] + 1e-4 * jax.random.normal(
        jax.random.fold_in(key, 1), (M, n))}
    dcfg = DPPFConfig(alpha=0.1, lam=0.5)
    eng = ConsensusEngine.from_stacked(stacked)  # fast jnp path
    assert not eng.precise and not eng.use_kernel
    flat = eng.flatten(stacked)
    floor_r = float(jnp.sqrt(GRAM_NOISE_FACTOR * _EPS32
                             * jnp.max(jnp.sum(jnp.square(flat), axis=1))))
    dists = [float(eng.dists_to_mean(flat).mean())]
    for _ in range(40):
        flat, _, _ = consensus.apply_round(flat, dcfg, 0.5, {}, engine=eng)
        dists.append(float(eng.dists_to_mean(flat).mean()))
        if dists[-1] > floor_r:
            break
    # monotone escape from the sub-resolution window...
    assert all(b > a for a, b in zip(dists, dists[1:]))
    assert dists[-1] > floor_r
    # ...and exact tree agreement once resolvable
    stacked_now = eng.unflatten(flat)
    new_t, _, m_t = consensus.apply_round(stacked_now, dcfg, 0.5, {})
    new_f, _, m_f = consensus.apply_round(flat, dcfg, 0.5, {}, engine=eng)
    np.testing.assert_allclose(np.asarray(eng.unflatten(new_f)["w"]),
                               np.asarray(new_t["w"]), atol=1e-3)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_pullpush_fused_exact_near_consensus(use_kernel):
    """The convenience wrapper keeps plain Eq. 5 semantics at every scale
    on BOTH execution paths (it must not inherit the fast path's floor)."""
    from repro.kernels.pullpush import pullpush_fused
    key = jax.random.PRNGKey(1)
    M, n = 8, 4096
    base = jax.random.normal(key, (n,))
    stacked = {"w": base[None] + 1e-5 * jax.random.normal(
        jax.random.fold_in(key, 1), (M, n))}
    got, r = pullpush_fused(stacked, 0.1, 0.5, use_kernel=use_kernel)
    want, m = pp.pullpush(stacked, 0.1, 0.5)
    np.testing.assert_allclose(np.asarray(r),
                               np.asarray(pp.worker_dists(stacked)),
                               rtol=1e-3)
    # both paths are fp32-limited to ~3e-4 here (coef ~ -800 amplifies the
    # fp32 distance rounding identically); the floor bug this guards
    # against produced O(0.5) errors
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(want["w"]),
                               atol=2e-3)


def test_fused_round_centered_gram_is_cancellation_safe():
    """Workers clustered far from the origin: the kernel's block-centered
    Gram keeps relative distance error ~1e-6 where a naive uncentered
    x @ x.T Gram loses several digits, summed over many chunks and over
    blocks with a ragged last one."""
    key = jax.random.PRNGKey(2)
    M = 4
    n = 2 * _widest_block(M) + 3 * 128
    base = jax.random.normal(key, (n,)) * 3.0 + 5.0
    flat = base[None] + 0.05 * jax.random.normal(
        jax.random.fold_in(key, 1), (M, n))
    T = jnp.full((M, M), 1.0 / M)
    _, r, _ = fused_round(flat, T, jnp.zeros(M), jnp.zeros(M))
    f64 = np.asarray(flat, np.float64)
    r_true = np.sqrt(((f64 - f64.mean(0)) ** 2).sum(1))
    np.testing.assert_allclose(np.asarray(r), r_true, rtol=1e-5)

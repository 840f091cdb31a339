"""Sharded ConsensusEngine rounds: shard_map parity for every method,
staleness-1 overlap (two-buffer reference + convergence), split kernel
phases with the psum epilogue, and train-state checkpoint resume.

Multi-device lowering runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (same pattern as
test_launch_sharding.py); single-device tests exercise the identical code
path on a 1x1 mesh in-process."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_train_state, save_train_state
from repro.configs import DPPFConfig, MeshPlan
from repro.core import consensus
from repro.optim import make_optimizer
from repro.train import (
    init_train_state, make_round_step, make_sharded_round_step,
    shard_train_state,
)
from repro.train.trainer import TrainState

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ROOT = os.path.join(os.path.dirname(__file__), "..")


def _mlp_setup(M=4, tau=2, dim=16, ncls=4, width=8):
    from benchmarks.common import mlp_init, mlp_loss
    opt = make_optimizer("sgd", momentum=0.9)
    p0 = lambda k: mlp_init(k, dim, ncls, width)

    def batches(seed):
        k = jax.random.PRNGKey(seed)
        return {"x": jax.random.normal(k, (tau, M, 8, dim)),
                "y": jax.random.randint(jax.random.fold_in(k, 1),
                                        (tau, M, 8), 0, ncls)}
    return opt, p0, mlp_loss, batches


# ---------------------------------------------------------------------------
# staleness-1 overlap: config plumbing, reference parity, convergence
# ---------------------------------------------------------------------------

def test_overlap_requires_flat_engine():
    with pytest.raises(ValueError, match="staleness1"):
        DPPFConfig(engine="tree", overlap="staleness1")
    with pytest.raises(ValueError, match="doublebuf"):
        DPPFConfig(engine="tree", overlap="doublebuf")
    with pytest.raises(ValueError, match="bogus"):
        DPPFConfig(overlap="bogus")
    with pytest.raises(ValueError, match="overlap_chunks"):
        DPPFConfig(engine="flat", overlap="doublebuf", overlap_chunks=0)
    # ddp never builds a flat engine -> the snapshot has nowhere to live
    opt, p0, loss, _ = _mlp_setup()
    for mode in ("staleness1", "doublebuf"):
        dcfg = DPPFConfig(engine="flat", overlap=mode, consensus="ddp")
        with pytest.raises(ValueError, match=mode):
            init_train_state(p0, opt, dcfg, 4, jax.random.PRNGKey(0))


@pytest.mark.parametrize("method", ["simple_avg", "easgd"])
def test_overlap_matches_two_buffer_reference(method):
    """The fused staleness-1 round must equal the explicit two-buffer
    scheme: x_{k+1} = q_k + (C(s_k) - s_k), s_{k+1} = q_k, with q from a
    pure-local-steps (identity-consensus) round and C the exact engine
    consensus of the snapshot."""
    M, tau = 4, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, consensus=method,
                      engine="flat", overlap="staleness1",
                      lam_schedule="fixed")
    key = jax.random.PRNGKey(0)

    st = init_train_state(p0, opt, dcfg, M, key)
    eng = st.engine
    assert st.snap is not None and st.snap["x"].shape == st.params.shape
    step = jax.jit(make_round_step(loss, opt, dcfg, base_lr=0.05,
                                   total_steps=20))

    # reference: local steps via an identity-consensus (ddp) round on the
    # same engine, stale consensus applied by hand
    dcfg_local = dataclasses.replace(dcfg, consensus="ddp", overlap="none")
    local_only = jax.jit(make_round_step(loss, opt, dcfg_local, base_lr=0.05,
                                         total_steps=20))
    st_ref = TrainState(params=st.params + 0.0,
                        opt=jax.tree.map(jnp.copy, st.opt),
                        cstate={}, t=st.t, engine=eng)
    snap = st.params + 0.0
    cstate = {}
    for r in range(4):
        b = batches(r)
        st, m = step(st, b)
        st_ref, _ = local_only(st_ref, b)
        q = st_ref.params
        c_out, cstate, _ = consensus.apply_round(
            snap, dcfg, float(m["lam_t"]), cstate, engine=eng)
        # round 0 is the explicit pipeline bubble (no delta applied)
        st_ref = dataclasses.replace(
            st_ref, params=q + (c_out - snap) if r > 0 else q)
        snap = q
        np.testing.assert_allclose(np.asarray(st.params),
                                   np.asarray(st_ref.params),
                                   atol=1e-5, rtol=1e-5, err_msg=f"round {r}")
        np.testing.assert_allclose(np.asarray(st.snap["x"]),
                                   np.asarray(snap), atol=1e-5, rtol=1e-5)


def test_overlap_round0_is_local_steps_only():
    """Round 0 is the explicit pipeline bubble: zero consensus delta, so
    params match a pure-local-step round (up to XLA fusion ulps — the two
    jit programs schedule the scan differently)."""
    M, tau = 4, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    base = dict(alpha=0.2, lam=0.4, tau=tau, engine="flat")
    key = jax.random.PRNGKey(3)
    st_o = init_train_state(p0, opt, DPPFConfig(overlap="staleness1", **base),
                            M, key)
    eng = st_o.engine
    st_l = TrainState(params=st_o.params + 0.0,
                      opt=jax.tree.map(jnp.copy, st_o.opt), cstate={},
                      t=st_o.t, engine=eng)
    b = batches(0)
    st_o, _ = jax.jit(make_round_step(
        loss, opt, DPPFConfig(overlap="staleness1", **base),
        base_lr=0.05, total_steps=20))(st_o, b)
    st_l, _ = jax.jit(make_round_step(
        loss, opt, DPPFConfig(consensus="ddp", **base),
        base_lr=0.05, total_steps=20))(st_l, b)
    np.testing.assert_allclose(np.asarray(st_o.params),
                               np.asarray(st_l.params), atol=1e-7, rtol=0)


# ---------------------------------------------------------------------------
# double-buffered overlap: bit-parity with staleness1, chunked numerics,
# the round-0 exact-consensus bubble, and the two-buffer reference
# ---------------------------------------------------------------------------

def _warm_pair(dcfg_s1, dcfg_db, M, tau, key, *, precise=True):
    """Two identical warm states (one staleness1 round from init — bit-
    identical under both modes by the acceptance bar) plus the two step
    fns, ready to diverge modes from round 1 on."""
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    st = init_train_state(p0, opt, dcfg_s1, M, key)
    if precise:
        st = dataclasses.replace(
            st, engine=dataclasses.replace(st.engine, precise=True))
    st2 = dataclasses.replace(
        st, params=st.params + 0.0, opt=jax.tree.map(jnp.copy, st.opt),
        snap=jax.tree.map(jnp.copy, st.snap))
    f1 = jax.jit(make_round_step(loss, opt, dcfg_s1, base_lr=0.05,
                                 total_steps=40))
    f2 = jax.jit(make_round_step(loss, opt, dcfg_db, base_lr=0.05,
                                 total_steps=40))
    b0 = batches(0)
    st, _ = f1(st, b0)
    st2, _ = f1(st2, b0)
    return st, st2, f1, f2, batches


@pytest.mark.parametrize("method", ["simple_avg", "hard", "easgd", "lsgd",
                                    "mgrawa"])
def test_doublebuf_chunks1_bitwise_equals_staleness1(method):
    """The correctness bar: doublebuf with ONE chunk runs the identical
    ops as staleness1 (same gather values, same single Gram psum, same
    stage math) — bit-for-bit in precise mode, metrics included, for
    every consensus method (ddp carries no overlap snapshot at all).
    Warm states (t > 0): round 0 differs by design — staleness1 skips
    its bubble, doublebuf fills the pipeline with an exact consensus
    (test below)."""
    M, tau = 4, 4
    base = dict(alpha=0.2, lam=0.4, tau=tau, consensus=method,
                engine="flat", lam_schedule="fixed")
    dcfg_s1 = DPPFConfig(overlap="staleness1", **base)
    dcfg_db = DPPFConfig(overlap="doublebuf", overlap_chunks=1, **base)
    st1, st2, f1, f2, batches = _warm_pair(dcfg_s1, dcfg_db, M, tau,
                                           jax.random.PRNGKey(0))
    for r in range(1, 4):
        b = batches(r)
        st1, m1 = f1(st1, b)
        st2, m2 = f2(st2, b)
    np.testing.assert_array_equal(np.asarray(st1.params),
                                  np.asarray(st2.params))
    np.testing.assert_array_equal(np.asarray(st1.snap["x"]),
                                  np.asarray(st2.snap["x"]))
    for k in m1:
        assert abs(float(m1[k]) - float(m2[k])) == 0.0, k


def test_doublebuf_chunked_gram_within_fp32_bounds():
    """The chunked-psum numerics contract (DESIGN.md §Overlap): splitting
    the stage-1 contraction into chunks only reorders fp32 reductions.
    Pinned at two levels: the summed per-chunk ``stage_comm`` matches the
    unchunked contraction to fp32 reduction-order tolerance in every
    engine mode, and a training trajectory stays close (NOT bit-identical
    — the unit-normed push amplifies ulps across rounds)."""
    from repro.core.engine import ConsensusEngine
    key = jax.random.PRNGKey(1)
    stacked = {"w": jax.random.normal(key, (4, 1000)) * 2.0 + 1.0}
    T = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (4, 4)))
    for patch in ({}, {"precise": True},
                  {"use_kernel": True, "interpret": True}):
        eng = ConsensusEngine.from_stacked(stacked, use_kernel=False,
                                           interpret=True)
        eng = dataclasses.replace(eng, **patch)
        flat = eng.flatten(stacked)
        whole = np.asarray(eng.stage_comm(flat, T))
        for k in (2, 4, 7):
            bounds, a = [], 0
            for i in range(k):
                b = a + 1000 // k + (1 if i < 1000 % k else 0)
                bounds.append((a, b))
                a = b
            chunked = sum(np.asarray(eng.stage_comm(flat[:, s:e], T))
                          for s, e in bounds)
            scale = max(abs(whole).max(), 1.0)
            assert abs(chunked - whole).max() <= 1e-5 * scale, (patch, k)

    # trajectory: reduction-order ulps amplify through the unit-normed
    # push but stay small over a short run
    M, tau = 4, 4
    base = dict(alpha=0.2, lam=0.4, tau=tau, engine="flat",
                lam_schedule="fixed", overlap="doublebuf")
    d1 = DPPFConfig(overlap_chunks=1, **base)
    d4 = DPPFConfig(overlap_chunks=4, **base)
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    st1 = init_train_state(p0, opt, d1, M, key)
    st4 = init_train_state(p0, opt, d4, M, key)
    f1 = jax.jit(make_round_step(loss, opt, d1, base_lr=0.05,
                                 total_steps=40))
    f4 = jax.jit(make_round_step(loss, opt, d4, base_lr=0.05,
                                 total_steps=40))
    for r in range(4):
        b = batches(r)
        st1, m1 = f1(st1, b)
        st4, m4 = f4(st4, b)
    np.testing.assert_allclose(np.asarray(st1.params),
                               np.asarray(st4.params), atol=2e-4, rtol=1e-3)
    for k in ("consensus_dist", "pre_dist", "train_loss"):
        np.testing.assert_allclose(float(m1[k]), float(m4[k]), rtol=1e-3,
                                   atol=1e-4)


def test_doublebuf_round0_bubble_is_exact_consensus():
    """The round-0 pipeline bubble under doublebuf APPLIES an exact
    consensus of the fresh post-scan view — it is not a skipped round.
    Per-worker inits make the consensus delta unambiguously nonzero."""
    M, tau = 4, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    key = jax.random.PRNGKey(3)
    base = dict(alpha=0.2, lam=0.4, tau=tau, engine="flat",
                lam_schedule="fixed")
    d_db = DPPFConfig(overlap="doublebuf", overlap_chunks=3, **base)
    d_ex = DPPFConfig(**base)
    d_s1 = DPPFConfig(overlap="staleness1", **base)
    st_db = init_train_state(p0, opt, d_db, M, key, same_init=False)
    st_ex = TrainState(params=st_db.params + 0.0,
                       opt=jax.tree.map(jnp.copy, st_db.opt), cstate={},
                       t=st_db.t, round=st_db.round, engine=st_db.engine)
    st_s1 = dataclasses.replace(
        st_db, params=st_db.params + 0.0,
        opt=jax.tree.map(jnp.copy, st_db.opt),
        snap=jax.tree.map(jnp.copy, st_db.snap))
    b = batches(0)
    st_db, m_db = jax.jit(make_round_step(loss, opt, d_db, base_lr=0.05,
                                          total_steps=20))(st_db, b)
    st_ex, _ = jax.jit(make_round_step(loss, opt, d_ex, base_lr=0.05,
                                       total_steps=20))(st_ex, b)
    st_s1, m_s1 = jax.jit(make_round_step(loss, opt, d_s1, base_lr=0.05,
                                          total_steps=20))(st_s1, b)
    # bubble == the exact round (up to cross-program fusion ulps)
    np.testing.assert_allclose(np.asarray(st_db.params),
                               np.asarray(st_ex.params), atol=1e-6, rtol=0)
    # ... and NOT the staleness1 skip (the consensus really applied)
    assert float(jnp.max(jnp.abs(st_db.params - st_s1.params))) > 1e-3
    # the staleness depth marks the bubble from the steady state
    assert float(m_db["staleness"]) == 0.0 and float(m_s1["staleness"]) == 0.0
    st_db, m_db = jax.jit(make_round_step(loss, opt, d_db, base_lr=0.05,
                                          total_steps=20))(st_db, batches(1))
    assert float(m_db["staleness"]) == 1.0


def test_doublebuf_matches_two_buffer_reference():
    """The doublebuf recursion against the explicit reference:
    x_1 = C(q_0) (exact bubble), then x_{k+1} = q_k + (C(s_k) - s_k) with
    s_{k+1} = q_k — the same two-buffer scheme as staleness1 with the
    bubble filled by an exact consensus instead of a skip."""
    M, tau = 4, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, consensus="easgd",
                      engine="flat", overlap="doublebuf", overlap_chunks=1,
                      lam_schedule="fixed")
    key = jax.random.PRNGKey(0)
    st = init_train_state(p0, opt, dcfg, M, key)
    eng = st.engine
    step = jax.jit(make_round_step(loss, opt, dcfg, base_lr=0.05,
                                   total_steps=20))
    dcfg_local = dataclasses.replace(dcfg, consensus="ddp", overlap="none")
    local_only = jax.jit(make_round_step(loss, opt, dcfg_local,
                                         base_lr=0.05, total_steps=20))
    st_ref = TrainState(params=st.params + 0.0,
                        opt=jax.tree.map(jnp.copy, st.opt),
                        cstate={}, t=st.t, engine=eng)
    snap = st.params + 0.0
    for r in range(4):
        b = batches(r)
        st, m = step(st, b)
        st_ref, _ = local_only(st_ref, b)
        q = st_ref.params
        if r == 0:
            new, _, _ = consensus.apply_round(
                q, dcfg, float(m["lam_t"]), {}, engine=eng)
        else:
            c_out, _, _ = consensus.apply_round(
                snap, dcfg, float(m["lam_t"]), {}, engine=eng)
            new = q + (c_out - snap)
        st_ref = dataclasses.replace(st_ref, params=new)
        snap = q
        np.testing.assert_allclose(np.asarray(st.params),
                                   np.asarray(st_ref.params),
                                   atol=1e-5, rtol=1e-5, err_msg=f"round {r}")
        np.testing.assert_allclose(np.asarray(st.snap["x"]),
                                   np.asarray(snap), atol=1e-5, rtol=1e-5)


def test_doublebuf_converges_close_to_exact():
    from benchmarks.common import default_data, run_distributed
    data = default_data()
    base = DPPFConfig(alpha=0.2, lam=0.8, tau=4, engine="flat",
                      lam_schedule="fixed")
    r_exact = run_distributed(data, base, M=4, steps=200)
    r_db = run_distributed(
        data, dataclasses.replace(base, overlap="doublebuf"), M=4,
        steps=200)
    assert np.isfinite(r_db.test_err)
    assert abs(r_db.test_err - r_exact.test_err) < 10.0
    assert np.isfinite(r_db.consensus_dist)


def test_overlap_converges_close_to_exact():
    from benchmarks.common import default_data, run_distributed
    data = default_data()
    base = DPPFConfig(alpha=0.2, lam=0.8, tau=4, engine="flat",
                      lam_schedule="fixed")
    r_exact = run_distributed(data, base, M=4, steps=200)
    r_stale = run_distributed(
        data, dataclasses.replace(base, overlap="staleness1"), M=4,
        steps=200)
    assert np.isfinite(r_stale.test_err)
    # staleness-1 shifts forces by one round; end-task quality must hold
    assert abs(r_stale.test_err - r_exact.test_err) < 10.0
    assert np.isfinite(r_stale.consensus_dist)


# ---------------------------------------------------------------------------
# split kernel phases: partial Grams add across column shards
# ---------------------------------------------------------------------------

def _shard_widths():
    """Column-shard widths: small; two blocks, the last ragged, at R = 2,
    4 and 5; exactly one block."""
    from repro.kernels.pullpush.pullpush import kernel_block

    def block(R):
        return kernel_block(R, 1 << 40)[0]
    return [(5, 250)] + [(R, block(R) + 256) for R in (2, 4, 5)] + [
        (4, block(4))]


@pytest.mark.parametrize("shape", _shard_widths())
def test_partial_gram_plus_mix_match_fused_round(shape):
    from repro.kernels.pullpush import (
        fused_round, fused_round_ref, mix_shard, partial_gram,
    )
    key = jax.random.PRNGKey(1)
    R, n_local = shape
    n = 4 * n_local
    flat = jax.random.normal(key, (R, n)) * 2.0 + 1.0
    T = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (R, R)))
    c0 = jnp.linspace(0.1, 0.5, R)
    c1 = jnp.linspace(-0.4, -0.1, R)
    want, r_want = fused_round_ref(flat, T, c0, c1)
    got_fused, r_fused, _ = fused_round(flat, T, c0, c1)

    # simulate 4 column shards: psum == plain sum of the partial Grams
    shards = jnp.split(flat, 4, axis=1)
    G = sum(partial_gram(s) for s in shards)
    V = jnp.eye(R) - T
    r = jnp.sqrt(jnp.maximum(jnp.sum((V @ G) * V, axis=1), 0.0))
    coef = c0 + c1 / jnp.maximum(r, 1e-12)
    out = jnp.concatenate([mix_shard(s, T, coef) for s in shards], axis=1)
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_want), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_fused), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(got_fused),
                               rtol=1e-5, atol=1e-5)


def test_partial_gram_centered_cancellation_safe():
    """Per-shard block-centering must survive the cross-shard sum: workers
    clustered far from the origin keep ~1e-5 relative distance accuracy,
    with each shard two blocks wide, the last ragged."""
    from repro.kernels.pullpush import partial_gram
    from repro.kernels.pullpush.pullpush import kernel_block
    key = jax.random.PRNGKey(2)
    M = 4
    n = 2 * (kernel_block(M, 1 << 40)[0] + 3 * 128)
    base = jax.random.normal(key, (n,)) * 3.0 + 5.0
    flat = base[None] + 0.05 * jax.random.normal(
        jax.random.fold_in(key, 1), (M, n))
    G = sum(partial_gram(s) for s in jnp.split(flat, 2, 1))
    T = jnp.full((M, M), 1.0 / M)
    V = jnp.eye(M) - T
    r = np.sqrt(np.maximum(np.asarray(jnp.sum((V @ G) * V, axis=1)), 0.0))
    f64 = np.asarray(flat, np.float64)
    r_true = np.sqrt(((f64 - f64.mean(0)) ** 2).sum(1))
    np.testing.assert_allclose(r, r_true, rtol=1e-5)


# ---------------------------------------------------------------------------
# sharded round on a 1x1 mesh (same program, trivial collectives)
# ---------------------------------------------------------------------------

def test_sharded_round_single_device_mesh_matches_plain():
    from repro.launch.mesh import make_cpu_mesh
    M, tau = 4, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    mesh = make_cpu_mesh()
    plan = MeshPlan(worker_axes=("data",), model_axes=("model",))
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="flat")
    key = jax.random.PRNGKey(0)
    st1 = init_train_state(p0, opt, dcfg, M, key)
    st2 = shard_train_state(init_train_state(p0, opt, dcfg, M, key),
                            mesh, plan)
    f1 = jax.jit(make_round_step(loss, opt, dcfg, base_lr=0.05,
                                 total_steps=20))
    f2 = jax.jit(make_sharded_round_step(loss, opt, dcfg, mesh=mesh,
                                         plan=plan, base_lr=0.05,
                                         total_steps=20))
    for r in range(2):
        st1, m1 = f1(st1, batches(r))
        st2, m2 = f2(st2, batches(r))
    np.testing.assert_allclose(np.asarray(st1.params), np.asarray(st2.params),
                               atol=1e-6, rtol=1e-6)
    for k in ("consensus_dist", "pre_dist", "train_loss"):
        np.testing.assert_allclose(float(m1[k]), float(m2[k]), rtol=1e-5,
                                   atol=1e-6)


def test_sharded_round_multi_axis_worker_group_and_tree_rejection():
    import numpy as onp
    from jax.sharding import Mesh
    M, tau = 3, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="flat")
    st = init_train_state(p0, opt, dcfg, M, jax.random.PRNGKey(0))
    devs = onp.asarray(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(devs, ("data", "model"))
    # a multi-axis worker group (size 1x1 here) must plumb through
    plan = MeshPlan(worker_axes=("data", "model"), model_axes=())
    step = make_sharded_round_step(loss, opt, dcfg, mesh=mesh, plan=plan,
                                   base_lr=0.05, total_steps=20)
    st, _ = jax.jit(step)(st, batches(0))
    assert st.params.shape == (M, st.engine.layout.n)
    # tree-engine state must be rejected outright
    dcfg_tree = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="tree")
    st_tree = init_train_state(p0, opt, dcfg_tree, M, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="flat"):
        make_sharded_round_step(loss, opt, dcfg_tree, mesh=mesh, plan=plan,
                                base_lr=0.05, total_steps=20)(
                                    st_tree, batches(0))


# ---------------------------------------------------------------------------
# hierarchical mesh: builders + 3-axis round on a 1x1x1 mesh
# ---------------------------------------------------------------------------

def test_make_hier_engine_mesh_validates_device_count():
    from repro.launch.mesh import make_hier_engine_mesh
    ndev = len(jax.devices())
    with pytest.raises(ValueError, match="host has"):
        make_hier_engine_mesh(ndev + 1, 2, 2)
    with pytest.raises(ValueError, match=">= 1"):
        make_hier_engine_mesh(0, 1, 1)


def test_hier_round_1x1x1_matches_plain():
    """The 3-axis plan (worker rows on data, columns on fsdp x model) runs
    the identical program on a trivial 1x1x1 mesh — parity with the plain
    single-shard round, aux row (easgd) included."""
    from repro.launch.mesh import make_hier_engine_mesh
    M, tau = 4, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    mesh, plan = make_hier_engine_mesh(1, 1, 1)
    assert plan.fsdp_axes == ("fsdp",)
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, consensus="easgd",
                      engine="flat")
    key = jax.random.PRNGKey(0)
    st1 = init_train_state(p0, opt, dcfg, M, key)
    st2 = shard_train_state(init_train_state(p0, opt, dcfg, M, key),
                            mesh, plan)
    f1 = jax.jit(make_round_step(loss, opt, dcfg, base_lr=0.05,
                                 total_steps=20))
    f2 = jax.jit(make_sharded_round_step(loss, opt, dcfg, mesh=mesh,
                                         plan=plan, base_lr=0.05,
                                         total_steps=20))
    for r in range(2):
        st1, m1 = f1(st1, batches(r))
        st2, m2 = f2(st2, batches(r))
    np.testing.assert_allclose(np.asarray(st1.params), np.asarray(st2.params),
                               atol=1e-6, rtol=1e-6)
    for k in ("consensus_dist", "pre_dist", "train_loss"):
        np.testing.assert_allclose(float(m1[k]), float(m2[k]), rtol=1e-5,
                                   atol=1e-6)


def test_flat_col_axes_subgroup_fallback():
    """The shared column rule: full fsdp+model group when divisible, else
    the divisible sub-group, else replicated. Pure function of the mesh
    SHAPE — a stub mesh suffices (no devices needed)."""
    from types import SimpleNamespace
    from repro.launch.mesh import flat_col_axes, flat_col_entry
    from repro.configs import MeshPlan
    mesh = SimpleNamespace(shape={"data": 2, "fsdp": 2, "model": 3})
    plan = MeshPlan(worker_axes=("data",), fsdp_axes=("fsdp",),
                    model_axes=("model",))
    # n divisible by 6: the psum group spans both axes
    assert flat_col_axes(mesh, 12, plan) == ("fsdp", "model")
    assert flat_col_entry(mesh, 12, plan) == ("fsdp", "model")
    # n % 3 != 0 but n % 2 == 0: fsdp-only fallback
    assert flat_col_axes(mesh, 8, plan) == ("fsdp",)
    assert flat_col_entry(mesh, 8, plan) == "fsdp"
    # n % 2 != 0 but n % 3 == 0: model-only fallback
    assert flat_col_axes(mesh, 9, plan) == ("model",)
    # prime n: replicate
    assert flat_col_axes(mesh, 7, plan) == ()
    assert flat_col_entry(mesh, 7, plan) is None


# ---------------------------------------------------------------------------
# checkpoint: mid-run resume == straight-through
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("overlap", ["none", "staleness1", "doublebuf"])
def test_train_state_checkpoint_resume_matches_straight_run(tmp_path,
                                                            overlap):
    """Mid-run resume == straight-through for every overlap mode: the
    snapshot (the overlap's persistent comm buffer) round-trips through
    the checkpoint, so a doublebuf resume continues the stale recursion
    exactly — no re-bubble."""
    M, tau = 4, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="flat",
                      overlap=overlap, overlap_chunks=2)
    key = jax.random.PRNGKey(0)
    step = jax.jit(make_round_step(loss, opt, dcfg, base_lr=0.05,
                                   total_steps=20), donate_argnums=0)

    straight = init_train_state(p0, opt, dcfg, M, key)
    resumed = init_train_state(p0, opt, dcfg, M, key)
    for r in range(2):
        straight, _ = step(straight, batches(r))
        resumed, _ = step(resumed, batches(r))
    path = str(tmp_path / "state.npz")
    save_train_state(path, resumed)

    # fresh template (same config/seed), restore, continue
    template = init_train_state(p0, opt, dcfg, M, key)
    resumed = load_train_state(path, template)
    assert int(resumed.t) == 2 * tau
    if overlap != "none":
        assert resumed.snap is not None
    for r in range(2, 4):
        straight, _ = step(straight, batches(r))
        resumed, _ = step(resumed, batches(r))
    np.testing.assert_array_equal(np.asarray(straight.params),
                                  np.asarray(resumed.params))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), straight.opt, resumed.opt)


def test_load_train_state_format_guard_and_snap_fallback(tmp_path):
    from repro.checkpoint import save_pytree
    M, tau = 4, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    key = jax.random.PRNGKey(0)

    # a final-params (serving) checkpoint is a different format: clear error
    bad = str(tmp_path / "final.npz")
    save_pytree(bad, {"w": np.zeros((3, 3))})
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="flat")
    template = init_train_state(p0, opt, dcfg, M, key)
    with pytest.raises(ValueError, match="train-state"):
        load_train_state(bad, template)

    # a mid-run checkpoint saved WITHOUT a snapshot (exact mode) resumes
    # into an overlap run with the RESTORED params as warm-start snapshot
    # (not the init fleet — its stale delta would jolt trained params)
    exact_state = init_train_state(p0, opt, dcfg, M, key)
    step = jax.jit(make_round_step(loss, opt, dcfg, base_lr=0.05,
                                   total_steps=20))
    exact_state, _ = step(exact_state, batches(0))
    path = str(tmp_path / "exact.npz")
    save_train_state(path, exact_state)
    for mode in ("staleness1", "doublebuf"):
        dcfg_o = dataclasses.replace(dcfg, overlap=mode)
        tmpl_o = init_train_state(p0, opt, dcfg_o, M, key)
        resumed = load_train_state(path, tmpl_o)
        assert resumed.snap is not None and int(resumed.t) == tau
        np.testing.assert_array_equal(np.asarray(resumed.snap["x"]),
                                      np.asarray(exact_state.params))
        np.testing.assert_array_equal(np.asarray(resumed.params),
                                      np.asarray(exact_state.params))
        # resuming mid-overlap never re-bubbles: t > 0 keeps the stale
        # recursion live, seeded by the warm-start snapshot; the step fn
        # runs cleanly from here
        step_o = jax.jit(make_round_step(loss, opt, dcfg_o, base_lr=0.05,
                                         total_steps=20))
        cont, m = step_o(resumed, batches(1))
        assert float(m["staleness"]) == 1.0
        assert np.isfinite(float(m["consensus_dist"]))


# ---------------------------------------------------------------------------
# the real thing: 8 forced host devices, every method, both engine modes
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_parity_8dev_all_methods():
    """One shard_map round on a (4 workers x 2 columns) host mesh vs the
    single-device flat engine, for every consensus method: bit-for-bit in
    precise mode (ulp-level for lsgd's argmin tie-breaks), Gram-floor
    tolerance otherwise; kernel path and staleness-1 overlap included."""
    body = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs import DPPFConfig, MeshPlan
from repro.core import consensus
from repro.train import (init_train_state, make_round_step,
                         make_sharded_round_step, shard_train_state)
from repro.optim import make_optimizer
from benchmarks.common import mlp_init, mlp_loss

dim, ncls, width, M, tau = 16, 4, 8, 4, 2
key = jax.random.PRNGKey(0)
opt = make_optimizer("sgd", momentum=0.9)
p0 = lambda k: mlp_init(k, dim, ncls, width)
def batches(seed):
    k = jax.random.PRNGKey(seed)
    return {"x": jax.random.normal(k, (tau, M, 8, dim)),
            "y": jax.random.randint(jax.random.fold_in(k, 1),
                                    (tau, M, 8), 0, ncls)}
mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
plan = MeshPlan(worker_axes=("data",), model_axes=("model",))
MKEYS = ("consensus_dist", "pre_dist", "pull_force", "push_force",
         "train_loss", "lam_t")

def run_pair(dcfg, engine_patch=None, rounds=2):
    st1 = init_train_state(p0, opt, dcfg, M, key)
    if st1.engine is None:  # ddp: reuse the simple_avg layout (aux = 0)
        st1 = init_train_state(
            p0, opt, dataclasses.replace(dcfg, consensus="simple_avg"),
            M, key)
    if engine_patch:
        st1 = dataclasses.replace(
            st1, engine=dataclasses.replace(st1.engine, **engine_patch))
    st2 = shard_train_state(st1, mesh, plan)
    f1 = jax.jit(make_round_step(mlp_loss, opt, dcfg, base_lr=0.05,
                                 total_steps=20))
    f2 = jax.jit(make_sharded_round_step(mlp_loss, opt, dcfg, mesh=mesh,
                                         plan=plan, base_lr=0.05,
                                         total_steps=20))
    for r in range(rounds):
        st1, m1 = f1(st1, batches(r))
        st2, m2 = f2(st2, batches(r))
    dp = float(jnp.max(jnp.abs(st1.params - st2.params)))
    dm = max(abs(float(m1[k]) - float(m2[k])) for k in MKEYS)
    return dp, dm

for method in consensus.METHODS:
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, consensus=method,
                      engine="flat")
    dp, dm = run_pair(dcfg)
    assert dp < 2e-5 and dm < 1e-4, (method, "fast", dp, dm)
    dp, dm = run_pair(dcfg, engine_patch={"precise": True})
    # bit-for-bit up to reduction-order ulps in the lsgd argmin input
    assert dp <= 1e-7 and dm < 1e-6, (method, "precise", dp, dm)
print("parity OK")

# kernel path (interpret mode): split phases + psum epilogue under shard_map
dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="flat")
dp, dm = run_pair(dcfg, engine_patch={"use_kernel": True, "interpret": True})
assert dp < 2e-5 and dm < 1e-4, ("kernel", dp, dm)
print("kernel OK")

# staleness-1 overlap: sharded == single-device (precise engine)
dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="flat",
                  overlap="staleness1", lam_schedule="fixed")
dp, dm = run_pair(dcfg, engine_patch={"precise": True}, rounds=3)
assert dp < 1e-6 and dm < 1e-5, ("overlap", dp, dm)
print("overlap OK")
print("ALL OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep + ROOT)
    out = subprocess.run([sys.executable, "-c", body], capture_output=True,
                         text=True, env=env, timeout=560, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ALL OK" in out.stdout


@pytest.mark.slow
def test_doublebuf_parity_8dev_flat_and_hier():
    """THE overlap acceptance leg (ISSUE 5): on 8 forced host devices,
    doublebuf with n_chunks=1 is bit-for-bit staleness1 in precise mode
    (<= 1e-7; exact-zero in practice) for every consensus method incl.
    the easgd aux row, on BOTH the flat 8x1 row-sharded mesh and the hier
    2x2x2 workers x fsdp x model mesh (where the mid-scan chunks really
    gather over the worker axis and psum over both column axes). Fast
    mode stays within the documented Gram-floor bounds with chunking
    (overlap_chunks=4), kernel path included. Warm states: round 0 runs
    under staleness1 for both trajectories (the doublebuf bubble is an
    exact consensus BY DESIGN and is pinned separately)."""
    body = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs import DPPFConfig, MeshPlan
from repro.core import consensus
from repro.train import (init_train_state, make_sharded_round_step,
                         shard_train_state)
from repro.optim import make_optimizer
from benchmarks.common import mlp_init, mlp_loss
from repro.launch.mesh import make_hier_engine_mesh

dim, ncls, width, M, tau = 16, 4, 8, 8, 4
key = jax.random.PRNGKey(0)
opt = make_optimizer("sgd", momentum=0.9)
p0 = lambda k: mlp_init(k, dim, ncls, width)
def batches(seed):
    k = jax.random.PRNGKey(seed)
    return {"x": jax.random.normal(k, (tau, M, 8, dim)),
            "y": jax.random.randint(jax.random.fold_in(k, 1),
                                    (tau, M, 8), 0, ncls)}

fmesh = Mesh(np.asarray(jax.devices()).reshape(8, 1), ("data", "model"))
fplan = MeshPlan(worker_axes=("data",), model_axes=("model",))
hmesh, hplan = make_hier_engine_mesh(2, 2, 2)
MK = ("consensus_dist", "pre_dist", "pull_force", "push_force",
      "train_loss", "lam_t", "staleness")

def run_pair(mesh, plan, dcfg_s1, dcfg_db, engine_patch=None, rounds=4):
    st0 = init_train_state(p0, opt, dcfg_s1, M, key)
    if engine_patch:
        st0 = dataclasses.replace(
            st0, engine=dataclasses.replace(st0.engine, **engine_patch))
    st1 = shard_train_state(st0, mesh, plan)
    st2 = shard_train_state(st0, mesh, plan)
    f1 = jax.jit(make_sharded_round_step(mlp_loss, opt, dcfg_s1, mesh=mesh,
                                         plan=plan, base_lr=0.05,
                                         total_steps=40))
    f2 = jax.jit(make_sharded_round_step(mlp_loss, opt, dcfg_db, mesh=mesh,
                                         plan=plan, base_lr=0.05,
                                         total_steps=40))
    b0 = batches(0)          # warm both through one staleness1 round
    st1, _ = f1(st1, b0)
    st2, _ = f1(st2, b0)
    for r in range(1, rounds):
        b = batches(r)
        st1, m1 = f1(st1, b)
        st2, m2 = f2(st2, b)
    dp = float(jnp.max(jnp.abs(st1.params - st2.params)))
    ds = float(jnp.max(jnp.abs(st1.snap["x"] - st2.snap["x"])))
    dm = max(abs(float(m1[k]) - float(m2[k])) for k in MK)
    return dp, ds, dm

# ddp carries no overlap snapshot (init_train_state rejects it): the bar
# covers the five consensus methods
for mname, mesh, plan in (("flat8x1", fmesh, fplan),
                          ("hier2x2x2", hmesh, hplan)):
    for method in ("simple_avg", "hard", "easgd", "lsgd", "mgrawa"):
        base = dict(alpha=0.2, lam=0.4, tau=tau, consensus=method,
                    engine="flat", lam_schedule="fixed")
        d_s1 = DPPFConfig(overlap="staleness1", **base)
        d_db1 = DPPFConfig(overlap="doublebuf", overlap_chunks=1, **base)
        d_db4 = DPPFConfig(overlap="doublebuf", overlap_chunks=4, **base)
        dp, ds, dm = run_pair(mesh, plan, d_s1, d_db1,
                              engine_patch={"precise": True})
        assert dp <= 1e-7 and ds <= 1e-7 and dm <= 1e-6, \
            (mname, method, "precise", dp, ds, dm)
        # fast mode + chunked dispatch: within the documented Gram floor
        dp, ds, dm = run_pair(mesh, plan, d_s1, d_db4)
        assert dp < 2e-5 and dm < 1e-4, (mname, method, "fast", dp, ds, dm)
print("doublebuf parity OK")

# kernel path: per-chunk partial_gram emission + mix_from_gram epilogue
base = dict(alpha=0.2, lam=0.4, tau=tau, engine="flat",
            lam_schedule="fixed")
d_s1 = DPPFConfig(overlap="staleness1", **base)
d_db = DPPFConfig(overlap="doublebuf", overlap_chunks=2, **base)
dp, ds, dm = run_pair(hmesh, hplan, d_s1, d_db,
                      engine_patch={"use_kernel": True, "interpret": True})
assert dp < 2e-5 and dm < 1e-4, ("kernel", dp, ds, dm)
print("doublebuf kernel OK")

# sharded round-0 bubble: doublebuf round 0 == the exact sharded round
d_ex = DPPFConfig(**base)
d_db = DPPFConfig(overlap="doublebuf", overlap_chunks=4, **base)
st0 = init_train_state(p0, opt, d_db, M, key, same_init=False)
st_ex0 = dataclasses.replace(st0, snap=None)
st_db = shard_train_state(st0, hmesh, hplan)
st_ex = shard_train_state(st_ex0, hmesh, hplan)
f_db = jax.jit(make_sharded_round_step(mlp_loss, opt, d_db, mesh=hmesh,
                                       plan=hplan, base_lr=0.05,
                                       total_steps=40))
f_ex = jax.jit(make_sharded_round_step(mlp_loss, opt, d_ex, mesh=hmesh,
                                       plan=hplan, base_lr=0.05,
                                       total_steps=40))
st_db, m_db = f_db(st_db, batches(0))
st_ex, _ = f_ex(st_ex, batches(0))
dp = float(jnp.max(jnp.abs(st_db.params - st_ex.params)))
assert dp <= 1e-6 and float(m_db["staleness"]) == 0.0, (dp, m_db)
st_db, m_db = f_db(st_db, batches(1))
assert float(m_db["staleness"]) == 1.0
print("doublebuf bubble OK")
print("ALL OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep + ROOT)
    out = subprocess.run([sys.executable, "-c", body], capture_output=True,
                         text=True, env=env, timeout=560, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ALL OK" in out.stdout


@pytest.mark.slow
def test_hierarchical_2x2x2_parity_and_cross_mesh_resume_8dev():
    """The acceptance leg (ISSUE 4): on 8 forced host devices, a 2x2x2
    workers x fsdp x model round — the partial-Gram psum spanning BOTH
    column axes, aux rows + fsdp column shards together — is bit-for-bit
    equal to the flat 8x1 row-sharded round in precise mode for every
    consensus method (<= 1 ulp of fp32; lsgd's argmin sees ulp-level loss
    inputs), within the Gram floor in fast mode, kernel path included;
    and a checkpoint saved mid-run on the 2x2x2 mesh resumes onto the 8x1
    mesh bit-for-bit (mesh-shape-independent checkpoints)."""
    body = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.checkpoint import load_train_state, save_train_state
from repro.configs import DPPFConfig, MeshPlan
from repro.core import consensus
from repro.train import (init_train_state, make_sharded_round_step,
                         shard_train_state)
from repro.optim import make_optimizer
from benchmarks.common import mlp_init, mlp_loss

dim, ncls, width, M, tau = 16, 4, 8, 8, 2
key = jax.random.PRNGKey(0)
opt = make_optimizer("sgd", momentum=0.9)
p0 = lambda k: mlp_init(k, dim, ncls, width)
def batches(seed):
    k = jax.random.PRNGKey(seed)
    return {"x": jax.random.normal(k, (tau, M, 8, dim)),
            "y": jax.random.randint(jax.random.fold_in(k, 1),
                                    (tau, M, 8), 0, ncls)}

from repro.launch.mesh import flat_col_axes, make_hier_engine_mesh
hmesh, hplan = make_hier_engine_mesh(2, 2, 2)
fmesh = Mesh(np.asarray(jax.devices()).reshape(8, 1), ("data", "model"))
fplan = MeshPlan(worker_axes=("data",), model_axes=("model",))
MKEYS = ("consensus_dist", "pre_dist", "pull_force", "push_force",
         "train_loss", "lam_t")

def run_pair(dcfg, engine_patch=None, rounds=2):
    st0 = init_train_state(p0, opt, dcfg, M, key)
    if st0.engine is None:  # ddp: reuse the simple_avg layout (aux = 0)
        st0 = init_train_state(
            p0, opt, dataclasses.replace(dcfg, consensus="simple_avg"),
            M, key)
    if engine_patch:
        st0 = dataclasses.replace(
            st0, engine=dataclasses.replace(st0.engine, **engine_patch))
    # the column group must really span both axes (4 shards)
    assert flat_col_axes(hmesh, st0.engine.layout.n, hplan) == \
        ("fsdp", "model")
    st1 = shard_train_state(st0, hmesh, hplan)
    st2 = shard_train_state(st0, fmesh, fplan)
    f1 = jax.jit(make_sharded_round_step(mlp_loss, opt, dcfg, mesh=hmesh,
                                         plan=hplan, base_lr=0.05,
                                         total_steps=20))
    f2 = jax.jit(make_sharded_round_step(mlp_loss, opt, dcfg, mesh=fmesh,
                                         plan=fplan, base_lr=0.05,
                                         total_steps=20))
    for r in range(rounds):
        st1, m1 = f1(st1, batches(r))
        st2, m2 = f2(st2, batches(r))
    dp = float(jnp.max(jnp.abs(st1.params - st2.params)))
    dm = max(abs(float(m1[k]) - float(m2[k])) for k in MKEYS)
    return dp, dm

for method in consensus.METHODS:
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, consensus=method,
                      engine="flat")
    dp, dm = run_pair(dcfg, engine_patch={"precise": True})
    # bit-for-bit up to reduction-order ulps in the (R, R) psums
    assert dp <= 1e-7 and dm < 1e-5, (method, "precise", dp, dm)
    dp, dm = run_pair(dcfg)
    assert dp < 2e-5 and dm < 1e-4, (method, "fast", dp, dm)
print("hier parity OK")

# kernel path: partial_gram/mix_shard + psum epilogue over BOTH axes
dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="flat")
dp, dm = run_pair(dcfg, engine_patch={"use_kernel": True, "interpret": True})
assert dp < 2e-5 and dm < 1e-4, ("kernel", dp, dm)
print("hier kernel OK")

# cross-mesh resume: 2 rounds on 2x2x2, save, resume on 8x1, 2 more
# rounds on each -> identical params/opt (checkpoints gather to host and
# reshard on load)
dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, consensus="easgd",
                  engine="flat")
st0 = init_train_state(p0, opt, dcfg, M, key)
st0 = dataclasses.replace(
    st0, engine=dataclasses.replace(st0.engine, precise=True))
f_h = jax.jit(make_sharded_round_step(mlp_loss, opt, dcfg, mesh=hmesh,
                                      plan=hplan, base_lr=0.05,
                                      total_steps=20))
f_f = jax.jit(make_sharded_round_step(mlp_loss, opt, dcfg, mesh=fmesh,
                                      plan=fplan, base_lr=0.05,
                                      total_steps=20))
st_h = shard_train_state(st0, hmesh, hplan)
for r in range(2):
    st_h, _ = f_h(st_h, batches(r))
with tempfile.TemporaryDirectory() as d:
    path = os.path.join(d, "hier.npz")
    save_train_state(path, st_h)
    template = dataclasses.replace(
        init_train_state(p0, opt, dcfg, M, key), engine=st_h.engine)
    st_f = shard_train_state(load_train_state(path, template), fmesh, fplan)
assert int(st_f.t) == 2 * tau and int(st_f.round) == 2
for r in range(2, 4):
    st_h, _ = f_h(st_h, batches(r))
    st_f, _ = f_f(st_f, batches(r))
# the two continuations run on different mesh shapes, so the (R, R) psum
# reduction order differs: ulp-level agreement, same bound as the parity
# legs above
np.testing.assert_allclose(np.asarray(st_h.params),
                           np.asarray(st_f.params), atol=1e-6, rtol=0)
jax.tree.map(lambda a, b: np.testing.assert_allclose(
    np.asarray(a), np.asarray(b), atol=1e-6, rtol=0), st_h.opt, st_f.opt)
print("cross-mesh resume OK")
print("ALL OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep + ROOT)
    out = subprocess.run([sys.executable, "-c", body], capture_output=True,
                         text=True, env=env, timeout=560, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ALL OK" in out.stdout

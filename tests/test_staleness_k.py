"""Staleness-k ring-pipelined consensus: the k-deep snapshot ring as the
generalization of the two-buffer doublebuf recursion (k=1 bit-parity), the
explicit k-buffer reference, the ppermute ring gather's concatenation-order
contract, bounded-async elastic rounds (drop / freeze / forced rejoin /
EASGD-style catch-up), and checkpoint resume mid-pipeline.

Multi-device legs run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (same pattern as
test_sharded_round.py); single-device tests exercise the identical traced
code path in-process."""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_train_state, save_train_state
from repro.configs import DPPFConfig
from repro.core import consensus
from repro.optim import make_optimizer
from repro.train import (
    RoundClock, init_train_state, make_round_step, set_participation,
)

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
# config plumbing
# ---------------------------------------------------------------------------

def test_staleness_k_config_validation():
    with pytest.raises(ValueError, match="staleness_k"):
        DPPFConfig(engine="tree", overlap="staleness_k")
    with pytest.raises(ValueError, match="staleness"):
        DPPFConfig(engine="flat", overlap="staleness_k", staleness=0)
    # elastic rides the staleness_k carry only
    with pytest.raises(ValueError, match="elastic"):
        DPPFConfig(engine="flat", overlap="doublebuf", elastic=True)
    with pytest.raises(ValueError, match="exact_second_term"):
        DPPFConfig(engine="flat", overlap="staleness_k", elastic=True,
                   exact_second_term=True)
    with pytest.raises(ValueError, match="elastic_catchup"):
        DPPFConfig(engine="flat", overlap="staleness_k", elastic=True,
                   elastic_catchup=1.5)
    dcfg = DPPFConfig(engine="flat", overlap="staleness_k", staleness=3,
                      elastic=True)
    assert dcfg.staleness == 3 and dcfg.elastic


def test_staleness_k_ring_state_shape():
    """init builds the (k, R, n) ring — every slot the init fleet — and
    the elastic carry (participation ring + membership + missed counter)
    only when requested."""
    M, k = 4, 3
    opt, p0, _, _ = _mlp_setup(M=M)
    dcfg = DPPFConfig(engine="flat", overlap="staleness_k", staleness=k)
    st = init_train_state(p0, opt, dcfg, M, jax.random.PRNGKey(0))
    assert st.snap["x"].shape == (k,) + st.params.shape
    np.testing.assert_array_equal(np.asarray(st.snap["x"][0]),
                                  np.asarray(st.snap["x"][k - 1]))
    assert st.snap["losses"].shape == (k, M)
    assert "active" not in st.snap
    st_e = init_train_state(
        p0, opt, dataclasses.replace(dcfg, elastic=True), M,
        jax.random.PRNGKey(0))
    assert st_e.snap["act"].shape == (k, M)
    assert st_e.snap["active"].shape == (M,)
    assert st_e.snap["missed"].shape == (M,)
    assert st_e.snap["missed"].dtype == jnp.int32


# ---------------------------------------------------------------------------
# k=1 == doublebuf, and the explicit k-buffer reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method",
                         ["simple_avg", "hard", "easgd", "lsgd", "mgrawa"])
def test_staleness_k1_bitwise_equals_doublebuf(method):
    """The acceptance bar's single-device half: staleness_k with k=1 and
    one chunk IS the doublebuf recursion — same exact-consensus fill
    round, same stale delta, same snapshot advance — bit-for-bit in
    precise mode from init, for every consensus method (easgd's aux row
    rides the ring too). The staleness metric counts depth, not a flag."""
    M, tau = 4, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    base = dict(alpha=0.2, lam=0.4, tau=tau, consensus=method,
                engine="flat", lam_schedule="fixed")
    d_db = DPPFConfig(overlap="doublebuf", overlap_chunks=1, **base)
    d_k1 = DPPFConfig(overlap="staleness_k", staleness=1, overlap_chunks=1,
                      **base)
    key = jax.random.PRNGKey(0)
    sts, fns, ms = [], [], [None, None]
    for d in (d_db, d_k1):
        st = init_train_state(p0, opt, d, M, key)
        st = dataclasses.replace(
            st, engine=dataclasses.replace(st.engine, precise=True))
        sts.append(st)
        fns.append(jax.jit(make_round_step(loss, opt, d, base_lr=0.05,
                                           total_steps=20)))
    for r in range(4):
        b = batches(r)
        for i in range(2):
            sts[i], ms[i] = fns[i](sts[i], b)
        dp = float(jnp.max(jnp.abs(sts[0].params - sts[1].params)))
        ds = float(jnp.max(jnp.abs(sts[0].snap["x"] - sts[1].snap["x"][0])))
        assert dp == 0.0 and ds == 0.0, (method, r, dp, ds)
        assert float(ms[0]["staleness"]) == float(ms[1]["staleness"]) \
            == (0.0 if r == 0 else 1.0)


@pytest.mark.parametrize("method", ["simple_avg", "easgd"])
def test_staleness_k_matches_k_buffer_reference(method):
    """The fused staleness-k round against the explicit k-buffer scheme
    (k=2): rounds 0..k-1 are exact-consensus pipeline fill
    x_{r+1} = C(q_r); from round k on, x_{r+1} = q_r + (C(s_{r-k}) -
    s_{r-k}) with the ring advanced by one snapshot per round."""
    M, tau, k = 4, 2, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, consensus=method,
                      engine="flat", overlap="staleness_k", staleness=k,
                      overlap_chunks=1, lam_schedule="fixed")
    key = jax.random.PRNGKey(0)
    st = init_train_state(p0, opt, dcfg, M, key)
    eng = st.engine
    step = jax.jit(make_round_step(loss, opt, dcfg, base_lr=0.05,
                                   total_steps=20))

    # reference: pure local steps via an identity-consensus (ddp) round on
    # the same engine, the ring and the stale delta maintained by hand
    from repro.train.trainer import TrainState
    dcfg_local = dataclasses.replace(dcfg, consensus="ddp", overlap="none",
                                     staleness=1)
    local_only = jax.jit(make_round_step(loss, opt, dcfg_local, base_lr=0.05,
                                         total_steps=20))
    st_ref = TrainState(params=st.params + 0.0,
                        opt=jax.tree.map(jnp.copy, st.opt),
                        cstate={}, t=st.t, engine=eng)
    ring = [st.params + 0.0 for _ in range(k)]
    cstate = {}
    for r in range(5):
        b = batches(r)
        st, m = step(st, b)
        st_ref, _ = local_only(st_ref, b)
        q = st_ref.params
        if r >= k:
            s_old = ring[0]
            c_out, cstate, _ = consensus.apply_round(
                s_old, dcfg, float(m["lam_t"]), cstate, engine=eng)
            new_x = q + (c_out - s_old)
            assert float(m["staleness"]) == k
        else:
            c_out, cstate, _ = consensus.apply_round(
                q, dcfg, float(m["lam_t"]), cstate, engine=eng)
            new_x = c_out
            assert float(m["staleness"]) == 0.0
        st_ref = dataclasses.replace(st_ref, params=new_x)
        ring = ring[1:] + [q]
        np.testing.assert_allclose(np.asarray(st.params),
                                   np.asarray(st_ref.params),
                                   atol=1e-5, rtol=1e-5, err_msg=f"round {r}")
        np.testing.assert_allclose(np.asarray(st.snap["x"][0]),
                                   np.asarray(ring[0]), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# elastic: masked lowering unit + drop/freeze/rejoin through the round
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method",
                         ["simple_avg", "easgd", "lsgd", "mgrawa"])
def test_lower_stages_elastic_mask(method):
    """The row-stochastic lowering under a participation mask: inactive
    worker rows get zero pull/push coefficients (their flat-view row
    passes through each mixing stage bit-exactly), active target weights
    renormalize, aux rows keep their coefficients; exact_second_term
    stages refuse the mask."""
    M = 4
    opt, p0, _, _ = _mlp_setup(M=M)
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, consensus=method, engine="flat")
    st = init_train_state(p0, opt, dataclasses.replace(
        dcfg, overlap="staleness1"), M, jax.random.PRNGKey(0))
    eng = st.engine
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    kw = {}
    if method == "lsgd":
        kw["losses"] = jnp.asarray([3.0, 2.0, 0.1, 4.0])
    if method == "mgrawa":
        kw["grad_norms"] = jnp.ones((M,))
    stages, _ = consensus.lower_stages(eng, dcfg, 0.3, mask=mask, **kw)
    assert stages, method
    for kind, T, c0, c1 in stages:
        assert kind == "coef"
        # dropped row 2 neither pulls nor pushes
        assert float(c0[2]) == 0.0 and float(c1[2]) == 0.0
        # surviving target weights renormalize (row-stochastic over the
        # ACTIVE workers — easgd splits the mass with its aux center row)
        # and the dropped worker never appears as a target
        w_row = np.asarray(T[0])
        if w_row.sum() > 0:
            assert abs(w_row.sum() - 1.0) < 1e-6
            assert w_row[2] == 0.0
    if method == "lsgd":
        # the masked argmin skips row 2's (smallest) loss: row 1 leads
        T1 = stages[0][1]
        assert float(T1[0][1]) == 1.0 and float(T1[0][2]) == 0.0
    if method == "easgd" and eng.layout.aux:
        # the center row keeps its coefficient (tracks the ACTIVE mean)
        assert float(stages[0][2][M]) > 0.0
    with pytest.raises(ValueError, match="exact_second_term"):
        consensus.lower_stages(
            eng, dataclasses.replace(dcfg, consensus="simple_avg",
                                     exact_second_term=True),
            0.3, mask=mask)


def test_elastic_drop_freeze_and_forced_rejoin():
    """Bounded-async semantics through the traced round: a dropped row's
    worker params freeze bit-exactly (local steps reverted, no stale
    delta received), the missed counter rides the carry, and after k
    missed rounds the bounded-staleness clamp forces the row back in with
    an EASGD-style catch-up pull toward the active mean."""
    M, tau, k = 4, 2, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="flat",
                      overlap="staleness_k", staleness=k, elastic=True,
                      elastic_catchup=0.5, lam_schedule="fixed")
    st = init_train_state(p0, opt, dcfg, M, jax.random.PRNGKey(0))
    step = jax.jit(make_round_step(loss, opt, dcfg, base_lr=0.05,
                                   total_steps=40))
    frozen_row = None
    for r in range(6):
        mask = np.ones(M, np.float32)
        if r in (2, 3, 4):          # requested out for 3 rounds > k
            mask[1] = 0.0
        st = set_participation(st, jnp.asarray(mask))
        before = np.asarray(st.engine.workers(st.params)[1])
        st, m = step(st, batches(r))
        after = np.asarray(st.engine.workers(st.params)[1])
        missed = int(st.snap["missed"][1])
        if r in (2, 3):
            np.testing.assert_array_equal(before, after)
            assert missed == r - 1
            frozen_row = after
        elif r == 4:
            # k rounds missed -> the clamp forces eff=1 despite the
            # requested drop: the row moves again and the counter resets
            assert np.abs(after - frozen_row).max() > 0.0
            assert missed == 0
        else:
            assert missed == 0
    assert np.isfinite(np.asarray(st.params)).all()
    # other rows never froze
    assert float(m["train_loss"]) < 10.0


def test_set_participation_validates():
    M = 4
    opt, p0, _, _ = _mlp_setup(M=M)
    st = init_train_state(
        p0, opt, DPPFConfig(engine="flat", overlap="staleness_k",
                            staleness=2), M, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="elastic"):
        set_participation(st, jnp.ones((M,)))
    st_e = init_train_state(
        p0, opt, DPPFConfig(engine="flat", overlap="staleness_k",
                            staleness=2, elastic=True), M,
        jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="shape"):
        set_participation(st_e, jnp.ones((M + 1,)))
    out = set_participation(st_e, jnp.zeros((M,)))
    np.testing.assert_array_equal(np.asarray(out.snap["active"]),
                                  np.zeros(M))


# ---------------------------------------------------------------------------
# checkpoint: resume mid-pipeline (fill and steady state)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stop_round", [1, 3])
def test_checkpoint_resume_mid_pipeline(tmp_path, stop_round):
    """A staleness-k (k=2) run checkpointed mid-pipeline — during the
    exact-consensus fill (round 1 < k) and in the steady stale state
    (round 3 >= k) — resumes bit-for-bit: the ring, the carried round
    index (which gates the fill cond), and the clock position all
    round-trip through the npz."""
    M, tau, k = 4, 2, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="flat",
                      overlap="staleness_k", staleness=k,
                      lam_schedule="fixed")
    clock = RoundClock.from_config(dcfg, base_lr=0.05, total_steps=12)
    step = jax.jit(make_round_step(loss, opt, dcfg, clock=clock))
    key = jax.random.PRNGKey(0)

    st_full = init_train_state(p0, opt, dcfg, M, key)
    st_half = init_train_state(p0, opt, dcfg, M, key)
    for r in range(6):
        st_full, _ = step(st_full, batches(r))
        if r < stop_round:
            st_half, _ = step(st_half, batches(r))
    path = str(tmp_path / "mid.npz")
    save_train_state(path, st_half)
    like = init_train_state(p0, opt, dcfg, M, key)
    st_res = load_train_state(path, like, clock=clock)
    assert int(st_res.round) == stop_round
    np.testing.assert_array_equal(np.asarray(st_res.snap["x"]),
                                  np.asarray(st_half.snap["x"]))
    for r in range(stop_round, 6):
        st_res, m = step(st_res, batches(r))
    assert float(m["staleness"]) == k
    np.testing.assert_allclose(np.asarray(st_res.params),
                               np.asarray(st_full.params), atol=1e-7,
                               rtol=0)


def test_checkpoint_snapless_resume_broadcasts_ring(tmp_path):
    """An exact-mode checkpoint (no snapshot) resuming into a staleness-k
    run warm-starts EVERY ring slot with the restored params (the 3-D
    generalization of the staleness-1 fallback)."""
    M, tau, k = 4, 2, 3
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    d_ex = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="flat",
                      lam_schedule="fixed")
    st = init_train_state(p0, opt, d_ex, M, jax.random.PRNGKey(0))
    st, _ = jax.jit(make_round_step(loss, opt, d_ex, base_lr=0.05,
                                    total_steps=20))(st, batches(0))
    path = str(tmp_path / "exact.npz")
    save_train_state(path, st)
    d_k = dataclasses.replace(d_ex, overlap="staleness_k", staleness=k)
    like = init_train_state(p0, opt, d_k, M, jax.random.PRNGKey(1))
    st_res = load_train_state(path, like)
    assert st_res.snap["x"].shape == (k,) + st.params.shape
    for slot in range(k):
        np.testing.assert_array_equal(np.asarray(st_res.snap["x"][slot]),
                                      np.asarray(st.params))


# ---------------------------------------------------------------------------
# 8-device legs: ring-gather contract + sharded parity + elastic
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ring_gather_matches_all_gather_8dev():
    """The ppermute ring delivers the SAME assembled view as one tiled
    all_gather — bit-for-bit, every block in row-major worker order (the
    concatenation-order contract precise mode rests on) — including
    non-unit per-device blocks; multi-axis groups fall back to
    all_gather."""
    body = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_flat_engine_mesh, ring_gather

mesh, plan = make_flat_engine_mesh(8)
for m_loc in (1, 3):
    x = jnp.arange(8 * m_loc * 5, dtype=jnp.float32).reshape(8 * m_loc, 5)
    def both(v):
        r = ring_gather(v, ("data",), world=8, axis=0)
        g = jax.lax.all_gather(v, ("data",), axis=0, tiled=True)
        return r, g
    r, g = jax.shard_map(both, mesh=mesh, in_specs=P("data", None),
                     out_specs=P(None, None), check_vma=False)(x)
    assert np.array_equal(np.asarray(r), np.asarray(g)), m_loc
    assert np.array_equal(np.asarray(r), np.asarray(x)), m_loc
print("ALL OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep + ROOT)
    out = subprocess.run([sys.executable, "-c", body], capture_output=True,
                         text=True, env=env, timeout=560, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ALL OK" in out.stdout


@pytest.mark.slow
def test_staleness_k_parity_8dev_flat_and_hier():
    """THE staleness-k acceptance leg: on 8 forced host devices,
    staleness_k(k=1, one chunk) is bit-for-bit doublebuf(one chunk) in
    precise mode (<= 1e-7; exact-zero in practice) for every consensus
    method incl. the easgd aux row, on BOTH the flat 8x1 mesh (where the
    mid-scan gather really runs the ppermute ring) and the hier 2x2x2
    mesh; a k=2 sharded run matches the single-device trace; and an
    elastic drop/rejoin schedule agrees across the sharded and
    single-device paths."""
    body = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs import DPPFConfig, MeshPlan
from repro.train import (init_train_state, make_round_step,
                         make_sharded_round_step, set_participation,
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

def run(dcfg, mesh=None, plan=None, rounds=4, drop=None):
    st = init_train_state(p0, opt, dcfg, M, key)
    st = dataclasses.replace(
        st, engine=dataclasses.replace(st.engine, precise=True))
    if mesh is not None:
        st = shard_train_state(st, mesh, plan, dcfg=dcfg)
        fn = jax.jit(make_sharded_round_step(
            mlp_loss, opt, dcfg, mesh=mesh, plan=plan, base_lr=0.05,
            total_steps=40))
    else:
        fn = jax.jit(make_round_step(mlp_loss, opt, dcfg, base_lr=0.05,
                                     total_steps=40))
    m = None
    for r in range(rounds):
        if drop:
            mask = np.ones(M, np.float32)
            if r in drop[1]:
                mask[drop[0]] = 0.0
            st = set_participation(st, jnp.asarray(mask))
        st, m = fn(st, batches(r))
    return st, m

# k=1 == doublebuf bitwise, both meshes, all five methods
for mname, mesh, plan in (("flat8x1", fmesh, fplan),
                          ("hier2x2x2", hmesh, hplan)):
    for method in ("simple_avg", "hard", "easgd", "lsgd", "mgrawa"):
        base = dict(alpha=0.2, lam=0.4, tau=tau, consensus=method,
                    engine="flat", lam_schedule="fixed")
        s_db, m_db = run(DPPFConfig(overlap="doublebuf", overlap_chunks=1,
                                    **base), mesh, plan)
        s_k1, m_k1 = run(DPPFConfig(overlap="staleness_k", staleness=1,
                                    overlap_chunks=1, **base), mesh, plan)
        dp = float(jnp.max(jnp.abs(s_db.params - s_k1.params)))
        ds = float(jnp.max(jnp.abs(s_db.snap["x"] - s_k1.snap["x"][0])))
        assert dp <= 1e-7 and ds <= 1e-7, (mname, method, dp, ds)
        assert float(m_db["staleness"]) == float(m_k1["staleness"]) == 1.0
print("k1 parity OK")

# k=2 sharded (ring gather over 8 worker rows) == single-device trace
base = dict(alpha=0.2, lam=0.4, tau=tau, engine="flat",
            lam_schedule="fixed")
d_k2 = DPPFConfig(overlap="staleness_k", staleness=2, overlap_chunks=2,
                  **base)
s_sh, m_sh = run(d_k2, fmesh, fplan, rounds=5)
s_1d, m_1d = run(d_k2, rounds=5)
dp = float(jnp.max(jnp.abs(s_sh.params - s_1d.params)))
assert dp <= 1e-6, dp
assert float(m_sh["staleness"]) == float(m_1d["staleness"]) == 2.0
print("k2 sharded OK")

# elastic drop/rejoin: sharded == single-device
d_el = DPPFConfig(overlap="staleness_k", staleness=2, overlap_chunks=2,
                  elastic=True, elastic_catchup=0.5, **base)
s_a, _ = run(d_el, rounds=6, drop=(5, (2, 3)))
s_b, _ = run(d_el, hmesh, hplan, rounds=6, drop=(5, (2, 3)))
dp = float(jnp.max(jnp.abs(s_a.params - s_b.params)))
assert dp <= 2e-6, dp
assert np.isfinite(np.asarray(s_b.params)).all()
print("elastic OK")
print("ALL OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep + ROOT)
    out = subprocess.run([sys.executable, "-c", body], capture_output=True,
                         text=True, env=env, timeout=560, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ALL OK" in out.stdout


def test_elastic_rejoin_across_checkpoint_resume(tmp_path):
    """Elastic membership state survives a checkpoint boundary: a run
    saved MID-DROP (worker 1 out, missed counter live, catch-up still
    ahead) resumes bit-for-bit against the uninterrupted run — the
    participation ring, the missed counters, the new scalar ``sync``
    gate, and the EASGD catch-up pull all round-trip through the npz."""
    tau, k = 2, 2
    Mw = 4
    opt, p0, loss, batches = _mlp_setup(M=Mw, tau=tau)
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="flat",
                      overlap="staleness_k", staleness=k, elastic=True,
                      elastic_catchup=0.5, lam_schedule="fixed")
    clock = RoundClock.from_config(dcfg, base_lr=0.05, total_steps=12)
    step = jax.jit(make_round_step(loss, opt, dcfg, clock=clock))
    key = jax.random.PRNGKey(0)

    def mask(r):
        m = np.ones(Mw, np.float32)
        if r in (2, 3):                    # dropped across the save point
            m[1] = 0.0
        return jnp.asarray(m)

    full = init_train_state(p0, opt, dcfg, Mw, key)
    half = init_train_state(p0, opt, dcfg, Mw, key)
    for r in range(6):
        full = set_participation(full, mask(r))
        full, _ = step(full, batches(r))
        if r < 3:
            half = set_participation(half, mask(r))
            half, _ = step(half, batches(r))
    # checkpoint after round 2: worker 1 has missed one round and is
    # still inside its drop window
    assert int(half.snap["missed"][1]) == 1
    path = str(tmp_path / "middrop.npz")
    save_train_state(path, half)
    like = init_train_state(p0, opt, dcfg, Mw, key)
    res = load_train_state(path, like, clock=clock)
    assert int(res.round) == 3
    assert int(res.snap["missed"][1]) == 1
    assert float(res.snap["sync"]) == 1.0  # the quorum gate round-trips
    np.testing.assert_array_equal(np.asarray(res.snap["active"]),
                                  np.asarray(half.snap["active"]))
    # finish the drop window and the rejoin catch-up post-resume
    for r in range(3, 6):
        res = set_participation(res, mask(r))
        res, _ = step(res, batches(r))
    np.testing.assert_array_equal(np.asarray(res.params),
                                  np.asarray(full.params))
    np.testing.assert_array_equal(np.asarray(res.snap["missed"]),
                                  np.asarray(full.snap["missed"]))
    np.testing.assert_array_equal(np.asarray(res.snap["x"]),
                                  np.asarray(full.snap["x"]))


def test_elastic_convergence_single_device():
    """End-task sanity: an elastic run with a transient dropout stays
    finite and close to the always-on run (the drop is bounded by k)."""
    M, tau = 4, 2
    opt, p0, loss, batches = _mlp_setup(M=M, tau=tau)
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=tau, engine="flat",
                      overlap="staleness_k", staleness=2, elastic=True,
                      lam_schedule="fixed")
    step = jax.jit(make_round_step(loss, opt, dcfg, base_lr=0.05,
                                   total_steps=40))
    losses = {}
    for drop in (False, True):
        st = init_train_state(p0, opt, dcfg, M, jax.random.PRNGKey(0))
        for r in range(10):
            mask = np.ones(M, np.float32)
            if drop and r in (3, 4):
                mask[2] = 0.0
            st = set_participation(st, jnp.asarray(mask))
            st, m = step(st, batches(r))
        losses[drop] = float(m["train_loss"])
        assert np.isfinite(np.asarray(st.params)).all()
    assert abs(losses[True] - losses[False]) < 1.0, losses

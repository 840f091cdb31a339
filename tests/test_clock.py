"""RoundClock: the lam-schedule off-by-one regression (round 0 sees
``lam_schedule(·, 0, T)``, the final round the full lam, in EVERY round
builder), QSR adaptive tau (constant-tau runs bit-for-bit equal to fixed
tau; adaptive runs save rounds at matching loss), remainder-step
accounting, checkpointed clock position, and the serving ``generate``
edge cases (max_new_tokens=1; first-sample key vs the fold-in chain)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import mlp_init, mlp_loss
from repro.checkpoint import load_train_state, save_train_state
from repro.configs import DPPFConfig, MeshPlan
from repro.core.schedules import lam_schedule
from repro.optim import make_optimizer
from repro.train import (
    RoundClock, init_train_state, make_round_step, make_sharded_round_step,
    shard_train_state,
)
from repro.train.trainer import TrainState

LAM = 0.5


def _setup(M=4, dim=16, ncls=4, width=8):
    opt = make_optimizer("sgd", momentum=0.9)
    p0 = lambda k: mlp_init(k, dim, ncls, width)

    def batch(tau, start):
        k = jax.random.fold_in(jax.random.PRNGKey(7), start)
        return {"x": jax.random.normal(k, (tau, M, 8, dim)),
                "y": jax.random.randint(jax.random.fold_in(k, 1),
                                        (tau, M, 8), 0, ncls)}
    return opt, p0, mlp_loss, batch


# ---------------------------------------------------------------------------
# the round plan
# ---------------------------------------------------------------------------

def test_round_plan_fixed_with_remainder():
    clock = RoundClock(total_steps=10, tau=4)
    assert [(s.index, s.start, s.tau) for s in clock.rounds] == [
        (0, 0, 4), (1, 4, 4), (2, 8, 2)]          # remainder runs, 10 == 10
    assert clock.total_rounds == 3 == clock.fixed_rounds
    assert sum(clock.taus()) == 10
    assert clock.round_of_step(0) == 0
    assert clock.round_of_step(4) == 1
    assert clock.round_of_step(9) == 2
    assert clock.round_of_step(10) == 3           # finished
    with pytest.raises(ValueError):
        clock.round_of_step(11)


def test_describe_returns_full_round_plan():
    """describe() carries the per-round plan (the dry-run report table and
    the committed BENCH_roundclock.json baseline both render it); the
    docstring's worked QSR example is pinned here."""
    clock = RoundClock(total_steps=10, tau=4, base_lr=0.1, lam=0.5,
                       lam_kind="increasing")
    d = clock.describe()
    assert [(r["round"], r["start"], r["tau"]) for r in d["plan"]] == [
        (0, 0, 4), (1, 4, 4), (2, 8, 2)]
    # lam spans both endpoints: round 0 zero (increasing), last round full
    assert d["plan"][0]["lam"] == 0.0
    assert abs(d["plan"][-1]["lam"] - 0.5) < 1e-6
    # lam matches the traced read the builders use
    for r in d["plan"]:
        assert abs(r["lam"] - float(clock.lam_at(r["round"]))) < 1e-6
    # lr window: cosine from base_lr down toward 0
    assert abs(d["plan"][0]["lr_start"] - 0.1) < 1e-6
    assert d["plan"][-1]["lr_end"] < d["plan"][0]["lr_start"]
    for r in d["plan"]:
        assert abs(r["lr_start"] - float(clock.lr_at(r["start"]))) < 1e-6

    # the worked QSR example from the describe() docstring
    qsr = RoundClock(total_steps=64, tau=4, base_lr=0.3,
                     tau_schedule="qsr", qsr_beta=0.4)
    assert qsr.taus() == (4, 4, 4, 4, 4, 4, 4, 4, 7, 16, 9)
    dq = qsr.describe()
    assert dq["rounds"] == 11 and dq["fixed_rounds"] == 16
    assert dq["allreduces_saved"] == 5


def test_plan_table_renders_and_elides():
    clock = RoundClock(total_steps=10, tau=4, base_lr=0.1)
    table = clock.plan_table()
    assert "| round | start | tau | lam | lr window | staleness |" in table
    assert table.count("\n") == 2 + 3  # header x3 + one line per round
    long = RoundClock(total_steps=400, tau=4, base_lr=0.1)
    elided = long.plan_table(max_rows=6)
    assert "| ... |" in elided
    assert "| 0 | 0 | 4 |" in elided and "| 99 | 396 | 4 |" in elided


def test_qsr_warmup_rounds_keep_base_tau():
    """Warmup-aware QSR: the plan samples the FULL LR schedule. Rounds
    starting inside the warmup keep the base tau (the raw rule
    (beta/eta)^2 on the tiny warmup LR would blow tau up exactly when the
    model changes fastest) and never straddle the warmup boundary; the
    cosine-ruled plan takes over at ``warmup``. describe()/plan_table()
    mark the warmup rounds."""
    clock = RoundClock(total_steps=64, tau=4, base_lr=0.3, warmup=10,
                       tau_schedule="qsr", qsr_beta=0.4)
    taus = clock.taus()
    assert sum(taus) == 64
    # warmup covers steps 0..9: rounds (0,0,4), (1,4,4), (2,8,2) — the
    # third round is clipped at the boundary, NOT a huge QSR round
    assert [(s.start, s.tau) for s in clock.rounds[:3]] == [
        (0, 4), (4, 4), (8, 2)]
    assert clock.rounds[3].start == 10
    # without the warmup guard, eta(0) = 0 would still fall back to tau
    # but eta(1) ~ 0.03 gives (0.4/0.03)^2 ~ 178 — the guard is what
    # keeps every warmup-resident round at tau_base
    d = clock.describe()
    assert d["warmup"] == 10 and d["warmup_rounds"] == 3
    assert [r["warmup"] for r in d["plan"][:4]] == [True, True, True, False]
    table = clock.plan_table()
    assert "(warm)" in table and "warmup 10 steps = 3 rounds" in table
    # zero-warmup clocks render without the marker (back-compat)
    plain = RoundClock(total_steps=10, tau=4, base_lr=0.1)
    assert "(warm)" not in plain.plan_table()
    assert "warmup" not in plain.plan_table()


def test_qsr_overlap_uses_stale_lr():
    """Overlap-aware QSR: with a stale consensus, round k applies round
    k-1's iterate, so its tau is ruled by the PREVIOUS round's start LR.
    The plan stays host-static, covers every step, and lags the exact
    plan by exactly one round in its tau growth."""
    exact = RoundClock(total_steps=64, tau=4, base_lr=0.3,
                       tau_schedule="qsr", qsr_beta=0.4)
    for mode in ("staleness1", "doublebuf"):
        stale = RoundClock(total_steps=64, tau=4, base_lr=0.3,
                           tau_schedule="qsr", qsr_beta=0.4, overlap=mode)
        assert sum(stale.taus()) == 64
        assert stale.describe()["overlap"] == mode
        # the exact plan (docstring example) grows tau at step 32 (7) and
        # step 39 (16); the stale plan sizes those rounds from the
        # previous round's LR, so growth arrives one round later and the
        # stale plan pays at least as many rounds
        assert stale.total_rounds >= exact.total_rounds
        for spec, prev in zip(stale.rounds[1:], stale.rounds):
            from repro.core.schedules import qsr_tau
            from repro.train.clock import _host_cosine_lr
            eta_prev = _host_cosine_lr(0.3, prev.start, 64, 0)
            want = min(qsr_tau(eta_prev, 4, 0.4), 64 - spec.start)
            assert spec.tau == want, (spec, want)
    # overlap="none" keeps the pinned worked example untouched
    assert exact.taus() == (4, 4, 4, 4, 4, 4, 4, 4, 7, 16, 9)


def test_qsr_staleness_k_looks_back_k_rounds():
    """staleness_k QSR: round r applies round r-k's iterate, so its tau is
    ruled by the LR from k rounds back; k=1 reproduces the staleness1
    plan exactly, and describe()/plan_table() carry the depth (fill
    rounds 0..k-1 report depth 0)."""
    from repro.core.schedules import qsr_tau
    from repro.train.clock import _host_cosine_lr
    s1 = RoundClock(total_steps=64, tau=4, base_lr=0.3,
                    tau_schedule="qsr", qsr_beta=0.4, overlap="staleness1")
    k1 = RoundClock(total_steps=64, tau=4, base_lr=0.3,
                    tau_schedule="qsr", qsr_beta=0.4,
                    overlap="staleness_k", staleness=1)
    assert k1.taus() == s1.taus()
    assert k1.staleness_depth == 1 and s1.staleness_depth == 1
    k2 = RoundClock(total_steps=64, tau=4, base_lr=0.3,
                    tau_schedule="qsr", qsr_beta=0.4,
                    overlap="staleness_k", staleness=2)
    assert sum(k2.taus()) == 64 and k2.staleness_depth == 2
    for i, spec in enumerate(k2.rounds):
        if i < 2:
            continue
        eta = _host_cosine_lr(0.3, k2.rounds[i - 2].start, 64, 0)
        want = min(qsr_tau(eta, 4, 0.4), 64 - spec.start)
        assert spec.tau == want, (spec, want)
    d = k2.describe()
    assert d["overlap"] == "staleness_k" and d["staleness"] == 2
    assert [r["staleness"] for r in d["plan"][:3]] == [0, 0, 2]
    assert "(k=2)" in k2.plan_table()


def test_staleness_k_warmup_validation():
    """A k-deep pipeline needs at least k warmup rounds of exact fill:
    warmup shorter than k rounds raises; exactly k rounds passes."""
    with pytest.raises(ValueError, match="warmup"):
        RoundClock(total_steps=64, tau=4, base_lr=0.3, warmup=4,
                   overlap="staleness_k", staleness=2)
    clock = RoundClock(total_steps=64, tau=4, base_lr=0.3, warmup=8,
                       overlap="staleness_k", staleness=2)
    assert clock.describe()["warmup_rounds"] >= 2
    # depth validation rides the config path too
    with pytest.raises(ValueError, match="staleness"):
        DPPFConfig(engine="flat", overlap="staleness_k", staleness=0)
    # from_config plumbs the overlap mode through
    dcfg = DPPFConfig(tau=4, engine="flat", overlap="doublebuf",
                      tau_schedule="qsr", qsr_beta=0.4)
    c = RoundClock.from_config(dcfg, base_lr=0.3, total_steps=64)
    assert c.overlap == "doublebuf"


def test_round_plan_validation():
    with pytest.raises(ValueError, match="tau schedule"):
        RoundClock(total_steps=8, tau=4, tau_schedule="bogus")
    with pytest.raises(ValueError, match="overlap"):
        RoundClock(total_steps=8, tau=4, overlap="bogus")
    with pytest.raises(ValueError, match="warmup"):
        RoundClock(total_steps=8, tau=4, warmup=-2)
    with pytest.raises(ValueError, match="qsr_beta"):
        RoundClock(total_steps=8, tau=4, tau_schedule="qsr")
    with pytest.raises(ValueError, match="base_lr"):
        RoundClock(total_steps=8, tau=4, tau_schedule="qsr", qsr_beta=0.1)
    with pytest.raises(ValueError, match="total_steps"):
        RoundClock(total_steps=0, tau=4)


def test_qsr_plan_grows_tau_as_lr_decays():
    clock = RoundClock(total_steps=64, tau=4, base_lr=0.3, lam=LAM,
                       tau_schedule="qsr", qsr_beta=0.4)
    taus = clock.taus()
    assert sum(taus) == 64                        # every step accounted for
    assert taus[0] == 4                           # high lr -> tau_base
    assert max(taus) > 4                          # low lr -> longer rounds
    assert clock.total_rounds < clock.fixed_rounds
    d = clock.describe()
    assert d["allreduces_saved"] == clock.fixed_rounds - clock.total_rounds


def test_lam_at_endpoints():
    clock = RoundClock(total_steps=8, tau=2, lam=LAM, lam_kind="increasing")
    assert clock.total_rounds == 4
    assert float(clock.lam_at(0)) == 0.0          # round 0: lam_schedule(·,0,T)
    assert float(clock.lam_at(3)) == pytest.approx(LAM, rel=1e-6)
    # trajectory == lam_schedule evaluated over total_rounds - 1
    for k in range(4):
        assert float(clock.lam_at(k)) == pytest.approx(
            float(lam_schedule("increasing", LAM, k, 3)), rel=1e-6)


def test_lam_at_single_round_applies_full_lam():
    """A plan with ONE round has no trajectory to span: its only round is
    also the final round and must apply the full lam, not a silent zero
    push."""
    for kind in ("fixed", "increasing", "decreasing"):
        clock = RoundClock(total_steps=4, tau=4, lam=LAM, lam_kind=kind)
        assert clock.total_rounds == 1
        assert float(clock.lam_at(0)) == pytest.approx(LAM, rel=1e-6)


def test_round_plan_is_lazy():
    """DDP drivers only read lr_at: constructing a clock must not eagerly
    allocate one RoundSpec per step (a 1M-step DDP baseline would pay
    seconds of host time for a plan nobody reads)."""
    clock = RoundClock(total_steps=1_000_000, tau=1, base_lr=0.1)
    assert "rounds" not in clock.__dict__         # cached_property unset
    assert float(clock.lr_at(0)) == pytest.approx(0.1, rel=1e-6)
    assert "rounds" not in clock.__dict__


# ---------------------------------------------------------------------------
# the off-by-one regression: every builder, round 0 -> 0, final -> lam
# ---------------------------------------------------------------------------

def _lam_trajectory(step_fn, state, clock, batch):
    lams = []
    for spec in clock.rounds:
        state, m = step_fn(state, batch(spec.tau, spec.start))
        lams.append(float(m["lam_t"]))
    return state, lams


@pytest.mark.parametrize("mode", ["tree", "flat", "overlap", "sharded"])
def test_lam_schedule_endpoints_in_every_builder(mode):
    """With lam_schedule='increasing' (the paper's main-results default),
    round 0 must produce lam_t == 0 and the final round lam_t == lam. The
    pre-clock builders read ``t // tau`` AFTER the scan advanced t, so
    round 0 was skipped and the whole trajectory ran one round early."""
    M = 4
    opt, p0, loss, batch = _setup(M=M)
    kw = dict(alpha=0.2, lam=LAM, tau=2, lam_schedule="increasing")
    if mode == "tree":
        dcfg = DPPFConfig(engine="tree", **kw)
    elif mode == "overlap":
        dcfg = DPPFConfig(engine="flat", overlap="staleness1", **kw)
    else:
        dcfg = DPPFConfig(engine="flat", **kw)
    clock = RoundClock.from_config(dcfg, base_lr=0.05, total_steps=8)
    state = init_train_state(p0, opt, dcfg, M, jax.random.PRNGKey(0))
    if mode == "sharded":
        from repro.launch.mesh import make_cpu_mesh
        mesh = make_cpu_mesh()
        plan = MeshPlan(worker_axes=("data",), model_axes=("model",))
        state = shard_train_state(state, mesh, plan)
        fn = jax.jit(make_sharded_round_step(loss, opt, dcfg, mesh=mesh,
                                             plan=plan, clock=clock))
    else:
        fn = jax.jit(make_round_step(loss, opt, dcfg, clock=clock))
    state, lams = _lam_trajectory(fn, state, clock, batch)
    want = [float(clock.lam_at(k)) for k in range(clock.total_rounds)]
    np.testing.assert_allclose(lams, want, rtol=1e-6, atol=0)
    assert lams[0] == 0.0
    assert lams[-1] == pytest.approx(LAM, rel=1e-6)
    assert int(state.t) == 8 and int(state.round) == clock.total_rounds


def test_legacy_state_without_round_counter_uses_prescan_index():
    """Hand-built TrainStates (no round counter) fall back to the PRE-scan
    ``t // tau`` — still fixing the off-by-one for fixed tau."""
    M = 2
    opt, p0, loss, batch = _setup(M=M)
    dcfg = DPPFConfig(alpha=0.2, lam=LAM, tau=2, lam_schedule="increasing")
    st = init_train_state(p0, opt, dcfg, M, jax.random.PRNGKey(0))
    legacy = TrainState(params=st.params, opt=st.opt, cstate=st.cstate,
                        t=st.t, engine=st.engine)
    assert legacy.round is None
    fn = jax.jit(make_round_step(loss, opt, dcfg, base_lr=0.05,
                                 total_steps=8))
    _, m = fn(legacy, batch(2, 0))
    assert float(m["lam_t"]) == 0.0               # round 0, not round 1


# ---------------------------------------------------------------------------
# QSR: constant-tau parity, remainder accounting, adaptive savings
# ---------------------------------------------------------------------------

def test_qsr_constant_tau_bitwise_equals_fixed():
    """beta small enough that QSR always returns tau_base -> the adaptive
    run must be bit-for-bit the fixed-tau run (same plan, same lam
    denominator, same global-step batch seeding)."""
    M = 4
    opt, p0, loss, batch = _setup(M=M)
    base = dict(alpha=0.2, lam=LAM, tau=2, engine="flat",
                lam_schedule="increasing")
    d_fixed = DPPFConfig(**base)
    d_qsr = DPPFConfig(tau_schedule="qsr", qsr_beta=1e-6, **base)
    c_fixed = RoundClock.from_config(d_fixed, base_lr=0.05, total_steps=8)
    c_qsr = RoundClock.from_config(d_qsr, base_lr=0.05, total_steps=8)
    assert c_fixed.rounds == c_qsr.rounds

    outs = []
    for dcfg, clock in ((d_fixed, c_fixed), (d_qsr, c_qsr)):
        st = init_train_state(p0, opt, dcfg, M, jax.random.PRNGKey(0))
        fn = jax.jit(make_round_step(loss, opt, dcfg, clock=clock))
        for spec in clock.rounds:
            st, m = fn(st, batch(spec.tau, spec.start))
        outs.append((np.asarray(st.params), float(m["lam_t"])))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    assert outs[0][1] == outs[1][1]


def test_remainder_steps_run_and_counted():
    """steps % tau used to be silently dropped by the launcher; the clock
    plans a short final round instead."""
    M = 2
    opt, p0, loss, batch = _setup(M=M)
    dcfg = DPPFConfig(alpha=0.2, lam=LAM, tau=4)
    clock = RoundClock.from_config(dcfg, base_lr=0.05, total_steps=10)
    st = init_train_state(p0, opt, dcfg, M, jax.random.PRNGKey(0))
    fn = jax.jit(make_round_step(loss, opt, dcfg, clock=clock))
    for spec in clock.rounds:
        st, m = fn(st, batch(spec.tau, spec.start))
    assert int(st.t) == 10                        # all 10 steps ran
    assert int(st.round) == 3
    assert float(m["lam_t"]) == pytest.approx(LAM, rel=1e-6)


def test_qsr_saves_rounds_at_matching_loss():
    """The §7.2 scenario end-to-end on the MLP task: QSR communicates in
    fewer rounds than fixed tau while the final test error stays within
    ERR_TOL percentage points (the adaptive run trains on the SAME step
    budget; only the consensus cadence changes, so the end error moves a
    little but must not degrade materially)."""
    ERR_TOL = 8.0   # pct points; MLP task std across seeds is ~2-3
    from benchmarks.common import default_data, run_distributed
    data = default_data()
    base = dict(alpha=0.1, lam=0.5, tau=4, engine="flat",
                lam_schedule="increasing")
    r_fixed = run_distributed(data, DPPFConfig(**base), M=4, steps=240)
    r_qsr = run_distributed(
        data, DPPFConfig(tau_schedule="qsr", qsr_beta=0.05, **base),
        M=4, steps=240)
    assert r_qsr.comm_pct < r_fixed.comm_pct      # fewer all-reduces
    assert abs(r_qsr.test_err - r_fixed.test_err) <= ERR_TOL


def test_launcher_resume_revalidates_clock_position(tmp_path):
    """Resuming with a LONGER --steps builds a different plan: the launcher
    must re-derive the round index from the step counter (the saved index
    belongs to the plan that wrote the checkpoint) and keep training;
    a step count that lands mid-round in the new plan must raise."""
    import shutil
    from repro.launch.train import main
    ck = str(tmp_path / "ck.npz")
    args = ["--arch", "yi-6b", "--smoke", "--workers", "2", "--tau", "4",
            "--seq", "16", "--batch", "2", "--lr", "0.3", "--ckpt", ck]
    main(args + ["--steps", "8"])                 # writes resume at t=8
    shutil.copy(str(tmp_path / "ck.state.npz"),
                str(tmp_path / "t8.state.npz"))
    loss = main(args + ["--steps", "16"]).eval_loss   # t=8: round 2 of 4
    assert np.isfinite(loss)
    shutil.copy(str(tmp_path / "t8.state.npz"),
                str(tmp_path / "ck.state.npz"))   # back to the t=8 point
    with pytest.raises(ValueError, match="mid-round"):
        main(args + ["--steps", "15", "--tau", "6"])   # plan: 6,6,3 — no 8


def test_launcher_cli_qsr_smoke():
    """`--tau-schedule qsr` through the real launcher: completes, returns a
    finite eval loss, and exercises the remainder + re-chunk path."""
    from repro.launch.train import main
    loss = main(["--arch", "yi-6b", "--smoke", "--workers", "2",
                 "--tau", "4", "--steps", "10", "--seq", "16", "--batch",
                 "2", "--lr", "0.3", "--tau-schedule", "qsr", "--qsr-beta",
                 "0.35"]).eval_loss
    assert np.isfinite(loss)


# ---------------------------------------------------------------------------
# per-round metrics logging hook (RoundMetricsLogger + --log-every-round)
# ---------------------------------------------------------------------------

def test_round_metrics_logger_jsonl(tmp_path):
    """The clock-driven hook: one JSON line per round carrying the clock
    position + the unified metrics dict; bare-int specs (the ddp per-step
    clock) log as tau=1 rows."""
    import json
    from repro.train import RoundMetricsLogger, RoundSpec
    path = str(tmp_path / "rounds.jsonl")
    with RoundMetricsLogger(path) as log:
        row = log(RoundSpec(index=0, start=0, tau=4),
                  {"consensus_dist": jnp.float32(1.5), "staleness": 0,
                   "note": "x"})
        assert row == {"round": 0, "start": 0, "tau": 4,
                       "consensus_dist": 1.5, "staleness": 0.0, "note": "x"}
        log(3, {"train_loss": 2.0})
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 2
    assert lines[0]["tau"] == 4 and lines[0]["consensus_dist"] == 1.5
    assert lines[1] == {"round": 3, "start": 3, "tau": 1, "train_loss": 2.0}


def test_launcher_log_every_round_jsonl(tmp_path):
    """--log-every-round through the real launcher: one line per plan
    round with the unified schema (staleness depth included) for a
    doublebuf run, and one line per STEP for the ddp branch."""
    import json
    from repro.launch.train import main
    path = str(tmp_path / "rounds.jsonl")
    loss = main(["--arch", "yi-6b", "--smoke", "--workers", "2",
                 "--tau", "4", "--steps", "10", "--seq", "16", "--batch",
                 "2", "--lr", "0.3", "--overlap", "doublebuf",
                 "--overlap-chunks", "2", "--log-every-round", path]).eval_loss
    assert np.isfinite(loss)
    lines = [json.loads(l) for l in open(path)]
    rows = [r for r in lines if "event" not in r]
    # the tau-2 remainder round compiles its step anew: the supervisor's
    # recompile event names it
    assert 2 in [r["round"] for r in lines if r.get("event") == "recompile"]
    clock = RoundClock(total_steps=10, tau=4, base_lr=0.3,
                       overlap="doublebuf")
    assert len(rows) == clock.total_rounds
    for want, got in zip(clock.rounds, rows):
        assert (got["round"], got["start"], got["tau"]) == (
            want.index, want.start, want.tau)
        for k in ("consensus_dist", "pre_dist", "pull_force", "push_force",
                  "train_loss", "lam_t", "staleness"):
            assert k in got, k
    # the bubble round is exact (depth 0), the steady state depth-1 stale
    assert rows[0]["staleness"] == 0.0
    assert all(r["staleness"] == 1.0 for r in rows[1:])

    ddp_path = str(tmp_path / "ddp.jsonl")
    loss = main(["--arch", "yi-6b", "--smoke", "--workers", "2",
                 "--consensus", "ddp", "--steps", "3", "--seq", "16",
                 "--batch", "2", "--log-every-round", ddp_path]).eval_loss
    assert np.isfinite(loss)
    rows = [json.loads(l) for l in open(ddp_path)]
    assert len(rows) == 3 and all(r["tau"] == 1 for r in rows)
    assert all(r["staleness"] == 0.0 and r["consensus_dist"] == 0.0
               for r in rows)


# ---------------------------------------------------------------------------
# checkpoint: the clock position survives save/resume
# ---------------------------------------------------------------------------

def test_checkpoint_persists_clock_position_qsr(tmp_path):
    """Mid-run resume of an ADAPTIVE run must restore the round index from
    the checkpoint (with QSR it is not derivable as t // tau) and continue
    bit-for-bit with the straight-through run."""
    M = 4
    opt, p0, loss, batch = _setup(M=M)
    dcfg = DPPFConfig(alpha=0.2, lam=LAM, tau=2, engine="flat",
                      lam_schedule="increasing", tau_schedule="qsr",
                      qsr_beta=0.25)
    clock = RoundClock.from_config(dcfg, base_lr=0.3, total_steps=16)
    assert clock.taus() != (2,) * (16 // 2)       # genuinely adaptive
    key = jax.random.PRNGKey(0)
    fn = jax.jit(make_round_step(loss, opt, dcfg, clock=clock))

    straight = init_train_state(p0, opt, dcfg, M, key)
    resumed = init_train_state(p0, opt, dcfg, M, key)
    cut = 2
    for spec in clock.rounds[:cut]:
        straight, _ = fn(straight, batch(spec.tau, spec.start))
        resumed, _ = fn(resumed, batch(spec.tau, spec.start))
    path = str(tmp_path / "state.npz")
    save_train_state(path, resumed)

    template = init_train_state(p0, opt, dcfg, M, key)
    resumed = load_train_state(path, template)
    assert int(resumed.round) == cut
    assert int(resumed.t) == clock.rounds[cut].start
    for spec in clock.rounds[cut:]:
        straight, _ = fn(straight, batch(spec.tau, spec.start))
        resumed, _ = fn(resumed, batch(spec.tau, spec.start))
    np.testing.assert_array_equal(np.asarray(straight.params),
                                  np.asarray(resumed.params))


def test_checkpoint_without_round_extra_recovers_via_clock(tmp_path):
    """Pre-RoundClock checkpoints carried only ``t``: the loader recovers
    the round index through clock.round_of_step."""
    import numpy as onp
    from repro.checkpoint.io import _SEP, _state_tree
    M = 2
    opt, p0, loss, batch = _setup(M=M)
    dcfg = DPPFConfig(alpha=0.2, lam=LAM, tau=2, engine="flat")
    clock = RoundClock.from_config(dcfg, base_lr=0.05, total_steps=8)
    st = init_train_state(p0, opt, dcfg, M, jax.random.PRNGKey(0))
    fn = jax.jit(make_round_step(loss, opt, dcfg, clock=clock))
    st, _ = fn(st, batch(2, 0))
    # simulate an old checkpoint: same tree, only the ``t`` extra
    from repro.checkpoint import save_pytree
    path = str(tmp_path / "old.npz")
    save_pytree(path, _state_tree(st),
                extra={"t": onp.asarray(jax.device_get(st.t))})
    template = init_train_state(p0, opt, dcfg, M, jax.random.PRNGKey(0))
    resumed = load_train_state(path, template, clock=clock)
    assert int(resumed.t) == 2
    assert int(resumed.round) == 1                # recovered from the plan

    # without a clock the loader must NOT adopt the template's fresh 0
    # (that would restart the lam schedule): round is None and the round
    # builders' pre-scan t // tau fallback produces the correct index
    blind = load_train_state(path, template)
    assert blind.round is None
    _, m = fn(blind, batch(2, 2))
    assert float(m["lam_t"]) == pytest.approx(
        float(clock.lam_at(1)), rel=1e-6)


# ---------------------------------------------------------------------------
# serving: generate() edges
# ---------------------------------------------------------------------------

def _tiny_model():
    from repro.configs import ARCHS, reduced
    from repro.models import build_model
    cfg = reduced(ARCHS["yi-6b"], n_layers=2, d_model=64, n_heads=2,
                  n_kv_heads=2, head_dim=32, d_ff=128, vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_generate_max_new_tokens_one():
    """max_new_tokens=1 is prefill-then-pick: the zero-length decode scan
    must not break shapes, and greedy output == argmax of the prefill
    logits."""
    from repro.serving import generate
    model, params = _tiny_model()
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    toks, logits = generate(model, params, {"tokens": prompt},
                            max_new_tokens=1, buf_len=16)
    assert toks.shape == (2, 1)
    np.testing.assert_array_equal(
        np.asarray(toks[:, 0]), np.asarray(jnp.argmax(logits, axis=-1)))
    # sampled flavor: one token drawn with the CALLER's key itself
    key = jax.random.PRNGKey(3)
    toks_s, logits_s = generate(model, params, {"tokens": prompt},
                                max_new_tokens=1, buf_len=16, greedy=False,
                                key=key)
    assert toks_s.shape == (2, 1)
    np.testing.assert_array_equal(
        np.asarray(toks_s[:, 0]),
        np.asarray(jax.random.categorical(key, logits_s)))


def test_generate_sample_keys_first_vs_fold_in_chain():
    """The first sampled token consumes the caller's key; tokens i >= 1
    use fold_in(key, i). The keys are pairwise distinct and the whole
    chain is reproducible from that contract (decode_key)."""
    from repro.serving import decode_key, generate
    model, params = _tiny_model()
    key = jax.random.PRNGKey(9)
    # the contract itself: decode_key(k, 0) IS k; the chain never collides
    assert np.array_equal(np.asarray(decode_key(key, 0)), np.asarray(key))
    raw = [np.asarray(decode_key(key, i)).tobytes() for i in range(4)]
    assert len(set(raw)) == 4

    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, 64)
    N = 3
    toks, _ = generate(model, params, {"tokens": prompt}, max_new_tokens=N,
                       buf_len=16, greedy=False, key=key)
    # reference replay straight from the ModelAPI + decode_key chain
    logits, states = model.prefill(params, {"tokens": prompt}, buf_len=16)
    tok = jax.random.categorical(decode_key(key, 0), logits).astype(jnp.int32)
    ref = [tok]
    for i in range(1, N):
        # token i-1 occupies position prompt_len + i - 1 (the first
        # generated token extends the prompt with no position gap)
        lg, states = model.decode_step(params, states, tok[:, None],
                                       prompt.shape[1] + i - 1)
        tok = jax.random.categorical(decode_key(key, i), lg).astype(jnp.int32)
        ref.append(tok)
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.stack(ref, axis=1)))

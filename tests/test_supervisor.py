"""Fault-tolerant round supervisor (DESIGN.md §Fault-tolerance): the
replayable ChaosPlan artifact, the heartbeat membership state machine
(ACTIVE -> SUSPECT -> DEAD -> REJOINING), quorum degrade through the
elastic carry's scalar ``sync`` gate, crash-safe checkpoint rotation with
the corrupt-archive restore ladder, and the OOM shrink + replay path.

The acceptance contracts pinned here:

* an empty plan (no membership, no chaos) makes the supervisor loop
  bit-for-bit the plain ``for spec in clock.rounds`` loop it replaced;
* ``ScheduleMembership`` (the ``--elastic-drop`` provider) is bit-for-bit
  the old inline ``set_participation`` loop;
* the SAME plan replayed from a fresh init walks a bit-identical
  recovery-event sequence and lands on bit-identical params;
* the committed 8-device CI leg (``results/chaos/plan_ci.json``) emits
  exactly the pinned sequence in ``results/chaos/events_ci.json``.

Multi-device legs run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the
test_staleness_k.py pattern)."""
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

from repro.checkpoint import (
    load_pytree, load_train_state, save_pytree, save_train_state,
)
from repro.configs import DPPFConfig
from repro.optim import make_optimizer
from repro.train import (
    ChaosEvent, ChaosMembership, ChaosPlan, FaultInjector,
    HeartbeatMembership, InjectedOOM, RoundClock, ScheduleMembership,
    Supervisor, init_train_state, is_oom, make_round_step,
    set_participation,
)
from repro.train.supervisor import ACTIVE, DEAD, REJOINING, SUSPECT

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ROOT = os.path.join(os.path.dirname(__file__), "..")

M, TAU, K = 4, 2, 2


def _setup(steps=12, elastic=True):
    from benchmarks.common import mlp_init, mlp_loss
    dim, ncls, width = 16, 4, 8
    opt = make_optimizer("sgd", momentum=0.9)
    p0 = lambda k: mlp_init(k, dim, ncls, width)
    dcfg = DPPFConfig(alpha=0.2, lam=0.4, tau=TAU, engine="flat",
                      overlap="staleness_k", staleness=K, elastic=elastic,
                      lam_schedule="fixed")
    clock = RoundClock.from_config(dcfg, base_lr=0.05, total_steps=steps)
    step = jax.jit(make_round_step(mlp_loss, opt, dcfg, clock=clock))

    def batch_fn(spec, bs):
        k = jax.random.fold_in(jax.random.PRNGKey(1), spec.index)
        return {"x": jax.random.normal(k, (spec.tau, M, bs, dim)),
                "y": jax.random.randint(jax.random.fold_in(k, 1),
                                        (spec.tau, M, bs), 0, ncls)}
    state = init_train_state(p0, opt, dcfg, M, jax.random.PRNGKey(0))
    return dcfg, clock, step, state, batch_fn, (p0, opt)


def _params(state):
    return np.asarray(jax.device_get(state.params))


# ---------------------------------------------------------------------------
# ChaosPlan: the byte-stable fault script
# ---------------------------------------------------------------------------

def test_chaos_plan_roundtrip_bytes(tmp_path):
    """save -> load -> dumps is byte-identical, and the canonical event
    sort makes dumps() independent of authoring order (the TunePlan
    idiom)."""
    a = ChaosPlan(events=(
        ChaosEvent(round=5, kind="oom", batch_above=2),
        ChaosEvent(round=1, kind="kill", worker=3, duration=2),
        ChaosEvent(round=1, kind="corrupt_ckpt"),
    ), seed=3)
    b = ChaosPlan(events=tuple(reversed(a.events)), seed=3)
    assert a.dumps() == b.dumps()
    path = str(tmp_path / "plan.json")
    a.save(path)
    assert ChaosPlan.load(path).dumps() == a.dumps()
    with open(path) as f:
        assert f.read() == a.dumps()
    # membership window query
    assert a.is_down(3, 1) and a.is_down(3, 2) and not a.is_down(3, 3)
    assert not a.is_down(0, 1)
    assert len(a.membership_events()) == 1


def test_chaos_plan_validation():
    with pytest.raises(ValueError, match="unknown chaos kind"):
        ChaosEvent(round=0, kind="meteor")
    with pytest.raises(ValueError, match="round"):
        ChaosEvent(round=-1, kind="corrupt_ckpt")
    with pytest.raises(ValueError, match="duration"):
        ChaosEvent(round=0, kind="kill", worker=0, duration=0)
    with pytest.raises(ValueError, match="worker"):
        ChaosEvent(round=0, kind="netdrop")
    with pytest.raises(ValueError, match="batch_above"):
        ChaosEvent(round=0, kind="oom")
    with pytest.raises(ValueError, match="version"):
        ChaosPlan(version=99)
    with pytest.raises(ValueError, match="malformed ChaosPlan"):
        ChaosPlan.from_dict({"seed": 0})        # no events key
    with pytest.raises(ValueError, match="malformed ChaosPlan"):
        ChaosPlan.from_dict({"events": [{"kind": "oom"}]})  # no round
    # the injected failure satisfies the PR 9 message contract
    assert is_oom(InjectedOOM(8))
    assert is_oom(InjectedOOM(8, round_idx=3))
    assert "round 3" in str(InjectedOOM(8, round_idx=3))


def test_fault_injector_hooks(tmp_path):
    plan = ChaosPlan(events=(
        ChaosEvent(round=2, kind="oom", batch_above=2),
        ChaosEvent(round=1, kind="corrupt_ckpt"),
    ))
    inj = FaultInjector(plan)
    inj.before_step(1, 8)                     # wrong round: no fault
    inj.before_step(2, 2)                     # at the threshold: cleared
    with pytest.raises(InjectedOOM):
        inj.before_step(2, 4)
    path = str(tmp_path / "c.npz")
    save_pytree(path, {"w": np.arange(64.0)})
    assert not inj.after_save(0, path)        # wrong round: untouched
    load_pytree(path, {"w": np.zeros(64)})
    assert inj.after_save(1, path)            # torn to half its bytes
    with pytest.raises(ValueError, match="corrupt"):
        load_pytree(path, {"w": np.zeros(64)})


# ---------------------------------------------------------------------------
# membership state machine
# ---------------------------------------------------------------------------

def test_heartbeat_state_machine():
    hb = HeartbeatMembership(3, timeout=0.9, suspect_after=1, dead_after=2)
    mask, tr = hb.poll(0.0)                   # everyone fresh
    np.testing.assert_array_equal(mask, [1, 1, 1])
    assert tr == []
    hb.beat(0, 1.0), hb.beat(1, 1.0)          # worker 2 silent
    mask, tr = hb.poll(1.0)
    assert tr == [(2, ACTIVE, SUSPECT)]
    np.testing.assert_array_equal(mask, [1, 1, 0])
    hb.beat(0, 2.0), hb.beat(1, 2.0)
    mask, tr = hb.poll(2.0)
    assert tr == [(2, SUSPECT, DEAD)]
    # first beat after DEAD: back in the mask as REJOINING
    assert hb.beat(2, 3.0) == [(2, DEAD, REJOINING)]
    mask, _ = hb.poll(3.0)
    np.testing.assert_array_equal(mask, [0, 0, 1])  # 0/1 now silent
    assert hb.beat(2, 4.0) == [(2, REJOINING, ACTIVE)]
    # a SUSPECT beat recovers straight to ACTIVE
    assert hb.beat(0, 4.0) == [(0, SUSPECT, ACTIVE)]
    with pytest.raises(ValueError, match="out of range"):
        hb.beat(3, 0.0)
    with pytest.raises(ValueError, match="timeout"):
        HeartbeatMembership(2, timeout=0.0)
    with pytest.raises(ValueError, match="suspect_after"):
        HeartbeatMembership(2, timeout=1.0, suspect_after=3, dead_after=2)


def test_chaos_membership_windows_and_monotonic_advance():
    plan = ChaosPlan(events=(
        ChaosEvent(round=1, kind="kill", worker=1, duration=2),))
    cm = ChaosMembership(plan, 2, timeout=0.9)
    mask, ev = cm.mask_for(0)
    np.testing.assert_array_equal(mask, [1, 1])
    assert ev == []
    mask, ev = cm.mask_for(1)
    np.testing.assert_array_equal(mask, [1, 0])
    assert ev == [{"event": "suspect", "worker": 1, "from": ACTIVE}]
    with pytest.raises(ValueError, match="one round at a time"):
        cm.mask_for(1)                        # replays go through the cache
    mask, ev = cm.mask_for(2)
    assert [e["event"] for e in ev] == ["evict"]
    _, ev = cm.mask_for(3)                    # window over: beat -> rejoin
    assert [e["event"] for e in ev] == ["rejoin"]
    _, ev = cm.mask_for(4)
    assert [e["event"] for e in ev] == ["recover"]
    with pytest.raises(ValueError, match="round_s"):
        ChaosMembership(plan, 2, timeout=0.9, round_s=0.0)


def test_schedule_membership_validation():
    with pytest.raises(ValueError, match="out of range"):
        ScheduleMembership(4, [(7, 0, 2)])
    with pytest.raises(ValueError, match="empty or negative"):
        ScheduleMembership(4, [(1, 3, 3)])
    sm = ScheduleMembership(4, [(1, 1, 3)])
    np.testing.assert_array_equal(sm.mask_for(0)[0], [1, 1, 1, 1])
    np.testing.assert_array_equal(sm.mask_for(2)[0], [1, 0, 1, 1])


# ---------------------------------------------------------------------------
# the sync gate: degraded rounds skip consensus bit-exactly
# ---------------------------------------------------------------------------

def test_sync_gate_value_identity_and_degrade():
    """``sync=1.0`` is value-identical to the pre-supervisor call (bit
    parity of the old --elastic-drop path); ``sync=0`` changes the round
    (consensus skipped) but carries through the ring unchanged."""
    _, clock, step, st0, batch_fn, _ = _setup()
    assert float(st0.snap["sync"]) == 1.0
    mask = jnp.ones((M,), jnp.float32)
    a = set_participation(st0, mask)               # sync untouched
    b = set_participation(st0, mask, sync=1.0)     # explicit
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # run two rounds so the consensus application actually lands
    on = set_participation(st0, mask, sync=1.0)
    off = set_participation(st0, mask, sync=0.0)
    for spec in clock.rounds[:2]:
        on, _ = step(on, batch_fn(spec, 8))
        off, _ = step(off, batch_fn(spec, 8))
    assert np.abs(_params(on) - _params(off)).max() > 0.0
    assert float(off.snap["sync"]) == 0.0          # carried, not reset
    assert np.isfinite(_params(off)).all()
    # flipping the gate back re-enables consensus mid-run
    off = set_participation(off, mask, sync=1.0)
    off, _ = step(off, batch_fn(clock.rounds[2], 8))
    assert np.isfinite(_params(off)).all()


def test_sync_gate_requires_elastic_carry():
    # non-elastic states have no participation carry at all
    _, _, _, st, _, _ = _setup(elastic=False)
    with pytest.raises(ValueError, match="elastic"):
        set_participation(st, jnp.ones((M,)), sync=0.0)
    # an elastic state whose snap predates the gate (legacy, in-memory)
    # refuses a sync override with a clear error
    _, _, _, st_e, _, _ = _setup()
    legacy = dataclasses.replace(
        st_e, snap={k: v for k, v in st_e.snap.items() if k != "sync"})
    with pytest.raises(ValueError, match="sync"):
        set_participation(legacy, jnp.ones((M,)), sync=0.0)


# ---------------------------------------------------------------------------
# supervisor: parity, recovery, determinism
# ---------------------------------------------------------------------------

def test_supervisor_empty_plan_is_plain_loop():
    """THE transparency acceptance: no membership, no chaos, no ckpt_dir
    -> the supervisor is bit-for-bit the inline round loop."""
    _, clock, step, st_a, batch_fn, _ = _setup()
    for spec in clock.rounds:
        st_a, _ = step(st_a, batch_fn(spec, 8))
    _, _, step2, st_b, _, _ = _setup()
    sup = Supervisor(clock, workers=M, batch_size=8)
    st_b = sup.run(st_b, step2, batch_fn)
    np.testing.assert_array_equal(_params(st_a), _params(st_b))
    assert sup.events == [] and sup.summary()["counters"] == {}


def test_supervisor_schedule_membership_parity():
    """ScheduleMembership == the old inline --elastic-drop loop, bit for
    bit (mask applied every round, sync pinned at its carried 1.0)."""
    drop = (1, 1, 3)
    _, clock, step, st_a, batch_fn, _ = _setup()
    for spec in clock.rounds:
        mask = np.ones(M, np.float32)
        if drop[1] <= spec.index < drop[2]:
            mask[drop[0]] = 0.0
        st_a = set_participation(st_a, jnp.asarray(mask))
        st_a, _ = step(st_a, batch_fn(spec, 8))
    _, _, step2, st_b, _, _ = _setup()
    sup = Supervisor(clock, workers=M,
                     membership=ScheduleMembership(M, [drop]),
                     batch_size=8)
    st_b = sup.run(st_b, step2, batch_fn)
    np.testing.assert_array_equal(_params(st_a), _params(st_b))
    assert sup.events == []                   # a requested drop: no fault


def _chaos_supervised_run(tmp_path, plan, tag, *, quorum=M, logger=None,
                          retry_budget=3, batch=8):
    _, clock, step, state, batch_fn, _ = _setup()
    d = str(tmp_path / tag)
    sup = Supervisor(clock, workers=M,
                     membership=ChaosMembership(plan, M, timeout=0.9),
                     quorum=quorum, chaos=FaultInjector(plan), ckpt_dir=d,
                     batch_size=batch, logger=logger,
                     retry_budget=retry_budget, seed=plan.seed)
    state = sup.run(state, step, batch_fn)
    return sup, state


def test_supervisor_oom_shrink_restore_replay(tmp_path):
    plan = ChaosPlan(events=(
        ChaosEvent(round=2, kind="oom", batch_above=4),), seed=5)
    sup, state = _chaos_supervised_run(tmp_path, plan, "a")
    # saves: the pre-loop anchor + 6 rounds, round 2 saved once on replay
    assert sup.summary()["counters"] == {
        "ckpt_saved": 7, "oom": 1, "restore": 1, "retry": 1, "shrink": 1}
    assert sup.batch_size == 4                # halved 8 -> 4
    seq = sup.event_seq()
    assert seq[:2] == ["r2:oom", "r2:shrink"]
    assert "r2:restore" in seq and "r2:retry" in seq
    # replay determinism: fresh init, same plan -> identical timeline
    # AND identical final params
    sup2, state2 = _chaos_supervised_run(tmp_path, plan, "b")
    assert sup2.event_seq() == seq
    np.testing.assert_array_equal(_params(state), _params(state2))
    # every recovery action also went through the metrics logger path
    rows = []
    sup3, _ = _chaos_supervised_run(
        tmp_path, plan, "c",
        logger=lambda spec, m: rows.append((spec, dict(m))))
    evs = [(r, m["event"]) for r, m in rows if "event" in m]
    assert [e for _, e in evs if e != "recompile"] == [
        "oom", "shrink", "restore", "retry"]
    # and the replay at the shrunk batch compiled the step anew
    assert [r for r, e in evs if e == "recompile"] == [2]


def test_supervisor_corrupt_ckpt_ladder(tmp_path):
    """A torn sup_last drops the restore to the prev rotation copy; the
    recovery replays one extra round and still completes."""
    plan = ChaosPlan(events=(
        ChaosEvent(round=1, kind="corrupt_ckpt"),
        ChaosEvent(round=2, kind="oom", batch_above=4),), seed=5)
    sup, state = _chaos_supervised_run(tmp_path, plan, "a")
    c = sup.summary()["counters"]
    assert c["restore_corrupt"] == 1 and c["restore"] == 1
    seq = sup.event_seq()
    assert seq.index("r2:restore_corrupt") < seq.index("r2:restore")
    # the prev copy holds round 1's state -> replay from round 1
    assert any(e["event"] == "restore" and "round 1" in e["detail"]
               for e in sup.events)
    assert np.isfinite(_params(state)).all()


def test_supervisor_quorum_degrade_backoff(tmp_path):
    """Below-quorum rounds degrade (sync=0), emit deterministic backoff,
    and never fail the run; the recorded jitter is pure sha256 state."""
    plan = ChaosPlan(events=(
        ChaosEvent(round=1, kind="kill", worker=0, duration=1),
        ChaosEvent(round=1, kind="netdrop", worker=2, duration=1),), seed=9)
    sup, state = _chaos_supervised_run(tmp_path, plan, "a", quorum=3)
    c = sup.summary()["counters"]
    assert c["degrade"] == 1 and "restore" not in c
    deg = [e for e in sup.events if e["event"] == "degrade"]
    assert deg[0]["attempt"] == 1 and deg[0]["backoff_s"] > 0
    sup2, _ = _chaos_supervised_run(tmp_path, plan, "b", quorum=3)
    assert [e.get("backoff_s") for e in sup2.events] == \
        [e.get("backoff_s") for e in sup.events]
    assert np.isfinite(_params(state)).all()


def test_supervisor_retry_budget_and_non_oom(tmp_path):
    """A persistent non-OOM failure propagates after retry_budget
    consecutive restore+replay attempts; with no ckpt_dir it propagates
    immediately (nothing to restore a donated state from)."""
    _, clock, step, state, batch_fn, _ = _setup()

    calls = {"n": 0}

    def bad_step(st, batch):
        calls["n"] += 1
        raise RuntimeError("xla miscompile of the week")

    sup = Supervisor(clock, workers=M, ckpt_dir=str(tmp_path / "d"),
                     batch_size=8, retry_budget=2)
    with pytest.raises(RuntimeError, match="miscompile"):
        sup.run(state, bad_step, batch_fn)
    assert calls["n"] == 3                    # 1 try + 2 retries
    assert sup.summary()["counters"]["retry"] == 2
    assert "oom" not in sup.summary()["counters"]

    _, _, _, state2, _, _ = _setup()
    sup2 = Supervisor(clock, workers=M, batch_size=8)   # no ckpt_dir
    with pytest.raises(RuntimeError):
        sup2.run(state2, bad_step, batch_fn)
    assert sup2.events == []


def test_supervisor_oom_floor_propagates(tmp_path):
    """When the batch cannot shrink further (size 1), the OOM
    propagates instead of death-looping."""
    _, clock, _, state, batch_fn, _ = _setup()

    def oom_step(st, batch):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating")

    sup = Supervisor(clock, workers=M, ckpt_dir=str(tmp_path / "d"),
                     batch_size=1)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        sup.run(state, oom_step, batch_fn)
    c = sup.summary()["counters"]
    assert c["oom"] == 1 and "shrink" not in c


def test_supervisor_validation():
    _, clock, _, _, _, _ = _setup()
    with pytest.raises(ValueError, match="workers"):
        Supervisor(clock, workers=0)
    with pytest.raises(ValueError, match="quorum"):
        Supervisor(clock, workers=M, quorum=-1)
    with pytest.raises(ValueError, match="exceeds the worker count"):
        Supervisor(clock, workers=M, quorum=M + 1)
    with pytest.raises(ValueError, match="retry_budget"):
        Supervisor(clock, workers=M, retry_budget=-1)
    with pytest.raises(ValueError, match="ckpt_every"):
        Supervisor(clock, workers=M, ckpt_every=0)
    with pytest.raises(ValueError, match="backoff_base"):
        Supervisor(clock, workers=M, backoff_base=0.0)
    with pytest.raises(ValueError, match="membership provider"):
        Supervisor(clock, workers=M,
                   membership=ScheduleMembership(M + 1, []))


# ---------------------------------------------------------------------------
# crash-safe checkpoints (checkpoint/io.py)
# ---------------------------------------------------------------------------

def test_checkpoint_atomic_write_and_corrupt_errors(tmp_path):
    tree = {"w": np.arange(32.0).reshape(8, 4), "b": np.zeros(4)}
    path = str(tmp_path / "ck.npz")
    save_pytree(path, tree)
    # atomic rename: no stray temp files next to the final archive
    assert os.listdir(str(tmp_path)) == ["ck.npz"]
    out, _ = load_pytree(path, jax.tree.map(np.zeros_like, tree))
    np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])
    # a truncated archive is a clear ValueError naming the path, NOT a
    # raw zipfile/zlib traceback
    with open(path, "rb") as f:
        data = f.read()
    with open(path, "wb") as f:
        f.write(data[:len(data) // 2])
    with pytest.raises(ValueError, match="truncated or corrupt") as ei:
        load_pytree(path, jax.tree.map(np.zeros_like, tree))
    assert "ck.npz" in str(ei.value)
    # non-zip garbage: same contract
    with open(path, "wb") as f:
        f.write(b"\x00" * 100)
    with pytest.raises(ValueError, match="truncated or corrupt"):
        load_pytree(path, jax.tree.map(np.zeros_like, tree))
    # a MISSING file stays FileNotFoundError (never re-wrapped)
    with pytest.raises(FileNotFoundError):
        load_pytree(str(tmp_path / "nope.npz"),
                    jax.tree.map(np.zeros_like, tree))


def test_legacy_checkpoint_sync_backfill(tmp_path):
    """A pre-supervisor elastic checkpoint (no snap::sync entry) loads
    into today's template with the gate backfilled to 1.0 — consensus
    stays ON, bit-compatible with the old behavior."""
    _, clock, step, st, batch_fn, _ = _setup()
    st, _ = step(st, batch_fn(clock.rounds[0], 8))
    legacy = dataclasses.replace(
        st, snap={k: v for k, v in st.snap.items() if k != "sync"})
    path = str(tmp_path / "legacy.npz")
    save_train_state(path, legacy)
    _, _, _, like, _, _ = _setup()
    res = load_train_state(path, like, clock=clock)
    assert float(res.snap["sync"]) == 1.0
    np.testing.assert_array_equal(np.asarray(res.snap["x"]),
                                  np.asarray(st.snap["x"]))


# ---------------------------------------------------------------------------
# the committed CI plan: pinned recovery-event sequence, 8 devices
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_chaos_ci_plan_pinned_sequence_8dev():
    """THE chaos acceptance leg: the committed plan
    (results/chaos/plan_ci.json) driven through the real launcher on 8
    forced host devices (sharded round, donated buffers, shard_map
    restore placement) reproduces results/chaos/events_ci.json exactly
    — recovery-event sequence, counters, and final batch."""
    with open(os.path.join(ROOT, "results", "chaos",
                           "events_ci.json")) as f:
        pinned = json.load(f)
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep + ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "yi-6b",
         "--smoke", "--d-model", "32", "--layers", "1", "--seq", "16",
         "--workers", "8", "--tau", "2", "--steps", "16", "--batch", "2",
         "--overlap", "staleness_k", "--staleness", "2", "--sharded",
         "--chaos", os.path.join("results", "chaos", "plan_ci.json"),
         "--quorum", "7", "--heartbeat-timeout", "0.9"],
        capture_output=True, text=True, env=env, timeout=560, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    ev_line = [l for l in out.stdout.splitlines()
               if l.startswith("supervisor events: ")]
    assert ev_line, out.stdout[-2000:]
    assert ev_line[0].split(": ", 1)[1].split() == pinned["event_seq"]
    ct_line = [l for l in out.stdout.splitlines()
               if l.startswith("supervisor counters: ")][0]
    got = dict(kv.split("=") for kv in ct_line.split(": ", 1)[1].split())
    assert int(got.pop("final_batch")) == pinned["final_batch"]
    assert {k: int(v) for k, v in got.items()} == pinned["counters"]

"""Fault-tolerance microbench: the round supervisor under a scripted
ChaosPlan. Writes ``BENCH_chaos.json`` at the repo root (committed;
``benchmarks/check_bench.py`` guards it in CI like the other benches).

Field classes follow check_bench's contract:

* **structural** — the plan itself, the recovery counters, the pinned
  ``event_seq``, ``final_batch``, and the three determinism/parity bools:
  ``replay_identical`` (the SAME plan run twice from a fresh init walks a
  bit-identical event sequence AND lands on bit-identical params),
  ``empty_plan_parity`` (with no membership and no chaos the supervisor
  loop is bit-for-bit the plain round loop it replaced), and
  ``schedule_parity`` (ScheduleMembership — the ``--elastic-drop`` path —
  matches the old inline set_participation loop bit-for-bit). Also the
  ``modeled`` block: ``roofline.supervisor_model`` degraded-round
  accounting, pure arithmetic.
* **timing** — ``wall_s``: host-relative, reported as a delta only.

The run is a small elastic staleness-k MLP fleet (no transformer — the
supervisor policy is host-side and model-agnostic), with every fault
class exercised: a kill window long enough to evict + rejoin, a quorum
degrade, an injected RESOURCE_EXHAUSTED (batch shrink + replay), and a
corrupt checkpoint (restore-ladder fallback to the rotation copy).

  PYTHONPATH=src:. python benchmarks/bench_chaos.py --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import mlp_init, mlp_loss
from repro.configs import DPPFConfig
from repro.launch.roofline import supervisor_model
from repro.optim import make_optimizer
from repro.train import (
    ChaosEvent, ChaosPlan, ChaosMembership, FaultInjector, RoundClock,
    ScheduleMembership, Supervisor, init_train_state, make_round_step,
    set_participation,
)

M, TAU, K, STEPS = 4, 2, 2, 16
DIM, NCLS, WIDTH, BATCH = 16, 4, 8, 8
QUORUM = 4
SEED = 0

# the committed fault script: one of everything (see module docstring)
PLAN = ChaosPlan(events=(
    ChaosEvent(round=2, kind="kill", worker=2, duration=2),
    ChaosEvent(round=4, kind="corrupt_ckpt"),
    ChaosEvent(round=5, kind="oom", batch_above=4),
), seed=7)


def _setup():
    dcfg = DPPFConfig(engine="flat", overlap="staleness_k", staleness=K,
                      elastic=True, tau=TAU)
    clock = RoundClock.from_config(dcfg, base_lr=0.1, total_steps=STEPS)
    opt = make_optimizer("sgd", momentum=0.9)
    p0 = lambda k: mlp_init(k, DIM, NCLS, WIDTH)
    step = jax.jit(make_round_step(mlp_loss, opt, dcfg, clock=clock),
                   donate_argnums=0)
    state = init_train_state(p0, opt, dcfg, M, jax.random.PRNGKey(SEED))
    return dcfg, clock, step, state


def _batch_fn(spec, bs):
    k = jax.random.fold_in(jax.random.PRNGKey(SEED + 1), spec.index)
    return {"x": jax.random.normal(k, (spec.tau, M, bs, DIM)),
            "y": jax.random.randint(jax.random.fold_in(k, 1),
                                    (spec.tau, M, bs), 0, NCLS)}


def _params(state):
    return np.asarray(jax.device_get(state.params))


def chaos_run(workdir):
    """One full supervised run under PLAN; returns (summary, params,
    restore_bytes, backoff_total)."""
    _, clock, step, state = _setup()
    sup = Supervisor(
        clock, workers=M,
        membership=ChaosMembership(PLAN, M, timeout=0.9),
        quorum=QUORUM, chaos=FaultInjector(PLAN), ckpt_dir=workdir,
        batch_size=BATCH, seed=PLAN.seed)
    state = sup.run(state, step, _batch_fn)
    rb = os.path.getsize(os.path.join(workdir, "sup_last.npz"))
    backoff = sum(e.get("backoff_s", 0.0) for e in sup.events)
    return sup.summary(), _params(state), rb, backoff


def manual_run(drop=None):
    """The pre-supervisor inline loop (bit-parity reference)."""
    _, clock, step, state = _setup()
    for spec in clock.rounds:
        if drop is not None:
            w, a, b = drop
            mask = jnp.ones((M,), jnp.float32)
            if a <= spec.index < b:
                mask = mask.at[w].set(0.0)
            state = set_participation(state, mask)
        state, _ = step(state, _batch_fn(spec, BATCH))
    return _params(state)


def supervised_run(membership=None):
    """Supervisor with no chaos and no checkpointing (the parity legs)."""
    _, clock, step, state = _setup()
    sup = Supervisor(clock, workers=M, membership=membership,
                     batch_size=BATCH)
    return _params(sup.run(state, step, _batch_fn))


def bench_chaos():
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        s1, p1, restore_bytes, backoff = chaos_run(d1)
        s2, p2, _, _ = chaos_run(d2)
    replay_identical = (s1["event_seq"] == s2["event_seq"]
                        and np.array_equal(p1, p2))

    empty_plan_parity = np.array_equal(manual_run(), supervised_run())
    drop = (2, 1, 3)
    schedule_parity = np.array_equal(
        manual_run(drop=drop),
        supervised_run(membership=ScheduleMembership(M, [drop])))

    c = s1["counters"]
    modeled = supervisor_model(
        rounds=len(RoundClock.from_config(
            DPPFConfig(engine="flat", overlap="staleness_k", staleness=K,
                       elastic=True, tau=TAU),
            base_lr=0.1, total_steps=STEPS).rounds),
        tau=TAU, work_s_per_step=2e-3, gather_bytes=1e6,
        device_kind="TPU v5 lite", R=M, staleness=K,
        degraded_rounds=c.get("degrade", 0),
        retried_rounds=c.get("retry", 0),
        restores=c.get("restore", 0), restore_bytes=float(restore_bytes),
        # the bench runs on virtual time (no sleep_fn) — the recorded
        # backoff seconds are reported separately, not priced as wall
        backoff_s=0.0)
    return {
        "workers": M, "tau": TAU, "staleness": K, "rounds": STEPS // TAU,
        "quorum": QUORUM, "batch": BATCH,
        "plan": PLAN.to_dict(),
        "counters": c,
        "event_seq": s1["event_seq"],
        "final_batch": s1["final_batch"],
        "completed": True,
        "replay_identical": bool(replay_identical),
        "empty_plan_parity": bool(empty_plan_parity),
        "schedule_parity": bool(schedule_parity),
        "restore_bytes": int(restore_bytes),
        "backoff_recorded_s": round(backoff, 3),
        "modeled": modeled,
        "wall_s": round(time.perf_counter() - t0, 2),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="BENCH_chaos.json")
    args = ap.parse_args(argv)
    result = {
        "backend": jax.default_backend(),
        "smoke": True,  # the plan is fixed; flag kept for CLI symmetry
        "chaos": bench_chaos(),
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
        f.write("\n")
    c = result["chaos"]
    print(f"events: {' '.join(c['event_seq'])}")
    print(f"replay_identical={c['replay_identical']} "
          f"empty_plan_parity={c['empty_plan_parity']} "
          f"schedule_parity={c['schedule_parity']} "
          f"final_batch={c['final_batch']} "
          f"overhead {c['modeled']['overhead_frac']:.3f}")
    print(f"wrote {args.out}")
    return result


if __name__ == "__main__":
    main()

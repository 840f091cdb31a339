"""The layer names the round step leaves in its compiled program, and the
host spans the supervisor leaves in a profiler trace.

The device trace of a TPU carries each op's ``op_name`` path (its
``tf_op``); ``bench/scopes.py`` splits a local step into forward,
backward, update and view by these paths, and a round's consensus from
it. Here the same paths are read from the compiled HLO on the CPU.
"""
from __future__ import annotations

import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ROOT = os.path.join(os.path.dirname(__file__), "..")
LOCAL = ("dppf.local", "dppf.view", "jvp(dppf.model)",
         "transpose(jvp(dppf.model))", "dppf.update", "dppf.consensus")

SETUP = """
import jax, jax.numpy as jnp
from repro.configs import DPPFConfig, get_arch
from repro.configs.base import reduced
from repro.models import build_model
from repro.optim import make_optimizer
from repro.train import RoundClock, init_train_state

M, TAU, S = 4, 2, 16
model = build_model(reduced(get_arch("yi-6b")))
dcfg = DPPFConfig(alpha=0.1, lam=0.5, tau=TAU, engine="flat",
                  consensus="simple_avg")
opt = make_optimizer("sgd", momentum=0.9, weight_decay=1e-3)
clock = RoundClock.from_config(dcfg, base_lr=0.01, total_steps=4 * TAU)
state = init_train_state(model.init, opt, dcfg, M, jax.random.PRNGKey(0))
ids = jnp.zeros((TAU, M, 1, S), jnp.int32)
batch = {"tokens": ids, "labels": ids}
"""


def op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def has_scope(names, scope):
    """Some path holds ``scope`` as a whole component (``jvp(dppf.model)``
    must not match inside ``transpose(jvp(dppf.model))``)."""
    pat = re.compile(r"(^|/|\()" + re.escape(scope) + r"(/|$|\))")
    hits = [n for n in names if pat.search(n)]
    if scope.startswith("jvp("):
        hits = [n for n in hits if "transpose(" + scope not in n]
    return bool(hits)


def test_round_step_hlo_carries_the_layer_scopes():
    ns = {}
    exec(SETUP, ns)
    from repro.train import make_round_step
    step = jax.jit(make_round_step(ns["model"].loss, ns["opt"], ns["dcfg"],
                                   clock=ns["clock"]))
    names = op_names(step.lower(ns["state"], ns["batch"]).compile()
                     .as_text())
    for scope in LOCAL:
        assert has_scope(names, scope), scope
    # the view's transposes (the gradient's pads) are the view's too
    assert has_scope(names, "transpose(jvp(dppf.view))")
    # no scope of the benchmark's own namespace in the program
    assert not any("bench." in n for n in names)


def test_sharded_round_step_hlo_carries_the_layer_scopes():
    body = SETUP + """
import re
from repro.launch.mesh import make_flat_engine_mesh
from repro.train import make_sharded_round_step, shard_train_state
mesh, plan = make_flat_engine_mesh(M)
assert mesh.devices.size == 4
state = shard_train_state(state, mesh, plan, dcfg=dcfg)
step = jax.jit(make_sharded_round_step(model.loss, opt, dcfg, mesh=mesh,
                                       plan=plan, clock=clock))
txt = step.lower(state, batch).compile().as_text()
for n in sorted(set(re.findall(r'op_name="([^"]*)"', txt))):
    print("OP_NAME " + n)
"""
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep + ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", body], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    names = {l[len("OP_NAME "):] for l in out.stdout.splitlines()
             if l.startswith("OP_NAME ")}
    for scope in LOCAL:
        assert has_scope(names, scope), scope
    # the worker-row gather sits under the consensus, as its exchange
    assert any("dppf.consensus" in n and "dppf.exchange" in n
               and "all_gather" in n for n in names)


def test_ddp_step_hlo_carries_model_and_update_scopes():
    from benchmarks.common import mlp_init, mlp_loss
    from repro.optim import make_optimizer
    from repro.train import RoundClock, make_ddp_step
    from repro.train.trainer import TrainState
    opt = make_optimizer("sgd", momentum=0.9)
    p0 = mlp_init(jax.random.PRNGKey(0), 8, 4, 8)
    state = TrainState(params=p0, opt=opt.init(p0), cstate={},
                       t=jnp.zeros((), jnp.int32))
    clock = RoundClock(total_steps=4, tau=1, base_lr=0.1)
    batch = {"x": jnp.zeros((2, 3, 8)), "y": jnp.zeros((2, 3), jnp.int32)}
    step = jax.jit(make_ddp_step(mlp_loss, opt, clock=clock))
    names = op_names(step.lower(state, batch).compile().as_text())
    for scope in ("jvp(dppf.model)", "transpose(jvp(dppf.model))",
                  "dppf.update"):
        assert has_scope(names, scope), scope


# ---------------------------------------------------------------------------
# the supervisor's host spans and compile counter
# ---------------------------------------------------------------------------

def _mlp_run(steps=6):
    from benchmarks.common import mlp_init, mlp_loss
    from repro.configs import DPPFConfig
    from repro.optim import make_optimizer
    from repro.train import RoundClock, init_train_state, make_round_step
    M, dim, ncls = 2, 8, 4
    opt = make_optimizer("sgd", momentum=0.9)
    dcfg = DPPFConfig(alpha=0.1, lam=0.2, tau=2, engine="flat")
    clock = RoundClock.from_config(dcfg, base_lr=0.05, total_steps=steps)
    state = init_train_state(lambda k: mlp_init(k, dim, ncls, 8), opt, dcfg,
                             M, jax.random.PRNGKey(0))
    step = jax.jit(make_round_step(mlp_loss, opt, dcfg, clock=clock))

    def batch_fn(spec, bs):
        k = jax.random.fold_in(jax.random.PRNGKey(1), spec.index)
        return {"x": jax.random.normal(k, (spec.tau, M, bs, dim)),
                "y": jnp.zeros((spec.tau, M, bs), jnp.int32)}
    return clock, state, step, batch_fn, M


def test_supervisor_writes_round_spans(tmp_path):
    from jax.profiler import ProfileData
    from repro.train import Supervisor
    clock, state, step, batch_fn, M = _mlp_run()
    sup = Supervisor(clock, workers=M, batch_size=2)
    with jax.profiler.trace(str(tmp_path)):
        sup.run(state, step, batch_fn)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    counts = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("dppf."):
                        counts[e.name] = counts.get(e.name, 0) + 1
    n = clock.total_rounds
    for name in ("dppf.round", "dppf.batch", "dppf.dispatch", "dppf.wait",
                 "dppf.report"):
        assert counts.get(name) == n, (name, counts)


def test_supervisor_counts_compiles_and_names_the_recompile():
    """A batch whose shape changes at round k compiles the step again:
    ``counters['compile']`` counts both compiles and exactly one
    ``recompile`` event names round k, with its seconds."""
    from repro.train import Supervisor
    clock, state, step, batch_fn, M = _mlp_run(steps=8)
    k = 2
    rows = []
    sup = Supervisor(clock, workers=M, batch_size=2,
                     logger=lambda r, m: rows.append((r, dict(m))))
    sup.run(state, step,
            lambda spec, bs: batch_fn(spec, bs + (spec.index >= k)))
    assert sup.counters["compile"] >= 2
    rec = [e for e in sup.events if e["event"] == "recompile"]
    assert [e["round"] for e in rec] == [k] and rec[0]["seconds"] > 0
    # the event reaches the logger; the fault timeline stays without it
    assert [r for r, m in rows if m.get("event") == "recompile"] == [k]
    assert sup.event_seq() == []
    s = sup.summary()
    assert s["counters"] == {} and s["compiles"]["recompile"] == 1


def test_supervisor_compile_listener_ends_with_the_run():
    """The listener counts compiles only while ``run`` runs, whether it
    returns or raises."""
    from repro.train import Supervisor
    clock, state, step, batch_fn, M = _mlp_run()
    sup = Supervisor(clock, workers=M, batch_size=2)
    sup.run(state, step, batch_fn)
    seen = sup.counters["compile"]
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((5, 7))).block_until_ready()
    assert sup.counters["compile"] == seen

    def bad_step(st, b):
        jax.jit(lambda x: x - 2)(jnp.ones((3, 11))).block_until_ready()
        raise RuntimeError("step failed")

    sup2 = Supervisor(clock, workers=M, batch_size=2)
    with pytest.raises(RuntimeError, match="step failed"):
        sup2.run(_mlp_run()[1], bad_step, batch_fn)
    seen = sup2.counters.get("compile", 0)
    assert seen >= 1                      # counted while it ran
    jax.jit(lambda x: x / 5)(jnp.ones((2, 13))).block_until_ready()
    assert sup2.counters.get("compile", 0) == seen

"""``python -O`` smoke for the de-asserted validation paths.

Run as a SCRIPT under ``python -O`` (the CI leg): with asserts stripped,
every user-facing check must still raise a real exception. Uses explicit
raises (not ``assert``) to report, since asserts are off by construction.

    PYTHONPATH=src python -O tests/optcheck.py
"""
from __future__ import annotations

import dataclasses
import sys

import numpy as np


def expect_raises(exc, fn, label):
    try:
        fn()
    except exc:
        print(f"ok: {label}")
        return
    raise SystemExit(f"FAIL: {label} did not raise {exc.__name__} "
                     f"(python -O stripped the check?)")


def main():
    if __debug__:
        print("warning: running with asserts ON — use python -O",
              file=sys.stderr)

    from repro.configs.base import DPPFConfig
    expect_raises(ValueError, lambda: DPPFConfig(engine="nope"),
                  "DPPFConfig unknown engine")
    expect_raises(ValueError, lambda: DPPFConfig(tau_schedule="nope"),
                  "DPPFConfig unknown tau schedule")
    expect_raises(ValueError, lambda: DPPFConfig(tau_schedule="qsr"),
                  "DPPFConfig qsr without beta")
    expect_raises(ValueError, lambda: DPPFConfig(overlap="bogus"),
                  "DPPFConfig unknown overlap mode")
    expect_raises(ValueError,
                  lambda: DPPFConfig(engine="flat", overlap="doublebuf",
                                     overlap_chunks=0),
                  "DPPFConfig overlap_chunks < 1")
    expect_raises(ValueError, lambda: DPPFConfig(overlap="doublebuf"),
                  "DPPFConfig doublebuf on tree engine")

    from repro.train import RoundClock
    expect_raises(ValueError,
                  lambda: RoundClock(total_steps=8, tau=4,
                                     tau_schedule="qsr", qsr_beta=0.0),
                  "RoundClock qsr without beta")
    expect_raises(ValueError,
                  lambda: RoundClock(total_steps=8, tau=4, overlap="bogus"),
                  "RoundClock unknown overlap mode")
    expect_raises(ValueError,
                  lambda: RoundClock(total_steps=8, tau=4, warmup=-1),
                  "RoundClock negative warmup")

    from repro.core import consensus
    import jax.numpy as jnp
    stacked = {"w": jnp.zeros((2, 3))}
    expect_raises(ValueError,
                  lambda: consensus.consensus_target("lsgd", stacked, {}),
                  "lsgd without losses (tree)")
    expect_raises(ValueError,
                  lambda: consensus.consensus_target("mgrawa", stacked, {}),
                  "mgrawa without grad norms (tree)")
    from repro.core.engine import ConsensusEngine
    eng = ConsensusEngine.from_stacked(stacked, method="lsgd")
    flat = eng.flatten(stacked)
    dcfg = DPPFConfig(consensus="lsgd", engine="flat")
    expect_raises(ValueError,
                  lambda: consensus.apply_round(flat, dcfg, 0.1, {},
                                                engine=eng),
                  "lsgd without losses (flat)")

    # method registry: unknown names, malformed specs, flat-only methods
    # on the tree engine, and the flat-path filtered-grad contract — all
    # ValueError (the registry validates in __post_init__, not assert)
    from repro.core.methods import MethodSpec, get_method
    expect_raises(ValueError, lambda: get_method("sgd_flavour_9000"),
                  "registry unknown method")
    expect_raises(ValueError,
                  lambda: MethodSpec(name="bad", doc="", weight_fn="uniform",
                                     aux_pull=1.0),
                  "MethodSpec aux_pull without aux row")
    expect_raises(ValueError,
                  lambda: MethodSpec(name="bad", doc="", weight_fn="uniform",
                                     push_source="filtered_grad",
                                     filter_mu=1.5),
                  "MethodSpec filter_mu out of range")
    expect_raises(ValueError,
                  lambda: DPPFConfig(consensus="lpf_sgd", engine="tree"),
                  "flat-only method on tree engine")
    lcfg = DPPFConfig(consensus="lpf_sgd", engine="flat")
    leng = ConsensusEngine.from_stacked(stacked, method="lpf_sgd")
    expect_raises(ValueError,
                  lambda: consensus.apply_round(leng.flatten(stacked), lcfg,
                                                0.1, {}, engine=leng),
                  "lpf_sgd without push_vec (flat)")
    ecfg = dataclasses.replace(dcfg, exact_second_term=True)
    expect_raises(ValueError,
                  lambda: consensus.apply_round(
                      flat, ecfg, 0.1, {}, losses=jnp.zeros((2,)),
                      engine=eng, mask=jnp.ones((2,))),
                  "elastic mask with exact second term")

    from repro.launch.mesh import make_hier_engine_mesh, make_hierarchical_mesh
    expect_raises(ValueError, lambda: make_hierarchical_mesh(7, 5, 3),
                  "hierarchical mesh with impossible factors")
    expect_raises(ValueError, lambda: make_hierarchical_mesh(0, 2, 2),
                  "hierarchical mesh with zero-size axis")
    import jax
    devs = jax.devices()
    expect_raises(ValueError,
                  lambda: make_hierarchical_mesh(2, 2, 2, devices=devs[:1]),
                  "hierarchical mesh product != given devices")
    expect_raises(ValueError,
                  lambda: make_hier_engine_mesh(len(devs) + 1, 2, 2),
                  "hierarchical engine mesh beyond host devices")

    from repro.launch.specs import train_batch_specs
    from repro.configs.base import InputShape
    from repro.configs import ARCHS
    shape = InputShape("odd", 8, 7, "train")
    expect_raises(ValueError,
                  lambda: train_batch_specs(ARCHS["yi-6b"], shape, 4, 2),
                  "train batch not divisible by workers")

    # serving surface: slot overflow, bad sampling params, ring-contract
    # conflicts — all ValueError (never assert) so they survive -O
    from repro.serving import Request, SamplingParams, Scheduler, SlotEngine
    from repro.serving import generate
    expect_raises(ValueError, lambda: SamplingParams(temperature=-1.0),
                  "SamplingParams negative temperature")
    expect_raises(ValueError, lambda: SamplingParams(top_k=-1),
                  "SamplingParams negative top_k")
    expect_raises(ValueError, lambda: SamplingParams(top_p=0.0),
                  "SamplingParams top_p out of range")
    expect_raises(ValueError, lambda: Scheduler(0),
                  "Scheduler zero slots")
    expect_raises(ValueError, lambda: Scheduler(1, mode="adaptive"),
                  "Scheduler unknown mode")
    expect_raises(ValueError,
                  lambda: Request(rid=0, tokens=np.zeros((0,)),
                                  max_new_tokens=1),
                  "Request empty prompt")

    from repro.configs import reduced
    from repro.models import build_model
    scfg = reduced(ARCHS["yi-6b"])
    smodel = build_model(scfg)
    sparams = smodel.init(jax.random.PRNGKey(0))
    expect_raises(ValueError,
                  lambda: SlotEngine(smodel, sparams, max_slots=0, buf_len=8),
                  "SlotEngine zero slots")
    expect_raises(ValueError,
                  lambda: SlotEngine(smodel, sparams, max_slots=1, buf_len=8,
                                     window=9),
                  "SlotEngine window exceeds buf_len")
    expect_raises(ValueError,
                  lambda: SlotEngine(smodel, sparams, max_slots=1, buf_len=16,
                                     window=16, chunk=8),
                  "SlotEngine chunk clobbers live ring slots")
    seng = SlotEngine(smodel, sparams, max_slots=1, buf_len=16)
    expect_raises(ValueError,
                  lambda: seng.insert(seng.blank_slots(), None, 1, 0, 0, 4,
                                      np.zeros(2, np.uint32)),
                  "SlotEngine slot overflow")
    expect_raises(ValueError,
                  lambda: Scheduler(1).submit(
                      Request(rid=0, tokens=np.ones((10,), np.int64),
                              max_new_tokens=10), seng),
                  "Scheduler submit beyond windowless buf_len")
    expect_raises(ValueError,
                  lambda: generate(smodel, sparams,
                                   {"tokens": np.zeros((1, 20), np.int32)},
                                   max_new_tokens=2, buf_len=16),
                  "generate windowless prompt overflow")

    from repro.models.attention import cache_update, init_cache
    import jax.numpy as jnp2
    cache = init_cache(1, 1, 4, 2, jnp2.float32)
    big = jnp2.zeros((1, 5, 1, 2))
    expect_raises(ValueError, lambda: cache_update(cache, big, big, 0),
                  "cache_update write exceeds buf_len")

    from repro.launch.roofline import serving_model
    expect_raises(ValueError,
                  lambda: serving_model(ARCHS["gemma2-2b"], max_slots=0,
                                        chunk=1, state_bytes_per_slot=1,
                                        device_kind="TPU v5 lite"),
                  "serving_model zero slots")

    # autotune surface (--autotune CI leg runs under -O): the search
    # space, the qsr/autotune conflict, and the probe roofline all
    # validate via ValueError, never assert
    from repro.train.autotune import Candidate, TunePlan, TuneSpace
    expect_raises(ValueError, lambda: TuneSpace(probe_budget=0),
                  "TuneSpace probe budget < 1")
    expect_raises(ValueError, lambda: TuneSpace(min_batch=8, max_batch=4),
                  "TuneSpace min_batch > max_batch")
    qsr_plan = TunePlan(chosen=Candidate(batch=4, tau=4, overlap_chunks=1),
                        probes=(), failures=(), probe_budget=1,
                        probes_used=1, overlap="doublebuf", staleness=1,
                        residual_scale=1.0, dominates_model=True,
                        dominates_measured=True)
    expect_raises(ValueError,
                  lambda: DPPFConfig(engine="flat", tau_schedule="qsr",
                                     qsr_beta=0.4).apply_tune_plan(qsr_plan),
                  "apply_tune_plan under a qsr schedule")
    from repro.launch.roofline import probe_round_model
    expect_raises(ValueError,
                  lambda: probe_round_model(work_s_per_step=1e-6, tau=4,
                                            gather_bytes=1e6, mode="bogus",
                                            device_kind="TPU v5 lite"),
                  "probe_round_model unknown overlap mode")

    import tempfile, os
    from repro.checkpoint import load_pytree, save_pytree
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "x.npz")
        save_pytree(p, {"w": np.zeros((3, 3))})
        expect_raises(ValueError,
                      lambda: load_pytree(p, {"w": np.zeros((2, 2))}),
                      "checkpoint shape mismatch")
        with open(p, "r+b") as f:
            f.truncate(40)
        expect_raises(ValueError,
                      lambda: load_pytree(p, {"w": np.zeros((3, 3))}),
                      "checkpoint truncated archive")

    # fault-tolerance surface (--chaos CI leg runs under -O): ChaosPlan
    # authoring/payload guards, the membership tables, and the supervisor
    # policy knobs all validate via ValueError, never assert
    from repro.train import (ChaosEvent, ChaosPlan, HeartbeatMembership,
                             ScheduleMembership, Supervisor)
    expect_raises(ValueError, lambda: ChaosEvent(round=0, kind="meteor"),
                  "ChaosEvent unknown kind")
    expect_raises(ValueError, lambda: ChaosEvent(round=0, kind="oom"),
                  "ChaosEvent oom without batch_above")
    expect_raises(ValueError, lambda: ChaosEvent(round=0, kind="kill"),
                  "ChaosEvent kill without worker")
    expect_raises(ValueError, lambda: ChaosPlan.from_dict({"seed": 1}),
                  "ChaosPlan malformed payload")
    expect_raises(ValueError, lambda: ChaosPlan(version=99),
                  "ChaosPlan version mismatch")
    expect_raises(ValueError,
                  lambda: HeartbeatMembership(2, timeout=0.0),
                  "HeartbeatMembership timeout <= 0")
    expect_raises(ValueError,
                  lambda: ScheduleMembership(4, [(1, 3, 3)]),
                  "ScheduleMembership empty drop window")
    clk = RoundClock(total_steps=8, tau=4)
    expect_raises(ValueError, lambda: Supervisor(clk, workers=4, quorum=-1),
                  "Supervisor negative quorum")
    expect_raises(ValueError, lambda: Supervisor(clk, workers=4, quorum=5),
                  "Supervisor quorum > workers")
    expect_raises(ValueError,
                  lambda: Supervisor(clk, workers=4, retry_budget=-1),
                  "Supervisor negative retry budget")
    from repro.launch.roofline import supervisor_model
    expect_raises(ValueError,
                  lambda: supervisor_model(rounds=2, tau=2,
                                           work_s_per_step=1e-3,
                                           gather_bytes=1e6,
                                           device_kind="TPU v5 lite",
                                           degraded_rounds=3),
                  "supervisor_model degraded_rounds > rounds")

    # launcher flag surface (argparse exits with code 2 on ap.error)
    from repro.launch import train as train_mod
    expect_raises(SystemExit,
                  lambda: train_mod.main(["--smoke", "--elastic-drop",
                                          "2,5,3", "--overlap",
                                          "staleness_k"]),
                  "--elastic-drop empty/negative window")
    expect_raises(SystemExit,
                  lambda: train_mod.main(["--smoke", "--quorum", "2"]),
                  "--quorum without a membership source")
    expect_raises(SystemExit,
                  lambda: train_mod.main(["--smoke", "--elastic-drop",
                                          "1,0,2", "--quorum", "2",
                                          "--heartbeat-timeout", "0",
                                          "--overlap", "staleness_k"]),
                  "--heartbeat-timeout <= 0")
    print("python -O validation smoke: all checks raise")


if __name__ == "__main__":
    main()

"""Training launcher: DPPF (or DDP) on any assigned architecture.

CPU-runnable end-to-end driver (the examples call this); on a real pod the
same script runs under the production mesh with the dry-run's shardings.

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
      --workers 4 --tau 4 --alpha 0.1 --lam 0.5 --steps 200

Without ``--smoke`` the published config runs at its published widths;
``--layers`` and ``--vocab`` cut it to one chip's share
(``configs.cut``), e.g. yi-6b on one TPU v5e:

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --layers 1 \
      --vocab 8000 --workers 4 --tau 2 --seq 2048 --batch 1 --steps 8
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from repro.checkpoint import load_train_state, save_pytree, save_train_state
from repro.configs import ARCHS, DPPFConfig, cut, get_arch, reduced
from repro.core import methods as method_registry
from repro.data import TokenTask, make_lm_batch, make_round_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim import make_optimizer
from repro.train import (
    ChaosMembership, ChaosPlan, FaultInjector, RoundClock,
    ScheduleMembership, Supervisor, init_train_state, make_ddp_step,
    make_round_step, make_sharded_round_step, shard_train_state,
)
from repro.train.clock import RoundMetricsLogger
from repro.train.trainer import TrainState, average_params


@dataclass
class TrainRun:
    """What ``main`` returns: the held-out eval loss, the final train
    state, and each round's wall seconds (ending in ``block_until_ready``;
    the first includes its compile)."""
    eval_loss: float
    state: Any = None
    round_s: list = field(default_factory=list)


def main(argv=None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--d-model", type=int, default=0,
                    help="override d_model of the smoke config (e.g. scale "
                         "toward ~100M params); --smoke only")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep the first N layers (whole layer-pattern "
                         "periods) of the config")
    ap.add_argument("--vocab", type=int, default=0,
                    help="keep V vocabulary rows (at least 1/8 of the "
                         "published vocabulary without --smoke)")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--tau", type=int, default=4)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--lam", type=float, default=0.5)
    # method choices and help come from the registry (core.methods): one
    # line per registered MethodSpec, aliases included in the choices
    method_help = "; ".join(
        f"{s.name} = {s.doc}"
        for s in (method_registry.get_method(n)
                  for n in method_registry.method_names(aliases=False)))
    flat_only = ", ".join(
        n for n in method_registry.method_names(aliases=False)
        if method_registry.get_method(n).requires_flat)
    ap.add_argument("--method", "--consensus", dest="consensus",
                    default="simple_avg",
                    choices=method_registry.method_names(),
                    help="consensus method (registry core.methods): "
                         + method_help)
    ap.add_argument("--engine", default="flat", choices=["tree", "flat"],
                    help="consensus execution engine (flat = persistent "
                         "(R, n) view — worker rows plus aux consensus-"
                         "state rows — with fused Gram/mixing round "
                         "update). Registry methods marked flat-only "
                         f"({flat_only}) refuse engine=tree")
    ap.add_argument("--overlap", default="none",
                    choices=["none", "staleness1", "doublebuf",
                             "staleness_k"],
                    help="staleness1 = apply the consensus computed from "
                         "the previous round's snapshot, hiding the "
                         "all-reduce behind the tau local steps; doublebuf "
                         "= additionally dispatch the snapshot's worker-"
                         "row gather + partial-Gram psum in chunks "
                         "interleaved with the scan, leaving only the mix "
                         "GEMM at the boundary (flat engine only); "
                         "staleness_k = generalize the carry to a k-deep "
                         "snapshot ring (--staleness) whose mid-scan "
                         "gather runs as a ppermute ring, spreading one "
                         "consensus over k rounds of compute")
    ap.add_argument("--overlap-chunks", type=int, default=4,
                    help="doublebuf/staleness_k: column chunks the "
                         "mid-scan snapshot comm splits into (1 = "
                         "bit-for-bit staleness1 consensus numerics)")
    ap.add_argument("--staleness", type=int, default=1,
                    help="staleness_k: ring depth k — round r applies the "
                         "consensus of the round-(r-k) snapshot; rounds "
                         "0..k-1 are exact-consensus pipeline fill (k=1 "
                         "is bit-for-bit doublebuf at --overlap-chunks 1)")
    ap.add_argument("--elastic", action="store_true",
                    help="staleness_k: bounded-async elastic rounds — a "
                         "worker row may sit out up to k rounds (frozen "
                         "params, dropped from the Gram target weights) "
                         "and rejoins with an EASGD-style catch-up pull")
    ap.add_argument("--elastic-catchup", type=float, default=0.5,
                    help="elastic: fraction of the gap to the active-row "
                         "mean a rejoining row closes on re-entry")
    ap.add_argument("--elastic-drop", default="", metavar="W,A,B",
                    help="elastic demo: mark worker row W inactive for "
                         "rounds [A, B) via train.set_participation (the "
                         "bounded-staleness clamp still forces a rejoin "
                         "after k missed rounds); runs through the same "
                         "supervisor loop as --chaos, as the trivial "
                         "ScheduleMembership provider")
    ap.add_argument("--chaos", default="", metavar="PLAN.json",
                    help="run under the fault-tolerant supervisor with a "
                         "replayable ChaosPlan (train.chaos): scripted "
                         "kill/stall/netdrop windows drive the heartbeat "
                         "membership table, oom events raise "
                         "RESOURCE_EXHAUSTED at the trainer boundary "
                         "(batch shrinks and the round replays from the "
                         "last good checkpoint), corrupt_ckpt events tear "
                         "a written checkpoint (the restore ladder falls "
                         "back to the previous rotation copy). The same "
                         "plan replays to a bit-identical recovery-event "
                         "sequence")
    ap.add_argument("--quorum", type=int, default=0,
                    help="minimum active worker rows for a consensus "
                         "round; below it the round degrades to local-"
                         "only steps (consensus skipped bit-exactly, "
                         "logged, backed off). 0 = disabled; requires a "
                         "membership source (--chaos or --elastic-drop)")
    ap.add_argument("--heartbeat-timeout", type=float, default=0.9,
                    help="seconds of heartbeat silence before a "
                         "membership poll counts a missed deadline (the "
                         "chaos clock is virtual: one round = 1s, so the "
                         "default suspects a worker on its first fully "
                         "silent round); must be > 0")
    ap.add_argument("--retry-budget", type=int, default=3,
                    help="supervisor: max CONSECUTIVE failed rounds "
                         "(restore + replay each) before the failure "
                         "propagates")
    ap.add_argument("--sharded", action="store_true",
                    help="run the round under shard_map on all local "
                         "devices (launch.mesh.make_flat_engine_mesh; "
                         "flat engine only)")
    ap.add_argument("--mesh", default="", metavar="W,F,M",
                    help="workers,fsdp,model — run the round under "
                         "shard_map on a hierarchical 3-axis mesh of "
                         "local devices (launch.mesh.make_hier_engine_"
                         "mesh; flat engine only): worker rows over the "
                         "first axis, flat-view columns over fsdp x "
                         "model. E.g. --mesh 2,2,2 under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8")
    ap.add_argument("--lam-schedule", default="increasing")
    ap.add_argument("--tau-schedule", default="fixed",
                    choices=["fixed", "qsr"],
                    help="qsr = Quadratic Synchronization Rule (§7.2): "
                         "tau_t = max(tau, floor((qsr_beta/lr_t)^2)) per "
                         "round — fewer consensus all-reduces as the "
                         "cosine LR decays")
    ap.add_argument("--qsr-beta", type=float, default=0.0,
                    help="QSR beta (required > 0 with --tau-schedule qsr)")
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adamw"])
    ap.add_argument("--sam-rho", type=float, default=0.0)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8, help="per-worker batch")
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--warmup", type=int, default=0,
                    help="linear LR warmup steps; the RoundClock samples "
                         "the FULL schedule (warmup + cosine) — QSR rounds "
                         "inside the warmup keep the base tau instead of "
                         "blowing up on the tiny warmup LR")
    ap.add_argument("--log-every-round", default="", metavar="PATH",
                    help="write one JSON line of the unified round-metrics "
                         "dict (consensus_dist/pull_force/push_force/"
                         "staleness, plus the clock position) per round to "
                         "PATH (train.clock.RoundMetricsLogger; the ddp "
                         "branch logs per step on its tau=1 clock)")
    ap.add_argument("--autotune", action="store_true",
                    help="probe-search the operating point before training "
                         "(train.autotune, DESIGN.md §Autotune): power-of-"
                         "two batch probes with OOM backoff + binary "
                         "refinement, then a joint (tau, overlap_chunks) "
                         "sweep at the frontier batch, scored by measured "
                         "round time reconciled against the roofline "
                         "overlap model; training then runs at the chosen "
                         "point (--batch/--max-batch bound the ladder, "
                         "--tau seeds the tau ladder {tau, 2*tau})")
    ap.add_argument("--tune-plan", default="", metavar="PATH",
                    help="with --autotune: write the searched TunePlan "
                         "JSON to PATH; without: load a committed TunePlan "
                         "from PATH and train at its chosen point (replay "
                         "is deterministic — the plan pins batch, tau, "
                         "overlap_chunks)")
    ap.add_argument("--probe-budget", type=int, default=16,
                    help="autotune: max probes (distinct candidates "
                         "measured or OOMed); on exhaustion the best "
                         "point found so far wins")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="autotune: batch-ladder ceiling (0 = 8x --batch)")
    ap.add_argument("--tune-oom-above", type=int, default=0,
                    help="autotune fault injection (CI): probes with "
                         "batch > this raise a scripted RESOURCE_EXHAUSTED "
                         "before touching the device, exercising the "
                         "backoff path without real memory pressure "
                         "(0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="",
                    help="checkpoint path: final (serving) params are "
                         "written here as before; DPPF runs additionally "
                         "keep a mid-run resume point at "
                         "<ckpt>.state.npz and resume from it when it "
                         "exists")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)
    mspec = method_registry.get_method(args.consensus)
    if (args.sharded or args.mesh) and (args.engine != "flat"
                                        or not mspec.communicates):
        ap.error("--sharded/--mesh require --engine flat and a "
                 "communicating consensus method (the shard_map round "
                 "runs on the flat engine's (R, n) view)")
    if args.sharded and args.mesh:
        ap.error("--sharded and --mesh are mutually exclusive (--mesh IS "
                 "a sharded run on an explicit workers,fsdp,model shape)")
    if (args.autotune or args.tune_plan) and (
            args.tau_schedule == "qsr" or args.qsr_beta > 0):
        ap.error("--autotune/--tune-plan pin a fixed tau at the measured "
                 "comm/compute crossover; --tau-schedule qsr would "
                 "re-adapt it — drop --qsr-beta when tuning")
    if args.autotune and not mspec.communicates:
        ap.error("--autotune searches the communication round's operating "
                 "point and needs a communicating consensus method")
    mesh_shape = ()
    if args.mesh:
        try:
            mesh_shape = tuple(int(x) for x in args.mesh.split(","))
            if len(mesh_shape) != 3:
                raise ValueError
        except ValueError:
            ap.error("--mesh expects three comma-separated ints: "
                     "workers,fsdp,model (e.g. --mesh 2,2,2)")
    # supervisor / membership flag validation — all before any model work
    drop_spec = ()
    if args.elastic_drop:
        try:
            drop_spec = tuple(int(x) for x in args.elastic_drop.split(","))
            if len(drop_spec) != 3 or not 0 <= drop_spec[0] < args.workers:
                raise ValueError
        except ValueError:
            ap.error("--elastic-drop expects W,A,B with worker row "
                     "0 <= W < --workers (e.g. --elastic-drop 2,3,5)")
        if not 0 <= drop_spec[1] < drop_spec[2]:
            ap.error(f"--elastic-drop window [{drop_spec[1]}, "
                     f"{drop_spec[2]}) is empty or negative — need "
                     "0 <= A < B (e.g. --elastic-drop 2,3,5)")
    if args.chaos and drop_spec:
        ap.error("--chaos and --elastic-drop are mutually exclusive (the "
                 "plan's kill/stall/netdrop events already script the "
                 "membership windows)")
    if args.heartbeat_timeout <= 0:
        ap.error("--heartbeat-timeout must be > 0 seconds")
    if args.retry_budget < 0:
        ap.error("--retry-budget must be >= 0")
    if not 0 <= args.quorum <= args.workers:
        ap.error(f"--quorum {args.quorum} must be in [0, --workers] "
                 f"({args.workers})")
    chaos_plan = None
    if args.chaos:
        try:
            chaos_plan = ChaosPlan.load(args.chaos)
        except ValueError as e:
            ap.error(f"--chaos {args.chaos}: {e}")
    if args.quorum and chaos_plan is None and not drop_spec:
        ap.error("--quorum needs a membership source: a --chaos plan or "
                 "an --elastic-drop window")
    needs_membership = bool(drop_spec) or args.quorum > 0 or (
        chaos_plan is not None and bool(chaos_plan.membership_events()))
    if needs_membership and args.overlap != "staleness_k":
        ap.error("membership-driven rounds (--elastic-drop / --quorum / "
                 "a --chaos plan with kill|stall|netdrop events) ride the "
                 "elastic staleness_k carry — add --overlap staleness_k "
                 "(with --staleness K)")
    if needs_membership and not mspec.communicates:
        ap.error("membership/quorum supervision needs a communicating "
                 "consensus method (a local-only method never syncs, so "
                 "there is nothing to degrade or rejoin)")

    published = cfg = get_arch(args.arch)
    if args.smoke:
        over = {}
        if args.d_model:
            over.update(d_model=args.d_model,
                        head_dim=max(args.d_model // 4, 32),
                        d_ff=2 * args.d_model if cfg.d_ff else 0)
        if args.layers:
            over["n_layers"] = args.layers
        if args.vocab:
            over["vocab_size"] = args.vocab
        cfg = reduced(cfg, **over)
    else:
        if args.d_model:
            ap.error("--d-model changes a width; without --smoke the "
                     "published widths are kept")
        try:
            cfg = cut(cfg, layers=args.layers, vocab=args.vocab)
        except ValueError as e:
            ap.error(str(e))
    enable_compile_cache()
    model = build_model(cfg)
    n_params = sum(l.size for l in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    if not args.smoke:
        print(f"cut: arch={cfg.name} layers={cfg.n_layers}/"
              f"{published.n_layers} vocab={cfg.vocab_size}/"
              f"{published.vocab_size} n={n_params} workers={args.workers}")
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M workers={args.workers} "
          f"tau={args.tau} alpha={args.alpha} lam={args.lam}")

    task = TokenTask(vocab_size=cfg.vocab_size, seq_len=args.seq)
    dcfg = DPPFConfig(alpha=args.alpha, lam=args.lam, tau=args.tau,
                      consensus=args.consensus, engine=args.engine,
                      overlap=args.overlap,
                      overlap_chunks=args.overlap_chunks,
                      staleness=args.staleness,
                      elastic=args.elastic or needs_membership,
                      elastic_catchup=args.elastic_catchup,
                      lam_schedule=args.lam_schedule,
                      tau_schedule=args.tau_schedule, qsr_beta=args.qsr_beta)
    opt = make_optimizer(args.optimizer, momentum=0.9, weight_decay=1e-3)
    key = jax.random.PRNGKey(args.seed)

    # --autotune: search the (batch, tau, overlap_chunks) operating point
    # on the real round step before committing to a plan; --tune-plan
    # alone replays a committed TunePlan (DESIGN.md §Autotune)
    batch_size, tune_plan = args.batch, None
    if args.autotune:
        from repro.train import (TuneSpace, inject_oom_above,
                                 make_lm_model_fn, make_round_probe_runner)
        from repro.train import autotune as tune
        space = TuneSpace(min_batch=args.batch,
                          max_batch=args.max_batch or args.batch * 8,
                          taus=(args.tau, args.tau * 2), chunks=(1, 2, 4),
                          probe_budget=args.probe_budget,
                          overlap=args.overlap, staleness=args.staleness)
        runner = make_round_probe_runner(
            model.init, model.loss, opt, dcfg, args.workers,
            lambda cand: make_round_batch(task, args.seed, args.workers,
                                          cand.tau, 0, cand.batch, cfg),
            base_lr=args.lr, total_steps=args.steps, seed=args.seed)
        if args.tune_oom_above:
            runner = inject_oom_above(runner, args.tune_oom_above)
        # the probe ranking's model is priced on the chip this repo
        # targets (launch.roofline.PEAKS); the measured probes rescale it
        model_fn = make_lm_model_fn(n_params=n_params, seq=args.seq,
                                    workers=args.workers,
                                    overlap=args.overlap,
                                    device_kind="TPU v5 lite",
                                    staleness=args.staleness)
        tune_plan = tune(runner, model_fn, space)
        ch = tune_plan.chosen
        print(f"autotune: chose batch={ch.batch} tau={ch.tau} "
              f"chunks={ch.overlap_chunks} after {tune_plan.probes_used} "
              f"probes (OOM batches: {list(tune_plan.failures) or 'none'}, "
              f"model scale {tune_plan.residual_scale:.3f})")
        if args.tune_plan:
            tune_plan.save(args.tune_plan)
            print(f"tune plan -> {args.tune_plan}")
    elif args.tune_plan:
        from repro.train import TunePlan
        tune_plan = TunePlan.load(args.tune_plan)
        ch = tune_plan.chosen
        print(f"tune plan <- {args.tune_plan}: batch={ch.batch} "
              f"tau={ch.tau} chunks={ch.overlap_chunks}")

    # the RoundClock is the single source of truth for step/round
    # accounting: round plan (incl. the steps % tau remainder, warmup
    # rounds, QSR-adaptive taus — stale-LR ruled under overlap), lam_t,
    # and LR position (DESIGN.md §Round-clock)
    if tune_plan is not None:
        clock = RoundClock.from_tune_plan(tune_plan, base_lr=args.lr,
                                          total_steps=args.steps,
                                          warmup=args.warmup, dcfg=dcfg)
        dcfg = dcfg.apply_tune_plan(tune_plan)
        batch_size = tune_plan.chosen.batch
    else:
        clock = RoundClock.from_config(dcfg, base_lr=args.lr,
                                       total_steps=args.steps,
                                       warmup=args.warmup)
    logger = RoundMetricsLogger(args.log_every_round) \
        if args.log_every_round else None

    t0 = time.time()
    if not mspec.communicates:
        p0 = model.init(key)
        state = TrainState(params=p0, opt=opt.init(p0), cstate={},
                           t=jnp.zeros((), jnp.int32))
        step = jax.jit(make_ddp_step(model.loss, opt, clock=clock,
                                     sam_rho=args.sam_rho))
        for s in range(args.steps):
            batch = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[make_lm_batch(task, args.seed, m, s, args.batch, cfg)
                  for m in range(args.workers)])
            state, m = step(state, batch)
            if logger is not None:   # ddp: per step on the tau=1 clock
                logger(s, m)
            if s % (args.log_every * args.tau) == 0:
                print(f"step {s:5d} loss {float(m['train_loss']):.4f}")
        final = state.params
    else:
        state = init_train_state(model.init, opt, dcfg, args.workers, key)
        # the resume point lives NEXT TO the final-params checkpoint (which
        # keeps its serving format at args.ckpt, see launch/serve.py)
        state_file = stem = ""
        if args.ckpt:
            stem = args.ckpt[:-4] if args.ckpt.endswith(".npz") else args.ckpt
            state_file = stem + ".state.npz"
        if state_file and os.path.exists(state_file):
            state = load_train_state(state_file, state, clock=clock)
            # the saved round index belongs to the plan that WROTE the
            # checkpoint; if this run's plan differs (changed --steps /
            # --lr / tau schedule), re-derive the position from the step
            # counter — a silent mismatch would replay or skip data
            import dataclasses as _dc
            t_res, rnd = int(state.t), int(state.round)
            if rnd >= clock.total_rounds or clock.rounds[rnd].start != t_res:
                rnd = clock.round_of_step(t_res)   # raises if t > steps
                if rnd < clock.total_rounds and \
                        clock.rounds[rnd].start != t_res:
                    raise ValueError(
                        f"checkpoint step {t_res} is mid-round in this "
                        f"run's plan (round {rnd} starts at "
                        f"{clock.rounds[rnd].start}) — resume with the "
                        "original --steps/--lr/--tau-schedule/--qsr-beta")
                state = _dc.replace(
                    state, round=jnp.asarray(rnd, jnp.int32))
            print(f"resumed from {state_file} at step {t_res} "
                  f"(round {rnd})")
        if args.sharded or mesh_shape:
            if mesh_shape:
                from repro.launch.mesh import make_hier_engine_mesh
                mesh, plan = make_hier_engine_mesh(*mesh_shape)
            else:
                from repro.launch.mesh import make_flat_engine_mesh
                mesh, plan = make_flat_engine_mesh(args.workers)
            print(f"sharded round on mesh {dict(mesh.shape)}")
            # resume happened ABOVE on host arrays, so a checkpoint written
            # under any mesh shape (or none) reshards here — the 2x2x2 ->
            # 8x1 cross-shape resume the tests pin
            state = shard_train_state(state, mesh, plan, dcfg=dcfg)
            step = jax.jit(make_sharded_round_step(
                model.loss, opt, dcfg, mesh=mesh, plan=plan, clock=clock,
                sam_rho=args.sam_rho), donate_argnums=0)
        else:
            # donation keeps the flat engine's (R, n) view (and the opt
            # state) in place across rounds — no per-round parameter copies
            step = jax.jit(make_round_step(model.loss, opt, dcfg,
                                           clock=clock,
                                           sam_rho=args.sam_rho),
                           donate_argnums=0)
        # the fault-tolerant supervisor owns the round iteration
        # (train/supervisor.py): it iterates the clock's round plan (every
        # step runs — the remainder round is part of the plan; a QSR tau
        # change simply retraces under jit), polls membership into the
        # participation mask, degrades below-quorum rounds to local-only
        # steps, and recovers failed rounds from rotation checkpoints.
        # With no membership and no chaos it is bit-for-bit the plain
        # `for spec in clock.rounds` loop this replaced.
        membership = injector = None
        if chaos_plan is not None:
            injector = FaultInjector(chaos_plan)
            if needs_membership:
                membership = ChaosMembership(chaos_plan, args.workers,
                                             timeout=args.heartbeat_timeout)
        elif drop_spec:
            membership = ScheduleMembership(args.workers, [drop_spec])
        sup_dir = ""
        if chaos_plan is not None:
            # recovery checkpoints (the sup_last/sup_prev rotation pair)
            # live next to the resume point when --ckpt names one, else
            # in a scratch dir for this run only
            sup_dir = stem + ".sup" if stem \
                else tempfile.mkdtemp(prefix="dppf-sup-")
        place_fn = None
        if args.sharded or mesh_shape:
            place_fn = (lambda st:
                        shard_train_state(st, mesh, plan, dcfg=dcfg))

        def on_round(spec, m):
            if spec.index % args.log_every == 0:
                # state.t after the step == spec.start + spec.tau
                print(f"round {spec.index:4d} "
                      f"(step {spec.start + spec.tau:5d} "
                      f"tau {spec.tau:3d}) "
                      f"loss {float(m['train_loss']):.4f} "
                      f"consensus_dist {float(m['consensus_dist']):.3f} "
                      f"lam_t {float(m.get('lam_t', 0)):.3f} "
                      f"wall {sup.round_wall_s[-1]:.3f}s")

        sup = Supervisor(clock, workers=args.workers, membership=membership,
                         quorum=args.quorum, retry_budget=args.retry_budget,
                         chaos=injector, ckpt_dir=sup_dir,
                         tune_plan=tune_plan, batch_size=batch_size,
                         logger=logger, on_round=on_round,
                         place_fn=place_fn, seed=args.seed)
        state = sup.run(
            state, step,
            lambda spec, bs: make_round_batch(task, args.seed, args.workers,
                                              spec.tau, spec.start, bs, cfg),
            start_round=int(state.round))
        s = sup.summary()
        if s["event_seq"]:
            print("supervisor events: " + " ".join(s["event_seq"]))
            print("supervisor counters: " + " ".join(
                f"{k}={v}" for k, v in s["counters"].items())
                  + f" final_batch={s['final_batch']}")
        if s["compiles"]:
            # recompile events (round, seconds) are in --log-every-round
            print("supervisor compiles: " + " ".join(
                f"{k}={v}" for k, v in s["compiles"].items()))
        print(f"comm rounds {clock.total_rounds} "
              f"(fixed tau={args.tau} would take {clock.fixed_rounds}; "
              f"all-reduces saved {clock.fixed_rounds - clock.total_rounds})")
        if state_file:
            save_train_state(state_file, state)
            print(f"train-state resume point -> {state_file}")
        final = average_params(state)

    # held-out eval
    eval_batch = make_lm_batch(task, args.seed + 999, 0, 10 ** 6,
                               batch_size * args.workers, cfg)
    loss, _ = jax.jit(model.loss)(final, eval_batch)
    if logger is not None:
        logger.close()
        print(f"round metrics -> {args.log_every_round}")
    print(f"eval loss {float(loss):.4f}  wall {time.time() - t0:.1f}s")
    if args.ckpt:
        save_pytree(args.ckpt, final, extra={"steps": args.steps})
        print(f"checkpoint -> {args.ckpt}")
    return TrainRun(eval_loss=float(loss), state=state,
                    round_s=list(sup.round_wall_s) if mspec.communicates
                    else [])


if __name__ == "__main__":
    main()

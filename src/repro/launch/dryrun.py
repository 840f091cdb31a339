import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# The two lines above MUST run before any jax import (device count locks at
# first backend init). Everything else follows.

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on the production mesh, print memory/cost analysis, parse the
collective schedule, and emit a JSON record per combo for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k \
      --mesh single --out results/dryrun
  python -m repro.launch.dryrun --all --mesh both
Plans:
  baseline  worker=data axis (M=16/32), TP=16  (the paper-faithful mapping)
  hier      hierarchical DPPF: M=4 workers x fsdp=4 x TP=16 (memory hillclimb)
  seqshard  baseline + sequence-sharded activations (hillclimb)

The hand-picked hillclimb plan SWEEPS (the committed ``opt``/``seqshard``/
``hier_opt`` record files) are superseded by ``launch/train.py
--autotune`` (DESIGN.md §Autotune), which probe-searches the
batch/tau/overlap_chunks operating point on real rounds and commits a
replayable TunePlan instead; the plan names above remain runnable for
one-off roofline comparisons.
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, DPPFConfig, INPUT_SHAPES, MeshPlan
from repro.launch import mesh as mesh_lib
from repro.launch import roofline as rf
from repro.launch import specs as specs_lib
from repro.models import build_model
from repro.optim import make_optimizer
from repro.serving import make_serve_step
from repro.train import (RoundClock, init_train_state, make_round_step,
                         make_ddp_step)
from repro.train.trainer import TrainState

# the LR/step budget every train-mode dry-run compiles against (and the
# clock the report's round-plan table renders)
TRAIN_LR, TRAIN_STEPS = 0.1, 1000
# the chip whose published peaks price the dry-run's roofline terms
MODELLED_KIND = "TPU v5 lite"


def _sds(tree_specs, tree_shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree_specs, tree_shardings)


def _plan_for(name: str, multi_pod: bool) -> MeshPlan:
    worker = ("pod", "data") if multi_pod else ("data",)
    if name in ("baseline", "opt"):
        return MeshPlan(worker_axes=worker)
    if name in ("hier", "hier_opt"):
        # M=4(8) workers, fsdp within worker; mesh axes renamed by
        # make_hierarchical_mesh to (data, fsdp, model)
        return MeshPlan(worker_axes=("data",), fsdp_axes=("fsdp",))
    if name == "seqshard":
        return MeshPlan(worker_axes=worker, seq_shard_acts=True)
    raise ValueError(name)


def _cfg_for(arch: str, plan_name: str, train: bool):
    """'opt' = beyond-paper optimized model config (§Perf): chunked mLSTM +
    bf16 MoE combine (+ bf16 momentum, applied in build_train)."""
    cfg = ARCHS[arch]
    if train:
        cfg = dataclasses.replace(cfg, remat=True)
    if plan_name in ("opt", "hier_opt"):
        cfg = dataclasses.replace(cfg, xlstm_chunk=256,
                                  moe_combine_dtype="bfloat16")
    if plan_name == "seqshard":
        cfg = dataclasses.replace(cfg, seq_shard_acts=True)
    return cfg


def _mesh_for(plan_name: str, multi_pod: bool):
    if plan_name in ("hier", "hier_opt"):
        return mesh_lib.make_hierarchical_mesh(8 if multi_pod else 4, 4, 16,
                                               multi_pod=multi_pod)
    return mesh_lib.make_production_mesh(multi_pod=multi_pod)


def _n_workers(mesh, plan):
    return int(jnp.prod(jnp.asarray([mesh.shape[a] for a in plan.worker_axes])))


# ---------------------------------------------------------------------------
# Builders per workload kind
# ---------------------------------------------------------------------------

def build_train(arch, shape, mesh, plan, *, ddp=False, tau=4,
                plan_name="baseline", overlap="none", staleness=1):
    cfg = _cfg_for(arch, plan_name, train=True)
    model = build_model(cfg)
    # the overlapped round needs the flat engine (the stale snapshot is a
    # flat (R, n) buffer — or a (k, R, n) ring under staleness_k); exact
    # rounds keep the tree engine the committed records were built with
    dcfg = DPPFConfig(tau=tau, consensus="ddp" if ddp else "simple_avg",
                      engine="flat" if overlap != "none" else "tree",
                      overlap=overlap, staleness=staleness)
    opt = make_optimizer(
        "sgd", momentum=0.9, weight_decay=1e-3,
        state_dtype="bfloat16" if plan_name in ("opt", "hier_opt")
        else "float32")
    M = _n_workers(mesh, plan)

    if ddp:
        step = make_ddp_step(model.loss, opt, base_lr=TRAIN_LR,
                             total_steps=TRAIN_STEPS)

        def _ddp_state(k):
            p = model.init(k)
            return TrainState(params=p, opt=opt.init(p), cstate={},
                              t=jnp.zeros((), jnp.int32))

        state_specs = jax.eval_shape(_ddp_state, jax.random.PRNGKey(0))
        p_sh = mesh_lib.param_shardings(mesh, state_specs.params, plan,
                                        stacked=False)
        st_sh = dataclasses.replace(
            state_specs,
            params=p_sh, opt={"mu": p_sh},
            cstate={}, t=NamedSharding(mesh, P()))
        batch_specs = specs_lib.input_specs(cfg, shape, plan, "ddp", M, tau)
        b_sh = mesh_lib.batch_shardings(mesh, batch_specs, plan,
                                        round_dims=False)
    else:
        step = make_round_step(model.loss, opt, dcfg, base_lr=TRAIN_LR,
                               total_steps=TRAIN_STEPS)
        state_specs = jax.eval_shape(
            lambda k: init_train_state(model.init, opt, dcfg, M, k),
            jax.random.PRNGKey(0))
        if state_specs.engine is not None:
            # flat engine (overlap runs): the persistent (R, n) view under
            # the flat-view storage rule
            p_sh = mesh_lib.flat_view_sharding(
                mesh, state_specs.params.shape, plan)
        else:
            p_sh = mesh_lib.param_shardings(mesh, state_specs.params, plan,
                                            stacked=True)
        snap_sh = None
        if state_specs.snap is not None:
            # overlap snapshot: a second (R, n) flat buffer — or the
            # (k, R, n) staleness ring — placed under the flat-view
            # storage rule (flat_view_sharding is ring-aware); the
            # per-round scalars replicated
            snap_sh = {k: NamedSharding(mesh, P())
                       for k in state_specs.snap if k != "x"}
            snap_sh["x"] = mesh_lib.flat_view_sharding(
                mesh, state_specs.snap["x"].shape, plan)
        st_sh = dataclasses.replace(
            state_specs,
            params=p_sh, opt={"mu": p_sh},
            cstate={}, t=NamedSharding(mesh, P()), snap=snap_sh,
            round=NamedSharding(mesh, P()))   # clock position: replicated
        batch_specs = specs_lib.input_specs(cfg, shape, plan, "train", M, tau)
        b_sh = mesh_lib.batch_shardings(mesh, batch_specs, plan,
                                        round_dims=True)

    args = (_sds(state_specs, st_sh), _sds(batch_specs, b_sh))
    return jax.jit(step), args, cfg


def build_prefill(arch, shape, mesh, plan, plan_name="baseline"):
    cfg = _cfg_for(arch, plan_name, train=False)
    model = build_model(cfg)
    params_specs = specs_lib.param_specs(cfg)
    p_sh = mesh_lib.param_shardings(mesh, params_specs, plan, stacked=False)
    batch_specs = specs_lib.prefill_batch_specs(cfg, shape)
    data_ok = shape.global_batch % mesh.shape[plan.worker_axes[0]] == 0
    b_sh = mesh_lib.serve_shardings(mesh, batch_specs, plan,
                                    batch=shape.global_batch, data_ok=data_ok)
    buf = specs_lib.buf_len_for(cfg, shape)

    def prefill(params, batch):
        return model.prefill(params, batch, buf_len=buf)

    args = (_sds(params_specs, p_sh), _sds(batch_specs, b_sh))
    return jax.jit(prefill), args, cfg


def build_decode(arch, shape, mesh, plan, plan_name="baseline"):
    cfg = _cfg_for(arch, plan_name, train=False)
    model = build_model(cfg)
    window = specs_lib.serve_window_for(cfg, shape)
    serve_step = make_serve_step(model, window=window)
    params_specs = specs_lib.param_specs(cfg)
    p_sh = mesh_lib.param_shardings(mesh, params_specs, plan, stacked=False)
    token_s, index_s, state_specs = specs_lib.decode_step_specs(cfg, shape)
    data_dim = mesh.shape[plan.worker_axes[0]]
    data_ok = shape.global_batch % data_dim == 0 and shape.global_batch >= data_dim
    st_sh = mesh_lib.serve_shardings(mesh, state_specs, plan,
                                     batch=shape.global_batch, data_ok=data_ok)
    tok_sh = NamedSharding(mesh, P(plan.worker_axes[0] if data_ok else None,
                                   None))
    args = (_sds(params_specs, p_sh), _sds(state_specs, st_sh),
            jax.ShapeDtypeStruct(token_s.shape, token_s.dtype, sharding=tok_sh),
            jax.ShapeDtypeStruct(index_s.shape, index_s.dtype,
                                 sharding=NamedSharding(mesh, P())))
    return jax.jit(serve_step), args, cfg


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def run_one(arch, shape_name, mesh_kind, *, mode=None, plan_name="baseline",
            tau=4, out_dir="results/dryrun", overlap="none", staleness=1):
    shape = INPUT_SHAPES[shape_name]
    multi_pod = mesh_kind == "multi"
    mesh = _mesh_for(plan_name, multi_pod)
    plan = _plan_for(plan_name, multi_pod)
    mode = mode or ("train" if shape.kind == "train" else shape.kind)
    if overlap != "none" and mode not in ("train",):
        raise ValueError("--overlap applies to train-mode dry-runs only")

    t0 = time.time()
    if mode in ("train", "ddp"):
        fn, args, cfg = build_train(arch, shape, mesh, plan,
                                    ddp=(mode == "ddp"), tau=tau,
                                    plan_name=plan_name, overlap=overlap,
                                    staleness=staleness)
    elif mode == "prefill":
        fn, args, cfg = build_prefill(arch, shape, mesh, plan, plan_name)
    else:
        fn, args, cfg = build_decode(arch, shape, mesh, plan, plan_name)

    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception as e:  # CPU backend may not support it
        mem["error"] = str(e)

    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        for k in ("flops", "bytes accessed", "transcendentals"):
            if k in ca:
                cost[k.replace(" ", "_")] = float(ca[k])
    except Exception as e:
        cost["error"] = str(e)

    hlo = compiled.as_text()
    n_model = mesh.shape.get("model", 1)
    ana = rf.analyze_hlo(hlo, n_model=n_model)  # trip-count-corrected
    coll = ana["collectives"]
    scale = 1.0 / tau if mode == "train" else 1.0
    terms = rf.roofline(ana["flops"], ana["bytes"], coll,
                        device_kind=MODELLED_KIND, seconds_scale=scale)
    mf = rf.model_flops(cfg, shape, mode=mode)
    chips = int(mesh.devices.size)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "mode": mode,
        "plan": plan_name, "chips": chips, "tau": tau, "overlap": overlap,
        "n_workers": _n_workers(mesh, plan) if mode in ("train", "ddp") else None,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem, "cost_raw_xla": cost,
        "hlo_flops_per_dev": ana["flops"], "hlo_bytes_per_dev": ana["bytes"],
        "collectives": coll,
        "collective_axis_bytes": ana["collective_axis_bytes"],
        "roofline": {k: v for k, v in terms.items()},
        "model_flops_total": mf,
        "model_flops_per_chip_step": mf / chips,
        "useful_flop_ratio": (mf / chips) / max(ana["flops"] * scale, 1.0),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    }
    if mode == "train":
        # modeled exact/staleness1/doublebuf/staleness-k round time (incl.
        # the ppermute-ring term) vs the comm/compute crossover
        # (launch.roofline.overlap_model) — rendered by roofline_report.py
        # and the EXPERIMENTS.md §Overlap-roofline table
        rec["overlap_model"] = rf.overlap_model(
            terms, ana["collective_axis_bytes"], device_kind=MODELLED_KIND,
            R=_n_workers(mesh, plan), seconds_scale=scale)
        rec["staleness"] = staleness if overlap == "staleness_k" else None
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}_{shape_name}_{mesh_kind}_{mode}_{plan_name}"
    if overlap == "staleness_k":
        tag += f"_{overlap}{staleness}"
    elif overlap != "none":
        tag += f"_{overlap}"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[OK] {tag}: compile={t_compile:.1f}s "
          f"flops/dev={cost.get('flops', 0):.3e} "
          f"coll={sum(v['bytes'] for v in coll.values()):.3e}B "
          f"bottleneck={terms['bottleneck']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--mode", default=None,
                    choices=[None, "train", "ddp", "prefill", "decode"])
    ap.add_argument("--plan", default="baseline",
                    choices=["baseline", "hier", "seqshard", "opt", "hier_opt"])
    ap.add_argument("--tau", type=int, default=4)
    ap.add_argument("--overlap", default="none",
                    choices=["none", "staleness1", "doublebuf",
                             "staleness_k"],
                    help="compile the overlapped round (flat engine) "
                         "instead of the exact tree round — train-mode "
                         "combos only; every train record additionally "
                         "carries the modeled exact/staleness1/doublebuf/"
                         "staleness-k + ring comparison (overlap_model)")
    ap.add_argument("--staleness", type=int, default=1,
                    help="staleness_k: snapshot-ring depth k")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    # round-plan report: the clock every train-mode combo compiles against
    # (DESIGN.md §Round-clock) — tau from the CLI, the dry-run LR budget
    print(RoundClock(total_steps=TRAIN_STEPS, tau=args.tau,
                     base_lr=TRAIN_LR, overlap=args.overlap,
                     staleness=args.staleness).plan_table())
    print()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = sorted(ARCHS) if args.all or not args.arch else [args.arch]
    shapes = (list(INPUT_SHAPES) if args.all or not args.shape
              else [args.shape])
    if args.overlap == "staleness_k":
        suffix = f"_{args.overlap}{args.staleness}"
    elif args.overlap != "none":
        suffix = f"_{args.overlap}"
    else:
        suffix = ""

    failures = []
    for mk in meshes:
        for a in archs:
            for s in shapes:
                tag = f"{a}_{s}_{mk}"
                mode = (args.mode or
                        ("train" if INPUT_SHAPES[s].kind == "train"
                         else INPUT_SHAPES[s].kind))
                if args.overlap != "none" and mode != "train":
                    print(f"[skip] {tag} (--overlap is train-only)")
                    continue
                path = os.path.join(
                    args.out, f"{a}_{s}_{mk}_{mode}_{args.plan}"
                    f"{suffix}.json")
                if os.path.exists(path):
                    print(f"[skip] {tag} (cached)")
                    continue
                try:
                    run_one(a, s, mk, mode=args.mode, plan_name=args.plan,
                            tau=args.tau, out_dir=args.out,
                            overlap=args.overlap, staleness=args.staleness)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"[FAIL] {tag}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"{len(failures)} failures:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("all dry-runs passed")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

  python3 bench/run.py --workload yi6b_tau4_1chip --seed 7 --seconds 30 \
      --trace 0

The cell, its configuration (``bench/configs/<config>.json``), its traffic
(``bench/traffic/<traffic>.json``), its correctness limits
(``bench/limits/<cell>.json``) and its metrics (``bench/metrics/<name>.py``)
are found by the names in ``BENCHMARK.json``; nothing here is particular to
one of them.

What runs is the program's own DPPF round loop, ``repro.train.Supervisor``
over the jitted, donating round step of ``repro.train`` (stacked workers on
one chip, or one worker a chip under ``make_sharded_round_step``), fed
token ids drawn with numpy from ``(seed, round)``. Set-up makes the
weights from the seed, builds the state and the step, sets the state's
clock to the traffic's ``check_start`` round of its plan (where the
increasing push strength ``lam_t`` is material), and runs the traffic's
check rounds from there, which compile the step; after them it reads what
the correctness comparison needs, then runs one more round. The window
then runs the plan's next rounds, as many as fill ``--seconds`` at that
round's pace. With ``--trace 1`` a few more rounds run under the profiler
after the window, and the per-layer metrics are read from that trace. Last, the program's
state is freed and ``bench.reference`` follows the check rounds plainly;
the gaps between the two decide ``correct``.

The last line of standard output is one JSON object (correct, attempted,
failed, metrics, device, breakdown with a trace, check); the compared
numbers and their limits also end standard error. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = BENCH / "out"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict              # the configuration file
    traffic: dict          # the traffic file
    limits: dict           # compared number -> limit
    metrics: dict          # "end_to_end"/"per_layer" -> [metric entries]


def load_cell(name, spec_path=ROOT / "BENCHMARK.json"):
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def applies(metric):
        return name in metric.get("workloads", [name])

    return Cell(
        name=name, chips=int(cell["chips"]),
        cfg=json.loads((ROOT / config["file"]).read_text()),
        traffic=json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()),
        metrics={kind: [m for m in spec[kind] if applies(m)]
                 for kind in ("end_to_end", "per_layer")})


@dataclasses.dataclass
class RunRecord:
    """What the metric readers see (``bench/metrics``): with the cell's
    configuration and traffic files, a reader can count a kernel's work
    from shapes through the architecture module
    (``bench.reference.architecture(ctx.cfg)``)."""
    cfg: dict
    traffic: dict
    chips: int
    tau: int
    setup_s: float
    window_s: float
    window_tokens: int
    round_s: list
    flops_per_token: float
    consensus_bytes_per_round: float
    peak: dict
    memory_peak_bytes: int = 0
    trace: object = None
    traced_rounds: int = 0


def program_config(cfg):
    """The program's ModelConfig for a configuration file: the named
    published architecture with the fields the file's architecture module
    fixes (``program_fields``). Where the file's ``program`` block names a
    cut beyond ``arch`` (``layers``, ``vocab``, ...), the result must be the
    program's own ``cut`` of the published architecture by those keys."""
    from bench import reference
    from repro.configs import cut, get_arch
    prog = cfg["program"]
    fields = reference.architecture(cfg).program_fields(cfg)
    mcfg = dataclasses.replace(get_arch(prog["arch"]), **fields)
    cut_by = {k: v for k, v in prog.items() if k != "arch"}
    if cut_by:
        want = cut(get_arch(prog["arch"]), **cut_by)
        if dataclasses.replace(mcfg, name=want.name) != want:
            raise ValueError(f"{cfg['name']}: the file's sizes differ from "
                             f"the program's cut of {prog['arch']}")
    return mcfg


def enable_cache():
    """JAX's persistent compile cache at one fixed path inside the
    checkout, whatever the environment names: the program's own helper
    (``repro.launch.compile_cache``) takes the directory from
    ``JAX_COMPILATION_CACHE_DIR``, so it agrees. Small programs are cached
    too, and nothing is evicted: a directory that an environment shares
    between runs with eviction on failed every write on the chip's host
    (PERF.md), and then every run compiled again."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def token_batch(seed, spec_index, tau, workers, batch, seq, vocab):
    """The round's token ids, uniform over the vocabulary, labels shifted
    by one: (tau, M, B, S) int32 each, from (seed, round) alone."""
    import numpy as np
    rng = np.random.default_rng([int(seed), int(spec_index)])
    ids = rng.integers(0, vocab, size=(tau, workers, batch, seq + 1),
                       dtype=np.int32)
    return ids[..., :-1], ids[..., 1:]


def execute(cell, seed, seconds, trace, devices, *, fault=None, log=None):
    """Set up, warm up, run the window (and the traced rounds), check.

    ``fault`` breaks the timed path for the harness's own tests:
    ``"unchanged"`` (the step hands back its input state), ``"half_batch"``
    (half of each sequence's labels are masked, the mean taken over the
    rest), ``"no_exchange"`` (the consensus moves nothing), ``"no_push"``
    (the pull alone, ``lam = 0``).
    Returns ``(record, check, correct, meta)``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import flops, reference
    from bench import trace as trace_mod
    from repro.configs import DPPFConfig
    from repro.models import build_model
    from repro.optim import make_optimizer
    from repro.train import (RoundClock, Supervisor, init_train_state,
                             make_round_step, make_sharded_round_step,
                             shard_train_state)

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    cfg, tr, traffic = cell.cfg, cell.cfg["training"], cell.traffic
    M, tau = tr["workers"], traffic["tau"]
    B, S = traffic["batch_per_worker"], traffic["seq_len"]
    V = cfg["vocab_size"]
    K, K0 = traffic["check_rounds"], traffic["check_start"]
    mcfg = program_config(cfg)
    model = build_model(mcfg)
    no_exchange = fault == "no_exchange"
    dcfg = DPPFConfig(alpha=0.0 if no_exchange else tr["alpha"],
                      lam=0.0 if fault in ("no_exchange", "no_push")
                      else tr["lam"], tau=tau,
                      lam_schedule=tr["lam_schedule"],
                      consensus=tr["consensus"], engine=tr["engine"])
    opt = make_optimizer(tr["optimizer"], momentum=tr["momentum"],
                         weight_decay=tr["weight_decay"])
    clock = RoundClock.from_config(dcfg, base_lr=tr["lr"],
                                   total_steps=traffic["plan_rounds"] * tau)

    # the weights: one jitted call from the seed, in bf16, of the tree the
    # program's own model takes (the same leaves in the same order, of the
    # same shapes and types); a host copy of the flat initial view is kept
    # for the change after the check rounds
    arch = reference.architecture(cfg)
    make_w = reference.make_weights(cfg)
    own = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ref_tree = jax.eval_shape(make_w, reference.seed_key(seed))
    if jax.tree.structure(own) != jax.tree.structure(ref_tree) or [
            (a.shape, a.dtype) for a in jax.tree.leaves(own)] != [
            (a.shape, a.dtype) for a in jax.tree.leaves(ref_tree)]:
        raise ValueError("the program's model does not take the "
                         "reference's leaves in the reference's order")
    w0 = make_w(reference.seed_key(seed))
    x0_host = np.concatenate([np.asarray(a).reshape(-1)
                              for a in jax.tree.leaves(w0)])
    state = init_train_state(lambda _key: w0, opt, dcfg, M,
                             jax.random.PRNGKey(0))
    del w0
    # the clock at the plan's round K0, as a run resumed there holds it
    state = dataclasses.replace(state, t=jnp.asarray(K0 * tau, state.t.dtype),
                                round=jnp.asarray(K0, state.round.dtype))
    lay = state.engine.layout
    shapes = [tuple(s) for s in jax.tree.leaves(
        arch.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))]
    if [tuple(s) for s in lay.shapes] != shapes:
        raise ValueError("the program's flat view does not hold the "
                         "reference's leaves in the reference's order")
    if state.engine.use_kernel and lay.width != flops.view_width(cfg):
        raise ValueError(f"view width {lay.width} != {flops.view_width(cfg)}")

    donate = () if fault == "unchanged" else (0,)
    if cell.chips > 1:
        from repro.launch.mesh import make_flat_engine_mesh
        mesh, plan = make_flat_engine_mesh(M)
        if mesh.devices.size != cell.chips:
            raise ValueError(f"mesh {dict(mesh.shape)} is not "
                             f"{cell.chips} chips")
        state = shard_train_state(state, mesh, plan, dcfg=dcfg)
        step = jax.jit(make_sharded_round_step(
            model.loss, opt, dcfg, mesh=mesh, plan=plan, clock=clock),
            donate_argnums=donate)
    else:
        step = jax.jit(make_round_step(model.loss, opt, dcfg, clock=clock),
                       donate_argnums=donate)
    if fault == "unchanged":
        real_step = step

        def step(st, b):
            return st, real_step(st, b)[1]

    def batches(r):
        return token_batch(seed, r, tau, M, B, S, V)

    def batch_fn(spec, bs):
        with jax.profiler.TraceAnnotation("bench.batch"):
            tok, lab = batches(spec.index)
            if fault == "half_batch":
                lab = lab.copy()
                lab[..., S // 2:] = -1
            return {"tokens": tok, "labels": lab}

    def step_fn(st, b):
        with jax.profiler.TraceAnnotation("bench.step"):
            return step(st, b)

    def drive(st, start, stop, on_round=None):
        if stop > clock.total_rounds:
            raise ValueError(f"round {stop} is past the plan's "
                             f"{clock.total_rounds} rounds")
        sup = Supervisor(types.SimpleNamespace(rounds=clock.rounds[:stop]),
                         workers=M, batch_size=B, on_round=on_round)
        st = sup.run(st, step_fn, batch_fn, start_round=start)
        return st, list(sup.round_wall_s)

    # -- check rounds (they compile the step and warm it up) ---------------
    views = [(off, math.prod(shape)) for off, shape in
             zip(lay.offsets, lay.shapes)]

    @jax.jit
    def leaf_norms(view):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            view[:M, o:o + n].astype(jnp.float32)), axis=1))
            for o, n in views], axis=1)

    @jax.jit
    def change_norms(view, x0):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            view[:M, o:o + n] - x0[None, o:o + n].astype(jnp.float32)),
            axis=1)) for o, n in views], axis=1)

    seen = []

    def on_round(spec, m):
        seen.append((float(m["train_loss"]), float(m["pre_dist"])))

    devices = list(devices)

    def peak_bytes():
        return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in devices]

    # the peak of set-up (weights, state) on each chip, before any round
    setup_peaks = peak_bytes()
    state, _ = drive(state, K0, K0 + 1, on_round)
    t = time.perf_counter()
    mu_norms = np.asarray(leaf_norms(state.opt["mu"]), np.float64)
    check_s = time.perf_counter() - t
    state, _ = drive(state, K0 + 1, K0 + K, on_round)
    t = time.perf_counter()
    chg = np.asarray(change_norms(state.params, x0_host), np.float64)
    check_s += time.perf_counter() - t
    del x0_host
    prog = {"losses": [a for a, _ in seen], "dists": [b for _, b in seen],
            "mu_norms": mu_norms, "change_norms": chg}

    # one more round after the readings, so that the window's first round
    # follows a round step as all its others do; its time sets the pace
    state, warm_s = drive(state, K0 + K, K0 + K + 1)
    start = K0 + K + 1

    # -- the window ----------------------------------------------------------
    n_window = max(1, math.ceil(seconds / warm_s[0]))
    setup_s = time.perf_counter() - T_START - check_s
    compiles = []

    def on_compile(event, duration, **_):
        # a compile, or a read of one from the persistent cache
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    t = time.perf_counter()
    state, round_s = drive(state, start, start + n_window)
    window_s = time.perf_counter() - t
    jax.monitoring.unregister_event_duration_listener(on_compile)
    window_compiles = len(compiles)
    log(f"window: {n_window} rounds in {window_s:.3f} s "
        f"(pace {warm_s[0]:.4f} s from the warm-up round), "
        f"{window_compiles} compiles; round_s "
        + " ".join(f"{x:.4f}" for x in round_s))

    peaks = json.loads((BENCH / "peaks.json").read_text())
    kind = devices[0].device_kind
    rec = RunRecord(
        cfg=cfg, traffic=traffic, chips=cell.chips, tau=tau,
        setup_s=setup_s, window_s=window_s,
        window_tokens=n_window * tau * M * B * S, round_s=round_s,
        flops_per_token=flops.flops_per_token(cfg, S),
        consensus_bytes_per_round=flops.consensus_bytes(lay.R, lay.width),
        peak=peaks.get(kind, {}))

    n_trace = traffic["trace_rounds"] if trace else 0
    if trace:
        trace_dir = OUT_DIR / f"trace-{cell.name}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            state, _ = drive(state, start + n_window,
                             start + n_window + n_trace)
        jax.profiler.stop_trace()
        rec.trace = trace_mod.load(trace_mod.find_xplane(str(trace_dir)))
        rec.traced_rounds = n_trace

    peaks_in_use = peak_bytes()
    rec.memory_peak_bytes = max(peaks_in_use)
    log("peak_bytes_in_use by chip " + " ".join(map(str, peaks_in_use))
        + "; after set-up " + " ".join(map(str, setup_peaks)))

    # -- the check: free the program's state, then the plain reference -----
    for leaf in jax.tree.leaves(state):
        leaf.delete()
    del state, step
    t = time.perf_counter()
    with jax.default_device(devices[0]):
        ref = reference.ReferenceRun(cfg, tr, traffic).run(
            make_w(reference.seed_key(seed)), batches, K, start=K0)
    log(f"reference: {K} rounds in {time.perf_counter() - t:.1f} s")
    check = reference.compare(prog, ref)
    correct = all(check[k] <= cell.limits[k] for k in cell.limits)
    meta = {"attempted": n_window + n_trace, "failed": 0,
            "window_compiles": window_compiles,
            "device": {"platform": devices[0].platform, "kind": kind,
                       "count": len(devices),
                       "memory_peak_bytes": rec.memory_peak_bytes}}
    return rec, check, correct, meta


def result_line(cell, rec, check, correct, meta, trace):
    from bench.metrics import reader
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics[kind]:
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": meta["attempted"],
           "failed": meta["failed"], "metrics": metrics,
           "device": dict(meta["device"]),
           "window_compiles": meta["window_compiles"]}
    if trace and rec.trace is not None:
        tr = rec.trace
        busy = [tr.busy_ns(c) for c in tr.chips]
        out["device"]["busy_s"] = sum(busy) / max(len(busy), 1) / 1e9
        out["device"]["window_s"] = tr.window_ns / 1e9
        gaps = sorted(((name, (b - a) / 1e9) for c in tr.chips
                       for a, b, name in tr.idle_gaps(c)),
                      key=lambda g: -g[1])
        out["breakdown"] = {"device_ops": [list(x) for x in
                                           tr.op_totals()[:10]],
                            "idle_gaps": [list(g) for g in gaps[:10]]}
    out["check"] = {k: {"value": v, "limit": cell.limits[k]}
                    for k, v in check.items()}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    # the TPU runtime writes no logs to a fixed path outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", str(OUT_DIR / "tpu_logs"))
    enable_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if devices[0].device_kind not in peaks:
        print(f"no peaks for {devices[0].device_kind!r} in bench/peaks.json",
              file=sys.stderr)
        return 3
    rec, check, correct, meta = execute(
        cell, args.seed, args.seconds, bool(args.trace),
        devices[:cell.chips])
    line = result_line(cell, rec, check, correct, meta, bool(args.trace))
    for k, v in line["check"].items():
        print(f"check {k} {v['value']:.6e} limit {v['limit']:.6e}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

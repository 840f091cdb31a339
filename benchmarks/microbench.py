"""Micro-benchmarks: us_per_call for the hot paths (flat ConsensusEngine vs
tree-path consensus, fused pull-push vs naive, DPPF round vs DDP steps at
equal token budget, QSR RoundClock vs fixed tau) on this host CPU.
Wall-times are host-relative — the TPU story is §Roofline — but the
RELATIVE comparison (flat-engine speedup, fused consensus cost, round
amortization, all-reduces saved) holds.

Besides the CSV rows, ``run`` writes ``BENCH_roundclock.json`` at the repo
root — rounds, all-reduce counts, and the engine-vs-tree row — so the perf
trajectory is machine-readable across PRs.

``--smoke`` shrinks every size so the whole file runs in seconds (CI).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import csv, default_data, mlp_init, mlp_loss
from repro.configs import DPPFConfig
from repro.core import consensus
from repro.core import pullpush as pp
from repro.core.engine import ConsensusEngine
from repro.optim import make_optimizer
from repro.train import (
    RoundClock, init_train_state, make_round_step, make_ddp_step,
    make_sharded_round_step, shard_train_state,
)
from repro.train.trainer import TrainState

# the chip whose published peaks price the modelled round times
# (launch.roofline.PEAKS); the measured columns are this host's
MODELLED_KIND = "TPU v5 lite"


def _time(fn, *args, n=20):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def _time_donated(fn, arg, n=20):
    """Time a donating jit'd fn by threading its output back in (this is
    exactly how the trainer reuses the flat view between rounds). Warms
    TWICE: the first output's shardings are the steady-state cache key
    (e.g. the doublebuf snapshot comes back row-sharded), so the second
    call is where any residual recompile lands."""
    out = fn(arg)
    jax.block_until_ready(out)
    out = fn(out)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(out)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def _transformer_like_stacked(key, M, target_params):
    """Worker-stacked pytree with a realistic leaf census — hundreds of
    mixed matrix/vector leaves, like a real LM checkpoint (a 1M-param model
    has ~750 leaves; a 6B one has ~400 larger ones). Per-leaf dispatch is
    exactly what the tree path pays for and the flat engine amortizes."""
    block = [(64, 64), (64,), (64, 16), (16,)]
    per_block = sum(s[0] * (s[1] if len(s) > 1 else 1) for s in block)
    shapes = block * max(target_params // per_block, 1)
    ks = jax.random.split(key, len(shapes))
    return {f"p{i}": jax.random.normal(ks[i], (M,) + s)
            for i, s in enumerate(shapes)}


def bench_engine_vs_tree(*, smoke=False):
    """THE acceptance row: flat ConsensusEngine vs the stacked-tree path on
    the same 8-worker x ~1M-param consensus round (Eq. 5)."""
    M = 8
    target = 20_000 if smoke else 1_000_000
    n_it = 3 if smoke else 20
    stacked = _transformer_like_stacked(jax.random.PRNGKey(0), M, target)
    dcfg = DPPFConfig(alpha=0.1, lam=0.5)
    lam_t = 0.3

    tree_fn = jax.jit(
        lambda s: consensus.apply_round(s, dcfg, lam_t, {})[0])
    us_tree = _time(tree_fn, stacked, n=n_it)

    engine = ConsensusEngine.from_stacked(stacked)
    flat = engine.flatten(stacked)          # ONCE per run — not timed
    flat_fn = jax.jit(
        lambda f: consensus.apply_round(f, dcfg, lam_t, {}, engine=engine)[0],
        donate_argnums=0)
    us_flat = _time_donated(flat_fn, flat, n=n_it)

    n = engine.layout.n
    csv("microbench", op=f"consensus_tree_{M}x{n}",
        us_per_call=round(us_tree, 1))
    csv("microbench", op=f"consensus_engine_{M}x{n}",
        us_per_call=round(us_flat, 1))
    csv("microbench", op="engine_vs_tree",
        speedup=round(us_tree / us_flat, 2),
        note="flat ConsensusEngine (persistent donated view) vs "
             "stacked-tree apply_round")
    return {"workers": M, "params_per_worker": n,
            "us_tree": round(us_tree, 1), "us_engine": round(us_flat, 1),
            "speedup": round(us_tree / us_flat, 2)}


def bench_pullpush(*, smoke=False):
    # fused pull-push vs naive multi-pass
    key = jax.random.PRNGKey(0)
    n = 20_000 if smoke else 1_000_000
    n_it = 3 if smoke else 20
    stacked = {"w": jax.random.normal(key, (8, n))}
    fused = jax.jit(lambda s: pp.pullpush(s, 0.1, 0.5)[0])

    def naive(s):
        a = jax.tree.map(lambda x: jnp.mean(x, 0), s)
        d = jax.tree.map(lambda x, c: x - c[None], s, a)
        r = jnp.sqrt(sum(jnp.sum(jnp.square(l), axis=tuple(range(1, l.ndim)))
                         for l in jax.tree.leaves(d)))
        coef = 0.1 - 0.5 / jnp.maximum(r, 1e-12)
        return jax.tree.map(lambda x, c: x + (c[None] - x) * coef.reshape(
            (-1,) + (1,) * (x.ndim - 1)), s, a)

    csv("microbench", op=f"pullpush_fused_8x{n}",
        us_per_call=round(_time(fused, stacked, n=n_it), 1))
    csv("microbench", op=f"pullpush_naive_8x{n}",
        us_per_call=round(_time(jax.jit(naive), stacked, n=n_it), 1))


def bench_round_vs_ddp(*, smoke=False):
    # DPPF round vs tau DDP steps at the same token budget
    key = jax.random.PRNGKey(0)
    data = default_data()
    opt = make_optimizer("sgd")
    M, bs, tau = 4, 16 if smoke else 64, 4
    n_it = 3 if smoke else 20
    dcfg = DPPFConfig(alpha=0.1, lam=0.5, tau=tau)
    st = init_train_state(lambda k: mlp_init(k, data["dim"],
                                             data["n_classes"]),
                          opt, dcfg, M, key)
    round_fn = jax.jit(make_round_step(mlp_loss, opt, dcfg, base_lr=0.05,
                                       total_steps=100))
    batch = {"x": jnp.zeros((tau, M, bs, data["dim"])),
             "y": jnp.zeros((tau, M, bs), jnp.int32)}
    us_round = _time(lambda s, b: round_fn(s, b)[0], st, batch, n=n_it)

    p0 = mlp_init(key, data["dim"], data["n_classes"])
    dstate = TrainState(params=p0, opt=opt.init(p0), cstate={},
                        t=jnp.zeros((), jnp.int32))
    ddp_fn = jax.jit(make_ddp_step(mlp_loss, opt, base_lr=0.05,
                                   total_steps=100))
    db = {"x": jnp.zeros((M, bs, data["dim"])),
          "y": jnp.zeros((M, bs), jnp.int32)}
    us_ddp = _time(lambda s, b: ddp_fn(s, b)[0], dstate, db, n=n_it)
    csv("microbench", op=f"dppf_round_tau{tau}", us_per_call=round(us_round, 1),
        derived=f"per_local_step={round(us_round / tau, 1)}")
    csv("microbench", op="ddp_step", us_per_call=round(us_ddp, 1),
        derived=f"tau_steps={round(us_ddp * tau, 1)}")


def bench_sharded_round(*, smoke=False):
    """Sharded vs single-shard flat-engine round on the host devices.
    Needs a multi-device CPU mesh (run with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``); emits a
    skipped row on one device so the CSV schema is stable."""
    ndev = len(jax.devices())
    if ndev < 2:
        csv("microbench", op="sharded_round", skipped=1,
            note="single device; set "
                 "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return
    from repro.launch.mesh import make_flat_engine_mesh
    data = default_data()
    opt = make_optimizer("sgd")
    M, bs, tau = 8, 16 if smoke else 64, 4
    n_it = 3 if smoke else 20
    mesh, plan = make_flat_engine_mesh(M)
    batch = {"x": jnp.zeros((tau, M, bs, data["dim"])),
             "y": jnp.zeros((tau, M, bs), jnp.int32)}
    init = lambda k: mlp_init(k, data["dim"], data["n_classes"],
                              width=32 if smoke else 256)
    rows = {}
    for overlap in ("none", "staleness1"):
        dcfg = DPPFConfig(alpha=0.1, lam=0.5, tau=tau, engine="flat",
                          overlap=overlap)
        st = init_train_state(init, opt, dcfg, M, jax.random.PRNGKey(0))
        single = jax.jit(make_round_step(mlp_loss, opt, dcfg, base_lr=0.05,
                                         total_steps=100), donate_argnums=0)
        us_single = _time_donated(lambda s: single(s, batch)[0], st, n=n_it)
        st = shard_train_state(
            init_train_state(init, opt, dcfg, M, jax.random.PRNGKey(0)),
            mesh, plan, dcfg=dcfg)
        sharded = jax.jit(make_sharded_round_step(
            mlp_loss, opt, dcfg, mesh=mesh, plan=plan, base_lr=0.05,
            total_steps=100), donate_argnums=0)
        us_sharded = _time_donated(lambda s: sharded(s, batch)[0], st,
                                   n=n_it)
        rows[overlap] = (us_single, us_sharded)
        csv("microbench", op=f"sharded_round_overlap_{overlap}",
            us_single_device=round(us_single, 1),
            us_sharded=round(us_sharded, 1),
            mesh="x".join(str(s) for s in mesh.devices.shape))
    us_exact, us_stale = rows["none"][1], rows["staleness1"][1]
    csv("microbench", op="sharded_round",
        overlap_speedup=round(us_exact / us_stale, 2),
        note="shard_map round (collective Gram); staleness-1 hides the "
             "consensus behind the tau local steps")


def bench_hierarchical_round(*, smoke=False):
    """Hierarchical 2x2x2 (workers x fsdp x model) round vs the flat 8x1
    row-sharded round on the same 8 workers: the column group spans both
    fsdp and model axes, so the partial-Gram psum reduces over 4 column
    shards (DESIGN.md §Hierarchical-mesh). Needs 8 forced host devices;
    emits a skipped row otherwise so the CSV schema is stable."""
    if len(jax.devices()) < 8:
        csv("microbench", op="hierarchical_round", skipped=1,
            note="needs 8 devices; set "
                 "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return None
    from repro.launch.mesh import make_flat_engine_mesh, make_hier_engine_mesh
    data = default_data()
    opt = make_optimizer("sgd")
    M, bs, tau = 8, 16 if smoke else 64, 4
    n_it = 3 if smoke else 20
    batch = {"x": jnp.zeros((tau, M, bs, data["dim"])),
             "y": jnp.zeros((tau, M, bs), jnp.int32)}
    init = lambda k: mlp_init(k, data["dim"], data["n_classes"],
                              width=32 if smoke else 256)
    dcfg = DPPFConfig(alpha=0.1, lam=0.5, tau=tau, engine="flat")
    out = {}
    for name, (mesh, plan) in (("flat_8x1", make_flat_engine_mesh(M)),
                               ("hier_2x2x2", make_hier_engine_mesh(2, 2, 2))):
        st = shard_train_state(
            init_train_state(init, opt, dcfg, M, jax.random.PRNGKey(0)),
            mesh, plan)
        fn = jax.jit(make_sharded_round_step(
            mlp_loss, opt, dcfg, mesh=mesh, plan=plan, base_lr=0.05,
            total_steps=100), donate_argnums=0)
        us = _time_donated(lambda s: fn(s, batch)[0], st, n=n_it)
        # us_ prefix: check_bench treats these as host-relative timing
        out[f"us_{name}"] = round(us, 1)
        csv("microbench", op=f"hierarchical_round_{name}",
            us_per_call=round(us, 1),
            mesh="x".join(str(s) for s in mesh.devices.shape))
    csv("microbench", op="hierarchical_round",
        flat_vs_hier=round(out["us_flat_8x1"] / out["us_hier_2x2x2"], 2),
        note="same 8 workers; hier column-shards the (R, n) view over "
             "fsdp x model with the Gram psum spanning both axes")
    return out


def bench_overlap_round(*, smoke=False):
    """THE overlap acceptance rows: exact vs staleness1 vs doublebuf round
    throughput on the 8-device mesh (hier 2x2x2 — both the worker-row
    gather and the column-axis partial-Gram psum are real collectives).
    doublebuf dispatches the snapshot's gather/Gram chunks mid-scan and
    leaves only the mix GEMM at the boundary.

    Two kinds of rows per mode:

    * ``us_per_round`` — measured host wall time. Host-relative and
      report-only (forced host devices run collectives as shared-memory
      memcpys, so there is little latency to hide on CPU; check_bench
      treats ``us_*``/``speedup_*`` as timing fields).
    * ``modeled_round_us`` — the §Roofline hardware model (TPU v5e ICI /
      peak-flops constants, `launch/roofline.py`) applied to this exact
      config: per-round compute window + boundary-serial consensus bytes
      (exact: gather + psum; staleness1: gather only — the stale psum
      hides; doublebuf: ZERO — all snapshot comm dispatches mid-scan,
      capped by the compute window). Deterministic arithmetic, so it is
      a STRUCTURAL field: the committed ``BENCH_overlap.json`` pins the
      doublebuf >= staleness1 >= exact throughput ordering in CI
      (``modeled_order_ok``).

    Emits a skipped row on fewer than 8 devices so the CSV schema is
    stable."""
    if len(jax.devices()) < 8:
        csv("microbench", op="overlap_round", skipped=1,
            note="needs 8 devices; set "
                 "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return None
    from repro.launch import roofline as rf
    from repro.launch.mesh import make_hier_engine_mesh
    data = default_data()
    opt = make_optimizer("sgd")
    M, bs, tau = 8, 16 if smoke else 64, 8
    width = 32 if smoke else 256
    n_it = 10 if smoke else 20
    mesh, plan = make_hier_engine_mesh(2, 2, 2)
    rows_sz, cols_sz = 2, 4          # worker shards x (fsdp x model) shards
    batch = {"x": jnp.zeros((tau, M, bs, data["dim"])),
             "y": jnp.zeros((tau, M, bs), jnp.int32)}
    init = lambda k: mlp_init(k, data["dim"], data["n_classes"], width=width)
    out = {"mesh": "x".join(str(s) for s in mesh.devices.shape),
           "workers": M, "tau": tau, "modes": {}}

    def modeled_us(mode, R, n, k=2):
        # per-device round: compute window = tau local steps of the MLP
        # (fwd+bwd ~ 3x fwd flops) on m_loc workers; consensus bytes =
        # worker-row all-gather + (R, R) partial-Gram psum. The per-mode
        # formulas live in launch.roofline (probe_round_model routes
        # through overlap_model — the ONE copy, shared with the autotune
        # probes and the dry-run §Overlap-roofline table). staleness_k
        # reads the k-deep ring entry (ppermute ring wire + k compute
        # windows to hide it behind).
        dims = [data["dim"], width, width, data["n_classes"]]
        fwd = 2 * bs * sum(a * b for a, b in zip(dims, dims[1:]))
        data_bytes = R * (n // cols_sz) * 4 + R * R * 4
        return rf.probe_round_model(
            work_s_per_step=3 * fwd * (M // rows_sz)
            / rf.peaks(MODELLED_KIND)["flops"],
            tau=tau, gather_bytes=data_bytes, device_kind=MODELLED_KIND,
            R=R, mode=mode,
            staleness=k if mode == "staleness_k" else 1) * 1e6

    K_DEPTH = 2
    for mode, chunks in (("none", 1), ("staleness1", 1), ("doublebuf", 4),
                         ("staleness_k", 4)):
        dcfg = DPPFConfig(alpha=0.1, lam=0.5, tau=tau, engine="flat",
                          overlap=mode, overlap_chunks=chunks,
                          staleness=K_DEPTH if mode == "staleness_k" else 1)
        st = init_train_state(init, opt, dcfg, M, jax.random.PRNGKey(0))
        L = st.engine.layout
        st = shard_train_state(st, mesh, plan, dcfg=dcfg)
        fn = jax.jit(make_sharded_round_step(
            mlp_loss, opt, dcfg, mesh=mesh, plan=plan, base_lr=0.05,
            total_steps=100), donate_argnums=0)
        us = _time_donated(lambda s: fn(s, batch)[0], st, n=n_it)
        mus = modeled_us(mode, L.R, L.n, k=K_DEPTH)
        row = {"overlap_chunks": chunks, "us_per_round": round(us, 1),
               "modeled_round_us": round(mus, 3)}
        if mode == "staleness_k":
            row["staleness"] = K_DEPTH
        out["modes"][mode] = row
        csv("microbench", op=f"overlap_round_{mode}",
            us_per_round=round(us, 1), modeled_round_us=round(mus, 3),
            overlap_chunks=chunks, mesh=out["mesh"])
    us = {m: out["modes"][m]["us_per_round"] for m in out["modes"]}
    mus = {m: out["modes"][m]["modeled_round_us"] for m in out["modes"]}
    out["speedup_staleness1"] = round(us["none"] / us["staleness1"], 2)
    out["speedup_doublebuf"] = round(us["none"] / us["doublebuf"], 2)
    out["speedup_staleness_k"] = round(us["none"] / us["staleness_k"], 2)
    out["modeled_order_ok"] = bool(
        mus["staleness_k"] <= mus["doublebuf"]
        <= mus["staleness1"] <= mus["none"])
    csv("microbench", op="overlap_round",
        speedup_staleness1=out["speedup_staleness1"],
        speedup_doublebuf=out["speedup_doublebuf"],
        speedup_staleness_k=out["speedup_staleness_k"],
        modeled_order_ok=out["modeled_order_ok"],
        note="round throughput vs exact on the hier 2x2x2 mesh; doublebuf "
             "chunks the snapshot gather+Gram mid-scan (boundary = mix "
             "GEMM only); modeled_* pins staleness_k >= doublebuf >= "
             "staleness1 >= exact on the roofline hardware model")
    return out


def bench_ring_round(*, smoke=False):
    """Ring-vs-gather acceptance rows: the staleness-k mid-scan gather as
    a ``ppermute`` ring (R-1 hops of one worker row each,
    launch.mesh.ring_gather) against one ``all_gather`` of the same
    payload, on the flat 8x1 mesh.

    * ``us_ring`` / ``us_gather`` — measured host wall time (timing
      fields; forced host devices make collectives memcpys, so the ring's
      latency-hiding advantage does not show on CPU).
    * ``ring_bytes_per_hop`` / ``gather_bytes`` / ``ring_hops`` — the
      modeled wire schedule (deterministic arithmetic). STRUCTURAL:
      the committed baseline pins ``ring_ok`` =
      ``ring_bytes_per_hop <= gather_bytes`` and the hop count R-1.
    * ``ring_matches_gather`` — bit-for-bit parity of the two assembled
      (R, n) views (the concatenation-order contract precise mode
      depends on). STRUCTURAL.
    """
    if len(jax.devices()) < 8:
        csv("microbench", op="ring_round", skipped=1,
            note="needs 8 devices; set "
                 "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return None
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_flat_engine_mesh, ring_gather
    R = 8
    n = 4096 if smoke else 65536
    n_it = 10 if smoke else 20
    mesh, plan = make_flat_engine_mesh(R)
    x = jax.device_put(
        jnp.arange(R * n, dtype=jnp.float32).reshape(R, n),
        jax.sharding.NamedSharding(mesh, P("data", None)))

    def _ring(v):
        return ring_gather(v, ("data",), world=R, axis=0)

    def _gather(v):
        return jax.lax.all_gather(v, ("data",), axis=0, tiled=True)

    f_ring = jax.jit(jax.shard_map(_ring, mesh=mesh, in_specs=P("data", None),
                               out_specs=P(None, None), check_vma=False))
    f_gather = jax.jit(jax.shard_map(_gather, mesh=mesh,
                                 in_specs=P("data", None),
                                 out_specs=P(None, None),
                                 check_vma=False))
    same = bool(jnp.array_equal(f_ring(x), f_gather(x)))
    us_ring = _time(f_ring, x, n=n_it)
    us_gather = _time(f_gather, x, n=n_it)
    gather_bytes = R * n * 4
    out = {"workers": R, "cols": n,
           "us_ring": round(us_ring, 1), "us_gather": round(us_gather, 1),
           "gather_bytes": gather_bytes,
           "ring_bytes_per_hop": gather_bytes // R,
           "ring_hops": R - 1,
           "ring_ok": gather_bytes // R <= gather_bytes,
           "ring_matches_gather": same}
    csv("microbench", op="ring_round", us_ring=round(us_ring, 1),
        us_gather=round(us_gather, 1), gather_bytes=gather_bytes,
        ring_bytes_per_hop=out["ring_bytes_per_hop"],
        ring_hops=out["ring_hops"], ring_ok=out["ring_ok"],
        ring_matches_gather=same,
        note="ppermute ring (R-1 one-row hops) vs one tiled all_gather of "
             "the full (R, n) view; parity is the staleness-k "
             "concatenation-order contract")
    return out


def bench_method_zoo(*, smoke=False):
    """One flat-engine round per REGISTERED consensus method on the same
    model/optimizer/tau: the method-zoo cost matrix. Methods come from
    the registry (``core.methods.method_names``), so a newly registered
    method lands a row here (and in the committed ``BENCH_overlap.json``
    ``method_zoo`` key) without touching this file. The canonical name
    LIST is structural (a registry change must regenerate the baseline);
    ``us_per_round`` rides the ``us_`` timing prefix. ddp has no round —
    its row times tau per-step gradient-averaging steps instead."""
    from repro.core.methods import get_method, method_names
    data = default_data()
    opt = make_optimizer("sgd")
    M, bs, tau = 8, 16 if smoke else 64, 4
    n_it = 3 if smoke else 20
    init = lambda k: mlp_init(k, data["dim"], data["n_classes"])
    batch = {"x": jnp.zeros((tau, M, bs, data["dim"])),
             "y": jnp.zeros((tau, M, bs), jnp.int32)}
    names = method_names(aliases=False)
    out = {"workers": M, "tau": tau, "engine": "flat",
           "method_names": list(names), "methods": {}}
    for name in names:
        spec = get_method(name)
        if not spec.communicates:     # ddp: tau per-step grad averages
            p0 = init(jax.random.PRNGKey(0))
            st = TrainState(params=p0, opt=opt.init(p0), cstate={},
                            t=jnp.zeros((), jnp.int32))
            fn = jax.jit(make_ddp_step(mlp_loss, opt, base_lr=0.05,
                                       total_steps=100))
            db = jax.tree.map(lambda a: a[0], batch)
            us = _time(lambda s, b: fn(s, b)[0], st, db, n=n_it) * tau
        else:
            dcfg = DPPFConfig(consensus=name, alpha=0.1, lam=0.5, tau=tau,
                              engine="flat")
            st = init_train_state(init, opt, dcfg, M, jax.random.PRNGKey(0))
            fn = jax.jit(make_round_step(mlp_loss, opt, dcfg, base_lr=0.05,
                                         total_steps=100), donate_argnums=0)
            us = _time_donated(lambda s: fn(s, batch)[0], st, n=n_it)
        out["methods"][name] = {"us_per_round": round(us, 1)}
        csv("microbench", op=f"method_zoo_{name}", us_per_round=round(us, 1),
            aux_rows=spec.aux_rows, communicates=spec.communicates)
    csv("microbench", op="method_zoo", methods=len(names),
        note="one flat-engine round per registered method (ddp = tau "
             "per-step grad averages); registry-driven rows")
    return out


def bench_autotune(*, smoke=False):
    """THE autotune acceptance row (DESIGN.md §Autotune): the probe
    search on the REAL round step with an INJECTED OOM frontier
    (``inject_oom_above`` — the same ``--tune-oom-above`` CI hook), so
    the committed record pins a deterministic ladder: doubling 2, 4, 8
    ok -> 16 OOM, binary refine 12 ok / 14, 13 OOM -> frontier 12, then
    the joint (tau, chunks) sweep at batch 12.

    Structural keys (host-independent; check_bench guards them on the
    committed ``BENCH_autotune.json``):

    * ``probes_within_budget`` — probe count bounded by the budget,
    * ``chosen_dominates_model`` — the chosen point beats every probed
      neighbor under the calibrated roofline model (per-sample round
      time; the calibration scale cannot flip an argmin),
    * ``backoff_exercised`` — the injected-OOM path really ran
      (``failures`` non-empty),
    * the plan's probe ladder itself (batches/taus/chunks/ok flags).

    Measured ``us_round`` per probe and ``residual_scale`` are
    host-relative timing fields."""
    from repro.train.autotune import (
        TuneSpace, autotune, inject_oom_above, make_round_probe_runner,
    )
    from repro.launch import roofline as rf
    data = default_data()
    M = 4
    width = 32 if smoke else 128
    reps = 2 if smoke else 10
    LIMIT = 12                       # injected feasibility frontier
    dcfg = DPPFConfig(alpha=0.1, lam=0.5, tau=4, engine="flat",
                      overlap="doublebuf", overlap_chunks=1)
    opt = make_optimizer("sgd")
    init = lambda k: mlp_init(k, data["dim"], data["n_classes"],
                              width=width)

    def batch_fn(cand):
        return {"x": jnp.zeros((cand.tau, M, cand.batch, data["dim"])),
                "y": jnp.zeros((cand.tau, M, cand.batch), jnp.int32)}

    runner = inject_oom_above(
        make_round_probe_runner(init, mlp_loss, opt, dcfg, M, batch_fn,
                                reps=reps), LIMIT)
    n = init_train_state(init, opt, dcfg, M,
                         jax.random.PRNGKey(0)).engine.layout.n

    def model_fn(cand):
        # the same accounting as bench_overlap_round: MLP fwd+bwd ~ 3x
        # fwd flops per local step, worker-row gather + (R, R) psum
        dims = [data["dim"], width, width, data["n_classes"]]
        fwd = 2 * cand.batch * sum(a * b for a, b in zip(dims, dims[1:]))
        return rf.probe_round_model(
            work_s_per_step=3 * fwd * M / rf.peaks(MODELLED_KIND)["flops"],
            tau=cand.tau,
            gather_bytes=M * n * 4 + M * M * 4, device_kind=MODELLED_KIND,
            R=M, mode="doublebuf") * 1e6

    space = TuneSpace(min_batch=2, max_batch=32, taus=(2, 4),
                      chunks=(1, 2), probe_budget=16, overlap="doublebuf")
    plan = autotune(runner, model_fn, space)
    out = {
        "workers": M, "width": width, "oom_limit": LIMIT,
        "space": {"min_batch": space.min_batch,
                  "max_batch": space.max_batch, "taus": list(space.taus),
                  "chunks": list(space.chunks),
                  "probe_budget": space.probe_budget,
                  "overlap": space.overlap},
        "plan": plan.to_dict(),
        "probes_within_budget": plan.probes_used <= space.probe_budget,
        "chosen_dominates_model": plan.dominates_model,
        "backoff_exercised": bool(plan.failures),
        "dominates_measured": plan.dominates_measured,
    }
    csv("microbench", op="autotune",
        chosen=f"batch{plan.chosen.batch}_tau{plan.chosen.tau}"
               f"_ch{plan.chosen.overlap_chunks}",
        probes_used=plan.probes_used,
        oom_batches="/".join(str(b) for b in plan.failures),
        probes_within_budget=out["probes_within_budget"],
        chosen_dominates_model=out["chosen_dominates_model"],
        backoff_exercised=out["backoff_exercised"],
        note="probe search on the real round step under an injected "
             "RESOURCE_EXHAUSTED frontier (batch > 12 fails); chosen "
             "point beats every probed neighbor under the calibrated "
             "roofline model")
    return out


def bench_roundclock(*, smoke=False):
    """QSR RoundClock vs fixed tau: communication rounds (= consensus
    all-reduces) saved at the same step budget, and the wall cost of the
    re-chunked adaptive loop (incl. its extra per-tau compiles)."""
    data = default_data()
    opt = make_optimizer("sgd")
    M, bs = 4, 16 if smoke else 64
    steps = 64 if smoke else 512
    lr, beta = 0.3, 0.4
    batch = lambda tau: {"x": jnp.zeros((tau, M, bs, data["dim"])),
                         "y": jnp.zeros((tau, M, bs), jnp.int32)}
    init = lambda k: mlp_init(k, data["dim"], data["n_classes"])
    out = {}
    for sched, qb in (("fixed", 0.0), ("qsr", beta)):
        dcfg = DPPFConfig(alpha=0.1, lam=0.5, tau=4, engine="flat",
                          tau_schedule=sched, qsr_beta=qb)
        clock = RoundClock.from_config(dcfg, base_lr=lr, total_steps=steps)
        st = init_train_state(init, opt, dcfg, M, jax.random.PRNGKey(0))
        fn = jax.jit(make_round_step(mlp_loss, opt, dcfg, clock=clock),
                     donate_argnums=0)
        t0 = time.perf_counter()
        for spec in clock.rounds:
            st, _ = fn(st, batch(spec.tau))
        jax.block_until_ready(st.params)
        wall = time.perf_counter() - t0
        out[sched] = dict(clock.describe(), wall_s=round(wall, 3))
        csv("microbench", op=f"roundclock_{sched}",
            rounds=clock.total_rounds, allreduces=clock.total_rounds,
            tau_min=min(clock.taus()), tau_max=max(clock.taus()),
            wall_s=round(wall, 3))
    saved = out["fixed"]["rounds"] - out["qsr"]["rounds"]
    csv("microbench", op="roundclock",
        allreduces_saved=saved,
        saved_pct=round(100.0 * saved / out["fixed"]["rounds"], 1),
        note="QSR adaptive tau vs fixed tau at the same step budget "
             "(one consensus all-reduce per round)")
    out["allreduces_saved"] = saved
    out["allreduces_saved_pct"] = round(
        100.0 * saved / out["fixed"]["rounds"], 1)
    return out


def run(*, smoke=False):
    engine_row = bench_engine_vs_tree(smoke=smoke)
    bench_pullpush(smoke=smoke)
    bench_round_vs_ddp(smoke=smoke)
    bench_sharded_round(smoke=smoke)
    hier_row = bench_hierarchical_round(smoke=smoke)
    overlap_row = bench_overlap_round(smoke=smoke)
    ring_row = bench_ring_round(smoke=smoke)
    zoo_row = bench_method_zoo(smoke=smoke)
    autotune_row = bench_autotune(smoke=smoke)
    roundclock = bench_roundclock(smoke=smoke)
    # machine-readable perf trajectory across PRs (repo root)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    payload = {"smoke": smoke, "backend": jax.default_backend(),
               "roundclock": roundclock, "engine_vs_tree": engine_row,
               "hierarchical_round": hier_row}
    path = os.path.join(root, "BENCH_roundclock.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}")
    # the overlap acceptance baseline rides in its own file so its
    # structural gate (mode set, mesh, chunk counts) can evolve without
    # churning the round-clock baseline (benchmarks/check_bench.py checks
    # both in CI)
    opath = os.path.join(root, "BENCH_overlap.json")
    with open(opath, "w") as f:
        json.dump({"smoke": smoke, "backend": jax.default_backend(),
                   "overlap_round": overlap_row,
                   "ring_gather": ring_row,
                   "method_zoo": zoo_row}, f, indent=2,
                  sort_keys=True)
        f.write("\n")
    print(f"wrote {opath}")
    # the autotune acceptance baseline: the searched TunePlan (probe
    # ladder, injected-OOM failures, chosen point) plus the structural
    # gates check_bench pins (probe budget, model dominance, backoff)
    apath = os.path.join(root, "BENCH_autotune.json")
    with open(apath, "w") as f:
        json.dump({"smoke": smoke, "backend": jax.default_backend(),
                   "autotune": autotune_row}, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {apath}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, few iterations (CI)")
    args = ap.parse_args()
    run(smoke=args.smoke)

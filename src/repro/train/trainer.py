"""DPPF trainer: a communication ROUND is one compiled function —
``lax.scan`` over tau purely-local optimizer steps (zero worker-axis
collectives) followed by the consensus pull-push update (the round's single
all-reduce). The DDP baseline is a separate per-step function whose gradient
mean over the worker axis lowers to the classic every-step all-reduce.

Both are generic over ``loss_fn(params, batch) -> (loss, metrics)`` so the
same trainer drives the 10 assigned LM architectures and the small
paper-table stand-in models.

With ``DPPFConfig.engine == "flat"`` the worker parameters live in the
ConsensusEngine's persistent ``(R, n)`` fp32 view for the WHOLE run: it is
built once in ``init_train_state``, local steps differentiate through cheap
slice/reshape views of it (``engine.unflatten_row``), and the consensus
update runs as flat Gram+mixing passes — no per-round flatten/concatenate.
Donate the state (``jax.jit(round_step, donate_argnums=0)``) so the buffer
is reused in place across rounds (DESIGN.md §Consensus-engine).

Two round-level extensions on top of the flat engine:

* ``make_sharded_round_step`` lowers the WHOLE round under
  ``jax.shard_map``: worker rows of the (R, n) view shard over the plan's
  worker axes, columns over its fsdp/model axes; the round's collectives
  are one worker-row all-gather at the round boundary plus the engine's
  (R, R) partial-Gram psum (DESIGN.md §Sharded-execution).
* ``DPPFConfig.overlap`` runs the stale-consensus recursion
  (DESIGN.md §Overlap): ``"staleness1"`` applies the consensus computed
  from the PREVIOUS round's snapshot (carried in ``TrainState.snap``), so
  the consensus collectives have no data dependence on the current round's
  local steps and the scheduler hides them behind tau steps of compute;
  ``"doublebuf"`` additionally carries the snapshot ROW-SHARDED and
  dispatches its worker-row gather + stage-1 Gram psum in
  ``overlap_chunks`` column chunks interleaved with the scan's segments,
  leaving only coefficient math + the mix GEMM at the round boundary
  (round 0 fills the pipeline with an EXACT consensus of the fresh view);
  ``"staleness_k"`` generalizes doublebuf to a k-deep snapshot RING —
  round r applies the consensus of the round-(r-k) snapshot, rounds
  0..k-1 are exact-consensus pipeline fill, the sharded worker-row gather
  runs as a ``launch.mesh.ring_gather`` ppermute ring (R-1 single-row
  hops interleaved with the scan segments), and ``DPPFConfig.elastic``
  adds bounded-async membership: a per-row participation mask rides the
  carry, an inactive row freezes and drops out of the consensus weights
  for up to k rounds, then rejoins with an EASGD-style catch-up pull
  (``set_participation`` is the host-side driver hook).

Step/round accounting is owned by ``repro.train.clock.RoundClock``
(DESIGN.md §Round-clock): every builder reads lam_t via
``clock.lam_at(state.round)`` — the index of the round ABOUT TO RUN, so
round 0 evaluates ``lam_schedule(·, 0, ·)`` and the final round the full
lam — and the LR via ``clock.lr_at(t)``. The builders are tau-oblivious:
``t`` advances by the batch's leading (scan) dim and ``round`` by one, so
ONE builder serves fixed, remainder, and QSR-adaptive round lengths
(``jax.jit``'s shape-keyed cache is the per-tau compile cache).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import DPPFConfig
from repro.core import consensus
from repro.core.engine import ConsensusEngine, ShardedLayout
from repro.core.methods import get_method
from repro.optim import Optimizer, sam_gradient
from repro.train.clock import RoundClock


@dataclass
class TrainState:
    params: Any          # worker-stacked (M, ...) for DPPF; flat for DDP;
                         # the engine's (R, n) flat view when engine is set
    opt: Any
    cstate: Any          # consensus state (EASGD center etc.)
    t: jnp.ndarray       # local-step counter (scalar int32)
    snap: Any = None     # overlap carry (flat engine only). staleness1/
                         # doublebuf: {"x": (R, n) snapshot, "losses": (M,),
                         # "gns": (M,)}; staleness_k: a k-deep ring ordered
                         # oldest -> newest — {"x": (k, R, n), "losses":
                         # (k, M), "gns": (k, M)} plus, when elastic,
                         # {"act": (k, M) participation at snapshot time,
                         # "active": (M,) requested membership,
                         # "missed": (M,) int32 consecutive misses}
    round: Any = None    # round counter (scalar int32) — the clock position;
                         # None on hand-built/DDP states (builders fall back
                         # to the pre-scan ``t // tau``)
    engine: Any = None   # ConsensusEngine (static metadata) or None


# ``engine`` is hashable static metadata: jit recompiles if the layout
# changes, and donation/vmap only ever see the array fields.
jax.tree_util.register_dataclass(
    TrainState, data_fields=("params", "opt", "cstate", "t", "snap", "round"),
    meta_fields=("engine",))


def _chunk_bounds(n: int, k: int):
    """Split ``range(n)`` into ``k`` contiguous near-equal pieces (host
    ints; first pieces absorb the remainder). The one copy of the
    double-buffered overlap's chunk arithmetic — used for both the
    snapshot's column chunks and the scan's step segments."""
    base, rem = divmod(n, k)
    bounds, a = [], 0
    for i in range(k):
        b = a + base + (1 if i < rem else 0)
        bounds.append((a, b))
        a = b
    return bounds


def _round_index(state: TrainState, dcfg: DPPFConfig):
    """The index of the round about to run. States built by
    ``init_train_state`` carry the clock position; legacy hand-built states
    fall back to the PRE-scan ``t // tau`` (correct for fixed tau — the
    historical post-scan ``t // tau`` was the off-by-one)."""
    if state.round is not None:
        return state.round
    return state.t // max(dcfg.tau, 1)


def _legacy_clock(dcfg, base_lr, total_steps, warmup, who):
    if base_lr is None or total_steps is None:
        raise ValueError(f"{who} needs a RoundClock (clock=...) or the "
                         "legacy base_lr/total_steps pair")
    return RoundClock.from_config(dcfg, base_lr=base_lr,
                                  total_steps=total_steps, warmup=warmup)


def _grad_norm(grads):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))


def _scan_local_steps(loss_fn, opt: Optimizer, p0, opt_st, t0, batch, *,
                      clock: RoundClock, sam_rho, view=None):
    """The tau purely-local steps shared by every round builder:
    ``lax.scan`` over the batch's leading (tau) dim, vmap over the worker
    dim of ``p0``/``opt_st``/``batch[:, m]``. ``view`` maps a worker's
    parameters as carried (a flat-engine row) to the tree ``loss_fn``
    takes. Returns ``(params, opt_st, t, losses, gns)`` with losses/gns
    shaped (tau, M).

    Each layer sits under a named scope that the device trace carries
    (``tf_op``): ``dppf.view`` (the row's slices and casts; under the
    gradient its transposes too), ``dppf.model`` (forward as
    ``jvp(dppf.model)``, backward as ``transpose(jvp(dppf.model))``) and
    ``dppf.update`` (LR, gradient norm, optimizer step), all inside
    ``dppf.local``, the scan: ops that the compiler makes in the loop
    without op metadata (the whole view's cast, the embedding gradient's
    scatter, layout copies) take the loop's name on the chip's trace."""
    def loss(p, b):
        if view is not None:
            with jax.named_scope("dppf.view"):
                p = view(p)
        with jax.named_scope("dppf.model"):
            return loss_fn(p, b)

    def local_step(p, o, b, t):
        if sam_rho > 0:
            (loss_v, _), g = sam_gradient(loss, p, b, sam_rho)
        else:
            (loss_v, _), g = jax.value_and_grad(loss, has_aux=True)(p, b)
        with jax.named_scope("dppf.update"):
            lr = clock.lr_at(t)
            gn = _grad_norm(g)
            p, o = opt.step(p, g, o, lr)
        return p, o, loss_v, gn

    def micro(carry, mb):
        params, opt_state, t = carry
        params, opt_state, losses, gns = jax.vmap(
            local_step, in_axes=(0, 0, 0, None))(params, opt_state, mb, t)
        return (params, opt_state, t + 1), (losses, gns)

    with jax.named_scope("dppf.local"):
        (params, opt_st, t), (losses, gns) = jax.lax.scan(
            micro, (p0, opt_st, t0), batch)
    return params, opt_st, t, losses, gns


def init_train_state(loss_params_init, opt: Optimizer, dcfg: DPPFConfig,
                     n_workers: int, key, *, same_init=True, engine=None):
    """Stack per-worker params. The paper initializes all workers from the
    same random model (Alg. 1); ``same_init=False`` gives per-worker seeds
    (useful for the width ablations).

    With ``dcfg.engine == "flat"`` (or an explicit ``engine``) the stacked
    tree is flattened ONCE here into the engine's persistent (R, n) view;
    every subsequent round reuses/donates that buffer.
    """
    if same_init:
        p0 = loss_params_init(key)
        params = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_workers,) + a.shape), p0)
        # materialize (broadcast arrays are lazy views)
        params = jax.tree.map(jnp.array, params)
    else:
        keys = jax.random.split(key, n_workers)
        params = jax.vmap(loss_params_init)(keys)
    if engine is None and getattr(dcfg, "engine", "tree") == "flat" \
            and get_method(dcfg.consensus).communicates:
        engine = ConsensusEngine.from_stacked(
            params, method=dcfg.consensus, eps=dcfg.eps)
    snap = None
    if engine is not None:
        params = engine.flatten(params)           # the ONE flatten per run
        opt_state = jax.vmap(opt.init)(engine.workers(params))
        cstate = consensus.init_state(dcfg.consensus, params, engine=engine)
        overlap_mode = getattr(dcfg, "overlap", "none")
        if overlap_mode == "staleness_k":
            # k-deep snapshot ring, oldest -> newest: slot 0 is the
            # round-(r-k) snapshot whose consensus applies after round r's
            # scan; rounds 0..k-1 are exact-consensus pipeline fill. The
            # + 0.0 copy keeps the ring and params donation-distinct.
            k = dcfg.staleness
            snap = {"x": jnp.broadcast_to(
                        params[None], (k,) + params.shape) + 0.0,
                    "losses": jnp.zeros((k, n_workers), jnp.float32),
                    "gns": jnp.ones((k, n_workers), jnp.float32)}
            if dcfg.elastic:
                # sync is the scalar quorum gate (train/supervisor.py):
                # 1 = normal round, 0 = quorum-degraded — local steps run
                # but the consensus application is skipped bit-exactly
                snap.update(
                    act=jnp.ones((k, n_workers), jnp.float32),
                    active=jnp.ones((n_workers,), jnp.float32),
                    missed=jnp.zeros((n_workers,), jnp.int32),
                    sync=jnp.ones((), jnp.float32))
        elif overlap_mode != "none":
            # round-0 snapshot: the (degenerate) init fleet. staleness1
            # gates the first delta off (explicit pipeline bubble, round 0
            # is local steps only); doublebuf instead runs an EXACT
            # consensus of the fresh post-scan view in round 0 (pipeline
            # fill, DESIGN.md §Overlap). Either way the pipeline fills in
            # one round. The + 0.0 copy keeps snap and params
            # donation-distinct.
            snap = {"x": params + 0.0,
                    "losses": jnp.zeros((n_workers,), jnp.float32),
                    "gns": jnp.ones((n_workers,), jnp.float32)}
    else:
        if getattr(dcfg, "overlap", "none") != "none":
            raise ValueError(
                f"overlap={dcfg.overlap!r} requires engine='flat' (the "
                "stale snapshot is an extra (R, n) flat buffer)")
        opt_state = jax.vmap(opt.init)(params)
        cstate = consensus.init_state(dcfg.consensus, params)
    return TrainState(params=params, opt=opt_state, cstate=cstate,
                      t=jnp.zeros((), jnp.int32), snap=snap,
                      round=jnp.zeros((), jnp.int32), engine=engine)


def _row_select(active, new, old):
    """Per-worker-row select: rows with ``active > 0`` take ``new``, the
    rest keep ``old`` BIT-exactly (``jnp.where``, not arithmetic blending
    — a frozen elastic row must not drift by even one ulp)."""
    cond = (active > 0).reshape(active.shape + (1,) * (new.ndim - 1))
    return jnp.where(cond, new, old)


def set_participation(state: TrainState, active, *,
                      sync=None) -> TrainState:
    """Host-side elastic-membership hook: set which worker rows take part
    in the NEXT rounds (1 = active, 0 = dropped). The mask rides the
    snapshot carry; a dropped row freezes (its local steps revert, its
    pull/push coefficients zero, and its row leaves the consensus target
    weights) until it is re-activated here — or until it has missed
    ``dcfg.staleness`` consecutive rounds, when the bounded-staleness rule
    forces it back in. Requires an elastic staleness_k state
    (``DPPFConfig.elastic=True``).

    ``sync`` (the supervisor's quorum gate) sets the scalar degrade flag:
    0.0 makes the next round local-only — the scan runs but the consensus
    application (stale delta, catch-up pull, center move) is skipped
    bit-exactly; 1.0 restores normal rounds. ``None`` leaves the carried
    flag untouched (the pre-supervisor call signature)."""
    if state.snap is None or "active" not in state.snap:
        raise ValueError(
            "set_participation requires an elastic staleness_k TrainState "
            "(DPPFConfig.overlap='staleness_k', elastic=True)")
    act = consensus.as_participation_mask(
        active, state.snap["active"].shape[0])
    new_snap = dict(state.snap, active=act)
    if sync is not None:
        if "sync" not in state.snap:
            raise ValueError(
                "sync gating requires a state whose elastic carry has the "
                "sync scalar (init_train_state adds it; legacy restored "
                "states are backfilled by load_train_state)")
        new_snap["sync"] = jnp.asarray(sync, jnp.float32).reshape(())
    return dataclasses.replace(state, snap=new_snap)


def make_round_step(loss_fn, opt: Optimizer, dcfg: DPPFConfig, *,
                    clock: Optional[RoundClock] = None,
                    base_lr: Optional[float] = None,
                    total_steps: Optional[int] = None, warmup: int = 0,
                    sam_rho: float = 0.0):
    """Build the fused DPPF round: scan(tau local steps) + consensus.

    Input batch pytree has leading dims (tau_r, M, ...) where tau_r is THIS
    round's length (``RoundSpec.tau`` — fixed, remainder, or QSR-adaptive;
    a new length just retraces under jit). Schedules come from ``clock``
    (built from the legacy ``base_lr``/``total_steps`` pair when omitted).
    Returns round_step(state, batch) -> (state, metrics). jit/shard at
    callsite (``donate_argnums=0`` recommended — required for in-place
    flat-view reuse when the state carries a ConsensusEngine).
    """
    if clock is None:
        clock = _legacy_clock(dcfg, base_lr, total_steps, warmup,
                              "make_round_step")
    overlap_mode = getattr(dcfg, "overlap", "none")
    overlap = overlap_mode != "none"
    spec = get_method(dcfg.consensus)
    lpf = spec.push_source == "filtered_grad"

    def round_step(state: TrainState, batch):
        engine = state.engine
        if overlap and engine is None:
            raise ValueError(
                f"overlap={overlap_mode!r} requires the flat engine")
        if engine is None:
            view, p0 = None, state.params
        else:
            # local steps differentiate through the flat rows directly:
            # unflatten_row is slices+reshapes, so grads arrive flat and the
            # optimizer state stays (M, n) — no per-step re-flatten
            view = engine.unflatten_row
            with jax.named_scope("dppf.view"):
                p0 = engine.workers(state.params)

        params, opt_st, t, losses, gns = _scan_local_steps(
            loss_fn, opt, p0, state.opt, state.t, batch, clock=clock,
            sam_rho=sam_rho, view=view)
        if engine is not None:
            with jax.named_scope("dppf.view"):
                params = engine.with_workers(state.params, params)

        with jax.named_scope("dppf.consensus"):
            # the round ABOUT TO apply its consensus — read the lam schedule
            # at the clock position, not the post-scan ``t // tau`` (the old
            # off-by-one that skipped round 0 and shifted the whole
            # trajectory)
            round_idx = _round_index(state, dcfg)
            lam_t = clock.lam_at(round_idx)
            ps = clock.pull_scale_at(round_idx)
            staleness_depth = jnp.int32(0)

            def lpf_update(params_now, cst):
                # EMA-filtered local progress (LPF-SGD): the per-round
                # parameter delta is the accumulated gradient direction;
                # filtering it gives the alternative push force. Frozen
                # elastic rows contribute a zero delta (their scan reverted).
                if not lpf:
                    return None, cst
                g = spec.filter_mu * cst["g_ema"] + (1.0 - spec.filter_mu) \
                    * (p0 - engine.workers(params_now))
                return g, {"g_ema": g}

            if overlap_mode == "staleness1":
                # staleness-1: consensus of the PREVIOUS round's snapshot; its
                # collectives have no data dependence on this round's scan, so
                # the scheduler overlaps them with the tau local steps. The
                # delta is applied to the fresh post-local-step view; the fresh
                # view becomes the next round's snapshot.
                snap = state.snap
                push_vec, cstate_in = lpf_update(params, state.cstate)
                c_out, cstate, metrics = consensus.apply_round(
                    snap["x"], dcfg, lam_t, cstate_in,
                    losses=snap["losses"], grad_norms=snap["gns"],
                    engine=engine, push_vec=push_vec, pull_scale=ps)
                new_snap = {"x": params, "losses": losses[-1], "gns": gns[-1]}
                # explicit round-0 pipeline bubble: the init snapshot is
                # (usually) collapsed, and consensus of a collapsed fleet is
                # noise-floor push (engine docstring) — skip the first delta
                live = (state.t > 0).astype(jnp.float32)
                params = params + live * (c_out - snap["x"])
                staleness_depth = live.astype(jnp.int32)
            elif overlap_mode == "doublebuf":
                # double-buffered: the snapshot's stage-1 column contraction
                # is dispatched in ``overlap_chunks`` pieces with no data
                # dependence on the scan (under shard_map the matching
                # gather/psum chunks interleave with the local steps — this
                # builder is the single-shard reference of the same
                # recursion); the round boundary runs coefficient math +
                # mixing only. Round 0 is the pipeline-fill bubble: an EXACT
                # consensus of the fresh q (not a skipped round — the init
                # snapshot is the collapsed fleet and carries no
                # information).
                snap = state.snap
                push_vec, cstate = lpf_update(params, state.cstate)
                stages, _ = consensus.lower_stages(
                    engine, dcfg, lam_t, losses=snap["losses"],
                    grad_norms=snap["gns"], pull_scale=ps)
                T1 = stages[0][1]
                width = snap["x"].shape[-1]
                n_eff = max(1, min(dcfg.overlap_chunks, width))
                gram = None
                for a, b in _chunk_bounds(width, n_eff):
                    part = engine.stage_comm(snap["x"][:, a:b], T1)
                    gram = part if gram is None else gram + part
                new_snap = {"x": params, "losses": losses[-1], "gns": gns[-1]}
                q = params

                def _stale(_):
                    c_out, _, m = consensus.apply_round(
                        snap["x"], dcfg, lam_t, cstate, losses=snap["losses"],
                        grad_norms=snap["gns"], engine=engine, first_gram=gram,
                        push_vec=push_vec, pull_scale=ps)
                    return q + (c_out - snap["x"]), m

                def _bubble(_):
                    new, _, m = consensus.apply_round(
                        q, dcfg, lam_t, cstate, losses=losses[-1],
                        grad_norms=gns[-1], engine=engine,
                        push_vec=push_vec, pull_scale=ps)
                    return new, m

                params, metrics = jax.lax.cond(state.t > 0, _stale, _bubble,
                                               None)
                staleness_depth = (state.t > 0).astype(jnp.int32)
            elif overlap_mode == "staleness_k":
                # staleness-k pipeline (DESIGN.md §Overlap): the snapshot
                # carry is a k-deep ring ordered oldest -> newest; slot 0
                # holds the round-(r-k) snapshot whose consensus applies
                # after THIS round's scan (doublebuf is the k=1 special case
                # of the same recursion). Rounds 0..k-1 are pipeline fill:
                # an EXACT consensus of the fresh post-scan view, gated by a
                # traced cond on the carried round index (resume-correct).
                k = dcfg.staleness
                snap = state.snap
                s_old = snap["x"][0]
                sl, sg = snap["losses"][0], snap["gns"][0]
                elastic = bool(getattr(dcfg, "elastic", False))
                act_old = eff = None
                if elastic:
                    active, missed = snap["active"], snap["missed"]
                    # bounded staleness: a row that already missed k rounds
                    # is forced back in this round
                    eff = jnp.where(missed >= k, jnp.float32(1.0), active)
                    act_old = snap["act"][0]
                    # dropped rows freeze: revert this round's local steps
                    # (params AND optimizer state) bit-exactly
                    params = engine.with_workers(
                        params, _row_select(eff, engine.workers(params), p0))
                    opt_st = jax.tree.map(
                        lambda nw, ow: _row_select(eff, nw, ow),
                        opt_st, state.opt)
                # filtered-grad update AFTER the elastic freeze: frozen rows'
                # reverted scans contribute a zero delta to the EMA
                push_vec, cstate = lpf_update(params, state.cstate)
                # the old slot's stage-1 contraction, chunked like doublebuf
                # (under shard_map the matching ring-gather + psum chunks
                # interleave with the scan — this is the single-shard
                # reference of the same recursion)
                stages, _ = consensus.lower_stages(
                    engine, dcfg, lam_t, losses=sl, grad_norms=sg,
                    mask=act_old, pull_scale=ps)
                T1 = stages[0][1]
                width = s_old.shape[-1]
                n_eff = max(1, min(dcfg.overlap_chunks, width))
                gram = None
                for a, b in _chunk_bounds(width, n_eff):
                    part = engine.stage_comm(s_old[:, a:b], T1)
                    gram = part if gram is None else gram + part
                q = params

                def _stale(_):
                    c_out, _, m = consensus.apply_round(
                        s_old, dcfg, lam_t, cstate, losses=sl, grad_norms=sg,
                        engine=engine, first_gram=gram, mask=act_old,
                        push_vec=push_vec, pull_scale=ps)
                    return q + (c_out - s_old), m

                def _fill(_):
                    new, _, m = consensus.apply_round(
                        q, dcfg, lam_t, cstate, losses=losses[-1],
                        grad_norms=gns[-1], engine=engine, mask=eff,
                        push_vec=push_vec, pull_scale=ps)
                    return new, m

                params, metrics = jax.lax.cond(round_idx >= k, _stale, _fill,
                                               None)
                if elastic:
                    # reception gate: the stale delta was masked by the
                    # SNAPSHOT-time participation (act_old); a row inactive
                    # NOW must not receive it either — keep it at its frozen
                    # q
                    params = engine.with_workers(
                        params,
                        _row_select(eff, engine.workers(params),
                                    engine.workers(q)))
                    # EASGD-style catch-up: a row rejoining after >= 1 missed
                    # rounds pulls toward the active-fleet mean
                    rejoin = eff * (missed > 0).astype(jnp.float32)
                    w = engine.workers(params)
                    mean = jnp.sum(eff[:, None] * w, axis=0) \
                        / jnp.maximum(jnp.sum(eff), 1.0)
                    w = w + (dcfg.elastic_catchup * rejoin)[:, None] \
                        * (mean[None] - w)
                    params = engine.with_workers(params, w)
                    if "sync" in snap:
                        # quorum-degrade gate (train/supervisor.py): sync == 0
                        # reverts the whole consensus application — stale
                        # delta, catch-up pull, and the aux-center move —
                        # leaving every row at its post-freeze local view q
                        # BIT-exactly (a where select, never arithmetic
                        # blending); the ring still advances below so the
                        # pipeline stays resume-correct
                        params = jnp.where(snap["sync"] > 0, params, q)
                # advance the ring: drop the consumed slot, append fresh q
                new_snap = {
                    "x": jnp.concatenate([snap["x"][1:], q[None]], axis=0),
                    "losses": jnp.concatenate(
                        [snap["losses"][1:], losses[-1][None]], axis=0),
                    "gns": jnp.concatenate(
                        [snap["gns"][1:], gns[-1][None]], axis=0)}
                if elastic:
                    new_snap.update(
                        act=jnp.concatenate([snap["act"][1:], eff[None]],
                                            axis=0),
                        active=active,
                        missed=jnp.where(eff > 0, 0, missed + 1)
                        .astype(jnp.int32))
                    if "sync" in snap:
                        new_snap["sync"] = snap["sync"]
                staleness_depth = jnp.where(round_idx >= k, k, 0) \
                    .astype(jnp.int32)
            else:
                push_vec, cstate_in = lpf_update(params, state.cstate)
                params, cstate, metrics = consensus.apply_round(
                    params, dcfg, lam_t, cstate_in,
                    losses=losses[-1], grad_norms=gns[-1], engine=engine,
                    push_vec=push_vec, pull_scale=ps)
                new_snap = state.snap
        metrics = dict(metrics)
        metrics["train_loss"] = losses.mean()
        metrics["lam_t"] = lam_t
        metrics["staleness"] = staleness_depth
        new_state = TrainState(params=params, opt=opt_st, cstate=cstate, t=t,
                               snap=new_snap,
                               round=jnp.asarray(round_idx + 1, jnp.int32),
                               engine=engine)
        return new_state, metrics

    return round_step


def _axis_entry(axes):
    """PartitionSpec entry for an axis group (None when empty)."""
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def _lin_index(axes, sizes):
    """Linear shard index over an ordered axis group (row-major, matching
    ``lax.all_gather(..., axes, tiled=True)`` concatenation order)."""
    idx = 0
    for a in axes:
        idx = idx * sizes[a] + jax.lax.axis_index(a)
    return idx


def make_sharded_round_step(loss_fn, opt: Optimizer, dcfg: DPPFConfig, *,
                            mesh, plan, clock: Optional[RoundClock] = None,
                            base_lr: Optional[float] = None,
                            total_steps: Optional[int] = None,
                            warmup: int = 0, sam_rho: float = 0.0):
    """Build the DPPF round lowered under ``jax.shard_map`` (flat engine
    only): worker rows of the (R, n) view shard over ``plan.worker_axes``,
    columns over ``plan.fsdp_axes + plan.model_axes``.

    Collective placement (DESIGN.md §Sharded-execution): the tau local
    steps run on column-gathered local worker rows with ZERO worker-axis
    collectives; the round boundary all-gathers worker rows per column
    shard (the paper's one consensus all-reduce, Table 2) and the engine
    completes its Gram with an (R, R) psum over the column axes. The
    (M, M)-sized coefficient math and the mixing GEMM are shard-local.
    With ``dcfg.overlap == "staleness1"`` the consensus reads the
    round-(k-1) snapshot (rows replicated, columns sharded), so its
    gather/psum have no data dependence on this round's scan and overlap
    with the local compute.

    On a hierarchical ``workers x fsdp x model`` mesh
    (`launch.mesh.make_hier_engine_mesh`) the column group spans BOTH the
    fsdp and model axes and the partial-Gram psum reduces over the full
    group. Requires M divisible by the worker-axes size; the column group
    falls back per `launch.mesh.flat_col_axes` (full fsdp+model group ->
    divisible sub-group -> replicated with the psum a no-op) when n is not
    divisible. jit with ``donate_argnums=0`` at the callsite, like
    ``make_round_step``.

    With ``dcfg.overlap == "doublebuf"`` the snapshot is carried
    ROW-SHARDED and the round is split into ``overlap_chunks`` segments:
    before each segment's local steps, one column chunk of the snapshot's
    worker-row all-gather and its stage-1 partial-Gram psum are dispatched
    — neither depends on the scan, so the scheduler hides ALL of the
    round's heavy communication behind compute; the boundary runs only the
    (R, R) coefficient math and the column-local mix GEMM (no fresh
    gather: each device applies its own rows of the delta). Round 0 is
    the pipeline-fill bubble and applies an EXACT consensus of the fresh
    view (DESIGN.md §Overlap).

    ``dcfg.overlap == "staleness_k"`` runs the k-deep generalization of
    the same recursion: the snapshot carry is a ring of ``k`` row-sharded
    buffers (oldest -> newest), each chunk's worker-row gather runs as a
    ``launch.mesh.ring_gather`` ppermute ring (R-1 hops of one local row
    block, bit-for-bit the tiled all_gather concatenation order, so
    precise-mode parity is preserved while the peak per-hop payload drops
    by 1/R), and rounds 0..k-1 fill the pipeline with exact consensus.
    ``dcfg.elastic`` threads the per-row participation mask through the
    same carry on flat Wx1 and hierarchical WxFxM meshes.
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import ring_gather

    if clock is None:
        clock = _legacy_clock(dcfg, base_lr, total_steps, warmup,
                              "make_sharded_round_step")
    overlap_mode = getattr(dcfg, "overlap", "none")
    stale1 = overlap_mode == "staleness1"
    dbuf = overlap_mode == "doublebuf"
    sk = overlap_mode == "staleness_k"
    k_depth = getattr(dcfg, "staleness", 1)
    elastic = sk and bool(getattr(dcfg, "elastic", False))
    spec = get_method(dcfg.consensus)
    lpf = spec.push_source == "filtered_grad"
    row_axes = tuple(plan.worker_axes)
    sizes = dict(mesh.shape)
    row_size = math.prod(sizes[a] for a in row_axes) if row_axes else 1

    def round_step(state: TrainState, batch):
        engine = state.engine
        if engine is None:
            raise ValueError("make_sharded_round_step requires the flat "
                             "engine (DPPFConfig.engine='flat')")
        L = engine.layout
        # n: the view's columns (the parameters plus any kernel padding)
        M, n, aux = L.M, state.params.shape[-1], L.aux
        if row_size > 1 and M % row_size:
            raise ValueError(
                f"workers ({M}) not divisible over worker axes "
                f"{row_axes} (size {row_size})")
        from repro.launch.mesh import flat_col_axes
        # the shared column rule (launch.mesh.flat_col_axes): the full
        # fsdp+model group when divisible — the partial-Gram psum then
        # spans both axes — else the divisible sub-group, else replicated
        # columns with the psum a no-op
        eff_cols = flat_col_axes(mesh, n, plan)
        col_e = _axis_entry(eff_cols)
        cols = math.prod(sizes[a] for a in eff_cols) if eff_cols else 1
        n_loc, m_loc = n // cols, M // row_size
        s_engine = dataclasses.replace(engine, shard=ShardedLayout(
            row_axes=row_axes, col_axes=eff_cols, rows=row_size, cols=cols))
        row_e = _axis_entry(row_axes)
        # the scalar quorum gate rides the elastic carry when present
        # (init_train_state always adds it; load_train_state backfills
        # legacy elastic checkpoints)
        has_sync = elastic and state.snap is not None \
            and "sync" in state.snap

        # GSPMD workaround (jax 0.4.37): when the specs leave mesh axes
        # unmentioned (the replicated-columns fallback), a
        # jnp.concatenate of shard_map outputs that is returned from jit
        # alongside ANY other shard_map output comes back multiplied by
        # the unmentioned-group size — the reshard of the concat SUMS
        # the replicas instead of selecting one (metrics stay exact
        # while params blow up 4x on a 2x2x2 mesh with cols=()).
        # Pinning the concat fully replicated sidesteps the bad
        # reshard; only the fallback case pays for it.
        unmentioned = mesh.size // (row_size * cols)

        def stitch(parts, axis=0):
            out = jnp.concatenate(parts, axis=axis)
            if unmentioned > 1:
                from jax.sharding import NamedSharding
                out = jax.lax.with_sharding_constraint(
                    out, NamedSharding(mesh, P(*([None] * out.ndim))))
            return out
        tau = jnp.shape(jax.tree.leaves(batch)[0])[0]

        def leading_dim_spec(leaf, entry, offset=0):
            nd = jnp.ndim(leaf)
            return P(*([None] * offset + [entry] + [None] * (nd - offset - 1))) \
                if nd > offset else P()

        def mapped(w_loc, opt_loc, t0, rnd0, b_loc, *rest):
            rest = list(rest)
            # the filtered-gradient EMA rides LAST in the operand list
            # (rows replicated, columns sharded) — pop it from the end
            # first so the positional front-pops below stay stable
            g_ema = rest.pop() if lpf else None
            aux_loc = rest.pop(0) if aux else None
            snap_x = snap_aux = snap_l = snap_g = None
            act_ring = active = missed = sync = None
            if stale1:
                snap_x, snap_l, snap_g = rest
            elif dbuf:
                snap_x = rest.pop(0)             # (m_loc, n_loc) row-sharded
                if aux:
                    snap_aux = rest.pop(0)       # (aux, n_loc)
                snap_l, snap_g = rest
            elif sk:
                snap_x = rest.pop(0)        # (k, m_loc, n_loc) row-sharded
                if aux:
                    snap_aux = rest.pop(0)       # (k, aux, n_loc)
                snap_l = rest.pop(0)             # (k, M)
                snap_g = rest.pop(0)             # (k, M)
                if elastic:
                    act_ring = rest.pop(0)       # (k, M)
                    active = rest.pop(0)         # (M,)
                    missed = rest.pop(0)         # (M,) int32
                    if has_sync:
                        sync = rest.pop(0)       # () quorum gate

            # clock position of the round about to mix (pre-scan index —
            # same off-by-one fix as make_round_step)
            with jax.named_scope("dppf.consensus"):
                lam_t = clock.lam_at(rnd0)
                ps = clock.pull_scale_at(rnd0)
            view = engine.unflatten_row
            with jax.named_scope("dppf.view"):
                w_full = jax.lax.all_gather(
                    w_loc, eff_cols, axis=1, tiled=True) \
                    if eff_cols else w_loc

            if dbuf or sk:
                # the tau local steps split into n_eff segments; ahead of
                # each segment one column chunk of the round-(r-k)
                # snapshot's worker-row gather + stage-1 contraction psum
                # is dispatched — no data dependence on the scan, so the
                # collectives run under the segment's compute. staleness_k
                # consumes ring slot 0 (the oldest snapshot) and moves
                # each chunk over the ppermute ring: R-1 single-row-block
                # hops instead of one monolithic all-gather, identical
                # concatenation order (launch.mesh.ring_gather contract)
                sx0 = snap_x[0] if sk else snap_x       # (m_loc, n_loc)
                sa0 = (snap_aux[0] if sk else snap_aux) if aux else None
                sl0 = snap_l[0] if sk else snap_l
                sg0 = snap_g[0] if sk else snap_g
                act0 = act_ring[0] if elastic else None
                with jax.named_scope("dppf.consensus"):
                    stages, _ = consensus.lower_stages(
                        s_engine, dcfg, lam_t, losses=sl0, grad_norms=sg0,
                        mask=act0, pull_scale=ps)
                T1 = stages[0][1]
                n_eff = max(1, min(dcfg.overlap_chunks, tau, n_loc))
                gram, gath = None, []
                params, opt_st, t = w_full, opt_loc, t0
                l_parts, g_parts = [], []
                for (ca, cz), (sa, sz) in zip(_chunk_bounds(n_loc, n_eff),
                                              _chunk_bounds(tau, n_eff)):
                    with jax.named_scope("dppf.consensus"):
                        piece = sx0[:, ca:cz]
                        if row_size > 1:
                            with jax.named_scope("dppf.exchange"):
                                piece = ring_gather(
                                    piece, row_axes, world=row_size,
                                    axis=0) if sk else jax.lax.all_gather(
                                        piece, row_axes, axis=0, tiled=True)
                        if aux:
                            piece = jnp.concatenate(
                                [piece, sa0[:, ca:cz]], axis=0)
                        gath.append(piece)
                        part = s_engine.stage_comm(piece, T1)
                        gram = part if gram is None else gram + part
                    seg = jax.tree.map(lambda l: l[sa:sz], b_loc)
                    params, opt_st, t, lj, gj = _scan_local_steps(
                        loss_fn, opt, params, opt_st, t, seg, clock=clock,
                        sam_rho=sam_rho, view=view)
                    l_parts.append(lj)
                    g_parts.append(gj)
                losses = jnp.concatenate(l_parts, axis=0)
                gns = jnp.concatenate(g_parts, axis=0)
                with jax.named_scope("dppf.consensus"):
                    s_full = jnp.concatenate(gath, axis=1)    # (R, n_loc)
            else:
                params, opt_st, t, losses, gns = _scan_local_steps(
                    loss_fn, opt, w_full, opt_loc, t0, b_loc, clock=clock,
                    sam_rho=sam_rho, view=view)

            with jax.named_scope("dppf.consensus"):
                eff = eff_loc = None
                r_off = 0
                if elastic:
                    # bounded staleness: a row that already missed k rounds
                    # is forced back in; dropped rows freeze bit-exactly
                    # (local steps revert on params AND optimizer state)
                    eff = jnp.where(missed >= k_depth, jnp.float32(1.0),
                                    active)
                    if row_size > 1:
                        r_off = _lin_index(row_axes, sizes) * m_loc
                        eff_loc = jax.lax.dynamic_slice_in_dim(
                            eff, r_off, m_loc, 0)
                    else:
                        eff_loc = eff
                    params = _row_select(eff_loc, params, w_full)
                    opt_st = jax.tree.map(
                        lambda nw, ow: _row_select(eff_loc, nw, ow),
                        opt_st, opt_loc)

                # round boundary: back to own columns
                if eff_cols:
                    c_idx = _lin_index(eff_cols, sizes)
                    q_loc = jax.lax.dynamic_slice_in_dim(
                        params, c_idx * n_loc, n_loc, 1)
                else:
                    q_loc = params
                if row_size > 1:
                    with jax.named_scope("dppf.exchange"):
                        l_last = jax.lax.all_gather(losses[-1], row_axes,
                                                    tiled=True)
                        g_last = jax.lax.all_gather(gns[-1], row_axes,
                                                    tiled=True)
                else:
                    l_last, g_last = losses[-1], gns[-1]

                push_vec = None
                if lpf:
                    # EMA-filtered local progress (LPF-SGD): the own-row,
                    # own-column delta of this round's scan (zero for frozen
                    # elastic rows — their q reverted to w), row-gathered to
                    # the full (M, n_loc) slab every column shard mixes with
                    delta = w_loc - q_loc
                    if row_size > 1:
                        with jax.named_scope("dppf.exchange"):
                            delta = jax.lax.all_gather(
                                delta, row_axes, axis=0, tiled=True)
                    push_vec = spec.filter_mu * g_ema \
                        + (1.0 - spec.filter_mu) * delta

                def gather_rows(x_loc, *, ring=False):
                    """Own-column worker rows + aux -> the full (R, n_loc)
                    view (THE consensus all-reduce of the paper). With
                    ``ring=True`` the gather runs over the ppermute ring
                    (bit-identical result, R-1 one-block hops)."""
                    if row_size > 1:
                        with jax.named_scope("dppf.exchange"):
                            rows = ring_gather(
                                x_loc, row_axes, world=row_size,
                                axis=0) if ring else jax.lax.all_gather(
                                    x_loc, row_axes, axis=0, tiled=True)
                    else:
                        rows = x_loc
                    return jnp.concatenate([rows, aux_loc], axis=0) if aux \
                        else rows

                def own_rows(full):
                    """Slice this device's worker rows back out."""
                    if row_size > 1:
                        return jax.lax.dynamic_slice_in_dim(
                            full[:M], _lin_index(row_axes, sizes) * m_loc,
                            m_loc, 0)
                    return full[:M]

                if dbuf or sk:
                    # boundary: coefficient math + mix GEMM only. The delta
                    # is applied shard-locally (own worker rows + aux) — no
                    # fresh row gather; the new snapshot is the row-SHARDED q
                    # (staleness_k: appended to the ring, displacing slot 0).
                    def _stale(_):
                        c_out, _, m = consensus.apply_round(
                            s_full, dcfg, lam_t, state.cstate, losses=sl0,
                            grad_norms=sg0, engine=s_engine, first_gram=gram,
                            mask=act0, push_vec=push_vec, pull_scale=ps)
                        delta = c_out - s_full
                        outs = [q_loc + own_rows(delta)]
                        if aux:
                            outs.append(aux_loc + delta[M:])
                        return tuple(outs + [m])

                    def _fill(_):
                        # pipeline fill: EXACT consensus of the fresh q
                        X = gather_rows(q_loc, ring=sk)
                        newX, _, m = consensus.apply_round(
                            X, dcfg, lam_t, state.cstate, losses=l_last,
                            grad_norms=g_last, engine=s_engine, mask=eff,
                            push_vec=push_vec, pull_scale=ps)
                        outs = [own_rows(newX)]
                        if aux:
                            outs.append(newX[M:])
                        return tuple(outs + [m])

                    pred = (rnd0 >= k_depth) if sk else (t0 > 0)
                    res = jax.lax.cond(pred, _stale, _fill, None)
                    new_w = res[0]
                    new_aux = res[1] if aux else None
                    metrics = dict(res[-1])
                    if elastic:
                        # reception gate: a row inactive NOW keeps its frozen
                        # q (the stale delta's mask is snapshot-time)
                        new_w = _row_select(eff_loc, new_w, q_loc)
                        # EASGD-style catch-up: a row rejoining after >= 1
                        # missed rounds pulls toward the active-fleet mean
                        rejoin = eff * (missed > 0).astype(jnp.float32)
                        partial = jnp.sum(eff_loc[:, None] * new_w, axis=0)
                        if row_size > 1:
                            with jax.named_scope("dppf.exchange"):
                                partial = jax.lax.psum(partial, row_axes)
                        mean = partial / jnp.maximum(jnp.sum(eff), 1.0)
                        cj = dcfg.elastic_catchup * rejoin
                        cj_loc = jax.lax.dynamic_slice_in_dim(
                            cj, r_off, m_loc, 0) if row_size > 1 else cj
                        new_w = new_w + cj_loc[:, None] * (mean[None] - new_w)
                        if has_sync:
                            # quorum-degrade gate: sync == 0 reverts the whole
                            # consensus application — every worker row keeps
                            # its frozen/post-scan q and the aux center its
                            # pre-round slab, bit-exactly (where select); the
                            # ring still advances below
                            new_w = jnp.where(sync > 0, new_w, q_loc)
                            if aux:
                                new_aux = jnp.where(sync > 0, new_aux, aux_loc)
                    if sk:
                        new_snap_x = jnp.concatenate(
                            [snap_x[1:], q_loc[None]], axis=0)
                        new_snap_aux = jnp.concatenate(
                            [snap_aux[1:], aux_loc[None]], axis=0) if aux \
                            else None
                        staleness_depth = jnp.where(
                            rnd0 >= k_depth, k_depth, 0).astype(jnp.int32)
                    else:
                        new_snap_x, new_snap_aux = q_loc, aux_loc
                        staleness_depth = (t0 > 0).astype(jnp.int32)
                elif stale1:
                    X = gather_rows(q_loc)
                    c_out, cstate, metrics = consensus.apply_round(
                        snap_x, dcfg, lam_t, state.cstate,
                        losses=snap_l, grad_norms=snap_g, engine=s_engine,
                        push_vec=push_vec, pull_scale=ps)
                    new_snap_x, new_snap_aux = X, None
                    # round-0 pipeline bubble, as in make_round_step
                    live = (t0 > 0).astype(jnp.float32)
                    newX = X + live * (c_out - snap_x)
                    new_w = own_rows(newX)
                    new_aux = newX[M:] if aux else None
                    metrics = dict(metrics)
                    staleness_depth = live.astype(jnp.int32)
                else:
                    X = gather_rows(q_loc)
                    newX, cstate, metrics = consensus.apply_round(
                        X, dcfg, lam_t, state.cstate,
                        losses=l_last, grad_norms=g_last, engine=s_engine,
                        push_vec=push_vec, pull_scale=ps)
                    new_snap_x = new_snap_aux = None
                    new_w = own_rows(newX)
                    new_aux = newX[M:] if aux else None
                    metrics = dict(metrics)
                    staleness_depth = jnp.int32(0)

            train_loss = losses.mean()
            if row_size > 1:
                train_loss = jax.lax.pmean(train_loss, row_axes)
            metrics["train_loss"] = train_loss
            metrics["lam_t"] = lam_t
            metrics["staleness"] = staleness_depth
            outs = [new_w, opt_st, t, rnd0 + 1, metrics]
            if aux:
                outs.append(new_aux)
            if stale1:
                outs.extend([new_snap_x, l_last, g_last])
            elif dbuf:
                outs.append(new_snap_x)
                if aux:
                    outs.append(new_snap_aux)
                outs.extend([l_last, g_last])
            elif sk:
                outs.append(new_snap_x)
                if aux:
                    outs.append(new_snap_aux)
                outs.extend([
                    jnp.concatenate([snap_l[1:], l_last[None]], axis=0),
                    jnp.concatenate([snap_g[1:], g_last[None]], axis=0)])
                if elastic:
                    outs.extend([
                        jnp.concatenate([act_ring[1:], eff[None]], axis=0),
                        active,
                        jnp.where(eff > 0, 0, missed + 1)
                        .astype(jnp.int32)])
                    if has_sync:
                        outs.append(sync)
            if lpf:
                outs.append(push_vec)       # rides LAST, like the input
            return tuple(outs)

        opt_in = jax.tree.map(lambda l: leading_dim_spec(l, row_e), state.opt)
        batch_in = jax.tree.map(lambda l: leading_dim_spec(l, row_e, 1),
                                batch)
        metric_out = {k: P() for k in ("consensus_dist", "pre_dist",
                                       "pull_force", "push_force",
                                       "train_loss", "lam_t", "staleness")}
        rnd0 = jnp.asarray(_round_index(state, dcfg), jnp.int32)
        args = [engine.workers(state.params), state.opt, state.t, rnd0,
                batch]
        in_specs = [P(row_e, col_e), opt_in, P(), P(), batch_in]
        out_specs = [P(row_e, col_e), opt_in, P(), P(), metric_out]
        if aux:
            args.append(state.params[M:])
            in_specs.append(P(None, col_e))
            out_specs.append(P(None, col_e))
        if stale1:
            # snapshot rows are replicated (every column shard needs the
            # full R rows to mix), columns sharded like the live view
            args.extend([state.snap["x"], state.snap["losses"],
                         state.snap["gns"]])
            in_specs.extend([P(None, col_e), P(), P()])
            out_specs.extend([P(None, col_e), P(), P()])
        elif dbuf:
            # the snapshot enters ROW-SHARDED (its worker-row gather is the
            # comm the next round hides mid-scan); aux rows columns-only
            args.append(state.snap["x"][:M])
            in_specs.append(P(row_e, col_e))
            out_specs.append(P(row_e, col_e))
            if aux:
                args.append(state.snap["x"][M:])
                in_specs.append(P(None, col_e))
                out_specs.append(P(None, col_e))
            args.extend([state.snap["losses"], state.snap["gns"]])
            in_specs.extend([P(), P()])
            out_specs.extend([P(), P()])
        elif sk:
            # the snapshot RING enters row-sharded per slot (ring dim
            # replicated); aux slabs columns-only; losses/gns/elastic
            # vectors replicated
            args.append(state.snap["x"][:, :M])
            in_specs.append(P(None, row_e, col_e))
            out_specs.append(P(None, row_e, col_e))
            if aux:
                args.append(state.snap["x"][:, M:])
                in_specs.append(P(None, None, col_e))
                out_specs.append(P(None, None, col_e))
            args.extend([state.snap["losses"], state.snap["gns"]])
            in_specs.extend([P(), P()])
            out_specs.extend([P(), P()])
            if elastic:
                args.extend([state.snap["act"], state.snap["active"],
                             state.snap["missed"]])
                in_specs.extend([P(), P(), P()])
                out_specs.extend([P(), P(), P()])
                if has_sync:
                    args.append(state.snap["sync"])
                    in_specs.append(P())
                    out_specs.append(P())
        if lpf:
            # the filtered-gradient EMA: rows replicated (every column
            # shard mixes the full M rows), columns sharded — LAST operand
            args.append(state.cstate["g_ema"])
            in_specs.append(P(None, col_e))
            out_specs.append(P(None, col_e))

        res = list(jax.shard_map(
            mapped, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=tuple(out_specs), check_vma=False)(*args))
        new_w, opt_st, t, rnd, metrics = res[:5]
        rest = res[5:]
        cstate = {"g_ema": rest.pop()} if lpf else state.cstate
        params = stitch([new_w, rest.pop(0)]) if aux else new_w
        if stale1:
            snap = {"x": rest[0], "losses": rest[1], "gns": rest[2]}
        elif dbuf:
            sx = rest.pop(0)
            if aux:
                sx = stitch([sx, rest.pop(0)])
            snap = {"x": sx, "losses": rest[0], "gns": rest[1]}
        elif sk:
            sx = rest.pop(0)
            if aux:
                sx = stitch([sx, rest.pop(0)], axis=1)
            snap = {"x": sx, "losses": rest.pop(0), "gns": rest.pop(0)}
            if elastic:
                snap.update(act=rest.pop(0), active=rest.pop(0),
                            missed=rest.pop(0))
                if has_sync:
                    snap["sync"] = rest.pop(0)
        else:
            snap = state.snap
        new_state = TrainState(params=params, opt=opt_st,
                               cstate=cstate, t=t, snap=snap,
                               round=rnd, engine=engine)
        return new_state, metrics

    return round_step


def shard_train_state(state: TrainState, mesh, plan, *, dcfg=None):
    """Place a flat-engine ``TrainState`` for ``make_sharded_round_step``:
    the (R, n) view under the flat-view rule (`launch.mesh.
    flat_view_sharding`), optimizer state over the worker axes, scalars
    replicated. The overlap snapshot defaults to replicated rows (what
    staleness-1 consumes); pass the run's ``dcfg`` so a doublebuf
    snapshot is placed ROW-SHARDED up front — the round emits it
    row-sharded, and a mismatched initial placement costs one silent
    recompile at round 1 (jit's cache keys include input shardings)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import flat_col_entry, flat_view_sharding

    if state.engine is None:
        raise ValueError("shard_train_state requires a flat-engine "
                         "TrainState (DPPFConfig.engine='flat')")
    row_e = _axis_entry(tuple(plan.worker_axes))

    def put(leaf, spec):
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    def opt_put(leaf):
        nd = jnp.ndim(leaf)
        return put(leaf, P(*([row_e] + [None] * (nd - 1))) if nd else P())

    params = jax.device_put(
        state.params, flat_view_sharding(mesh, state.params.shape, plan))
    snap = state.snap
    if snap is not None:
        col_e = flat_col_entry(mesh, snap["x"].shape[-1], plan)
        if snap["x"].ndim == 3 or \
                getattr(dcfg, "overlap", None) == "doublebuf":
            # doublebuf / the staleness_k ring (3-D snap): worker rows
            # sharded like the live view (aux rows keep the flat-view
            # fallback: replicated when they break divisibility)
            x = jax.device_put(
                snap["x"], flat_view_sharding(mesh, snap["x"].shape, plan))
        else:
            x = put(snap["x"], P(None, col_e))
        snap = dict({key: put(v, P()) for key, v in snap.items()
                     if key != "x"}, x=x)
    rnd = put(state.round, P()) if state.round is not None else None
    cstate = state.cstate
    if cstate:
        # method aux state (e.g. the LPF filtered-gradient EMA): 2-D
        # (M, n) slabs shard like replicated-row snapshots, scalars/
        # vectors replicate
        cstate = {
            key: put(v, P(None, flat_col_entry(mesh, v.shape[-1], plan))
                     if jnp.ndim(v) == 2 else P())
            for key, v in cstate.items()}
    return TrainState(params=params, opt=jax.tree.map(opt_put, state.opt),
                      cstate=cstate, t=put(state.t, P()), snap=snap,
                      round=rnd, engine=state.engine)


def make_ddp_step(loss_fn, opt: Optimizer, *,
                  clock: Optional[RoundClock] = None,
                  base_lr: Optional[float] = None,
                  total_steps: Optional[int] = None, warmup: int = 0,
                  sam_rho: float = 0.0):
    """DDP baseline: one replica; per-worker micro-grads are averaged every
    step (lowers to the per-step all-reduce on the mesh). Batch leading dim
    is M (the worker/data axis). The LR position comes from the same
    ``RoundClock`` the round builders use (tau is irrelevant here — DDP is
    the tau=1-per-step clock)."""
    if clock is None:
        if base_lr is None or total_steps is None:
            raise ValueError("make_ddp_step needs a RoundClock (clock=...) "
                             "or the legacy base_lr/total_steps pair")
        clock = RoundClock(total_steps=total_steps, tau=1, base_lr=base_lr,
                           warmup=warmup)

    def loss_scoped(p, b):
        with jax.named_scope("dppf.model"):
            return loss_fn(p, b)

    def step(state: TrainState, batch):
        def per_worker(b):
            if sam_rho > 0:
                (loss, _), g = sam_gradient(loss_scoped, state.params, b,
                                            sam_rho)
            else:
                (loss, _), g = jax.value_and_grad(loss_scoped, has_aux=True)(
                    state.params, b)
            return loss, g

        losses, grads = jax.vmap(per_worker)(batch)
        with jax.named_scope("dppf.update"):
            g = jax.tree.map(
                lambda a: jnp.mean(a.astype(jnp.float32), axis=0), grads)
            lr = clock.lr_at(state.t)
            params, opt_st = opt.step(state.params, g, state.opt, lr)
        new_state = TrainState(params=params, opt=opt_st, cstate=state.cstate,
                               t=state.t + 1)
        # the unified round-metrics schema (consensus.py::_metrics + the
        # trainer keys), so per-round loggers see one stable dict from
        # every branch; DDP's single replica has no worker spread and no
        # stale consensus — the consensus fields are true zeros
        zero = jnp.float32(0.0)
        return new_state, {"train_loss": losses.mean(),
                           "consensus_dist": zero, "pre_dist": zero,
                           "pull_force": zero, "push_force": zero,
                           "lam_t": zero, "staleness": jnp.int32(0)}

    return step


def stacked_params(state: TrainState):
    """The worker-stacked parameter pytree, whichever engine holds it."""
    if state.engine is not None:
        return state.engine.unflatten(state.params)
    return state.params


def average_params(state: TrainState):
    """Final returned model: the worker average (Alg. 1 last line).
    fp32 leaves on every engine (the tree path's tree_mean0 is fp32)."""
    if state.engine is not None:
        return state.engine.unflatten_row(
            jnp.mean(state.engine.workers(state.params), axis=0), cast=False)
    if jax.tree.leaves(state.params)[0].ndim == 0:
        return state.params
    from repro.core import pullpush as pp
    return pp.tree_mean0(state.params)

"""Soft-consensus family (paper §3 Alg. 1, §7.1) and their DPPF couplings.

Every method produces a consensus target x_C; the round update is
    pull:  x_m <- (1-alpha) x_m + alpha x_C
    push:  x_m <- x_m + lam (x_m - x_A)/||x_m - x_A||        (if DPPF)
For simple_avg + push the two fuse into Eq. 5 (pullpush.pullpush).

Methods are DATA: ``repro.core.methods`` registers a ``MethodSpec`` per
method (target-weight rule, aux-row contract, coefficient flags, input
needs) and this module lowers any spec to generic engine stages — there
is no per-method branch here.  ``methods.method_names()`` lists the zoo
(simple_avg/dppf, hard, easgd, lsgd, mgrawa/grawa, ddp, parle, lpf_sgd,
entropy_sgd); DESIGN.md §Method-registry documents the schema.

``apply_round`` is the single entry point. With ``engine=None`` it runs the
stacked-pytree reference path (the parity oracle); with a
``repro.core.engine.ConsensusEngine`` it lowers the method to a short list
of (target-weights, coefficient) stages over the persistent flat view — the
production hot path (DESIGN.md §Consensus-engine). Both paths emit the SAME
metrics pytree from every branch (stable under ``lax.scan``/loggers):
``consensus_dist``, ``pre_dist``, ``pull_force``, ``push_force``.

The flat lowering also runs under a mapped axis (``jax.shard_map``): with
``engine.shard`` set, ``params`` is the full-R-row LOCAL column shard
``(R, n_local)`` and the stages' column contractions psum over the shard's
column axes inside the engine. The lowering itself is shard-oblivious —
target weights, coefficients, and the (R, R) mixing are replicated math —
but ``losses``/``grad_norms`` must then be the GLOBAL (M,) vectors
(all-gathered over the worker axes by the sharded trainer), since lsgd's
argmin and mgrawa's weights are fleet-wide reductions
(DESIGN.md §Sharded-execution).

Remark 1 (paper): DPPF_lsgd with push away from x_A does NOT converge; the
documented fix pushes away from the leader instead (push_from="leader").
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import methods as _methods
from repro.core import pullpush as pp
from repro.core.methods import get_method

# canonical methods with a tree reference path (parity-test surface);
# lpf_sgd is flat-engine-only and excluded by construction
METHODS = _methods.tree_method_names()

EASGD_BETA = _methods.EASGD_BETA   # re-export (pre-registry callers)


def init_state(method, stacked, *, engine=None):
    """Per-method consensus state. With a flat engine, row-shaped state
    (easgd/parle centers) lives in the flat buffer's aux rows instead;
    LPF-SGD's filtered gradient is a worker-shaped EMA buffer that rides
    in ``TrainState.cstate`` either way."""
    spec = get_method(method)
    if engine is not None:
        if spec.filter_mu:
            L = engine.layout
            return {"g_ema": jnp.zeros((L.M, L.width), jnp.float32)}
        return {}
    if spec.center_beta:
        return {"center": pp.tree_mean0(stacked)}
    return {}


def consensus_target(method, stacked, state, *, losses=None, grad_norms=None,
                     easgd_beta=None):
    """Returns (x_C tree [no worker dim] or stacked, new_state, leader_idx).
    ``easgd_beta`` overrides the spec's center step (legacy knob)."""
    spec = get_method(method)
    if spec.tree_target is None:
        raise ValueError(method)
    if easgd_beta is not None and easgd_beta != spec.center_beta:
        spec = dataclasses.replace(spec, center_beta=easgd_beta)
    return spec.tree_target(spec, stacked, state, losses=losses,
                            grad_norms=grad_norms)


def _metrics(consensus_dist, pre_dist, pull_force, push_force):
    """The ONE metrics schema every branch of every path emits."""
    return {
        "consensus_dist": jnp.asarray(consensus_dist, jnp.float32),
        "pre_dist": jnp.asarray(pre_dist, jnp.float32),
        "pull_force": jnp.asarray(pull_force, jnp.float32),
        "push_force": jnp.asarray(push_force, jnp.float32),
    }


def _pull_coef(spec, dcfg, lam_t, pull_scale):
    """The effective pull coefficient: alpha, hard-pulled to 1, ramped by
    the replica-coupling schedule (Parle: lam_t / lam), and scaled by the
    clock's inner/outer plan (Entropy-SGD sub-rounds). Exact alpha for
    every spec without ramp/scale (x * 1.0 is IEEE-exact)."""
    pull = 1.0 if spec.hard_pull else dcfg.alpha
    if spec.pull_ramp and dcfg.lam > 0:
        pull = pull * (lam_t / dcfg.lam)
    return pull * pull_scale


def apply_round(params, dcfg, lam_t, state, *, losses=None, grad_norms=None,
                push_from="average", engine=None, first_gram=None, mask=None,
                push_vec=None, pull_scale=1.0):
    """One communication round. Returns (params, state, metrics).

    ``params`` is a worker-stacked pytree (tree path) or the engine's flat
    ``(R, n)`` view (flat path). Metrics keys are identical either way.
    ``first_gram`` (flat path only) is a precomputed column contraction
    for the FIRST stage — the summed ``engine.stage_comm`` chunks the
    double-buffered overlap dispatches mid-scan; the stage then runs its
    coefficient math + mixing only (DESIGN.md §Overlap). ``mask`` (flat
    path only) is the elastic participation vector ``(M,)`` — inactive
    worker rows drop out of every target-weight combination AND have their
    pull/push coefficients zeroed, so their rows pass through the mixing
    bit-exactly unchanged (DESIGN.md §Overlap, elastic membership).
    ``push_vec`` (flat path only) is the per-worker push direction field
    ``(M, n[_local])`` for specs with ``push_source="filtered_grad"``
    (LPF-SGD's EMA gradient). ``pull_scale`` scales the pull coefficient
    (the RoundClock's inner/outer plan; 1.0 = exact no-op).
    """
    if engine is not None:
        return _apply_round_flat(engine, params, dcfg, lam_t, state,
                                 losses=losses, grad_norms=grad_norms,
                                 push_from=push_from, first_gram=first_gram,
                                 mask=mask, push_vec=push_vec,
                                 pull_scale=pull_scale)
    if first_gram is not None:
        raise ValueError("first_gram requires the flat engine")
    if mask is not None:
        raise ValueError("elastic mask requires the flat engine")
    if push_vec is not None:
        raise ValueError("push_vec requires the flat engine")
    return _apply_round_tree(params, dcfg, lam_t, state, losses=losses,
                             grad_norms=grad_norms, push_from=push_from,
                             pull_scale=pull_scale)


# ---------------------------------------------------------------------------
# Reference path: stacked pytrees (the flat engine's parity oracle)
# ---------------------------------------------------------------------------

def _apply_round_tree(stacked, dcfg, lam_t, state, *, losses, grad_norms,
                      push_from, pull_scale=1.0):
    spec = get_method(dcfg.consensus)
    pull = _pull_coef(spec, dcfg, lam_t, pull_scale)
    push = dcfg.push and spec.pushes

    if not spec.communicates:               # ddp: metrics only
        r = pp.worker_dists(stacked).mean()
        return stacked, state, _metrics(r, r, 0.0, 0.0)

    if spec.fuse_eq5 and push and not dcfg.exact_second_term \
            and push_from == "average":
        new, metrics = pp.pullpush(stacked, pull, lam_t, dcfg.eps)
        return new, state, _metrics(**{k: metrics[k] for k in (
            "consensus_dist", "pre_dist", "pull_force", "push_force")})

    target, state, leader_idx = consensus_target(
        dcfg.consensus, stacked, state, losses=losses, grad_norms=grad_norms)
    pre = jnp.mean(pp.worker_dists(stacked))
    new = pp.pull_only(stacked, target, pull)

    if push:
        if dcfg.exact_second_term:
            new = pp.exact_push(new, lam_t * pp.worker_dists(new).shape[0],
                                dcfg.eps)
        elif push_from == "leader" and leader_idx is not None:
            leader = jax.tree.map(lambda a: a.astype(jnp.float32)[leader_idx],
                                  new)
            new = pp.push_only(new, lam_t, center=leader, eps=dcfg.eps)
        else:
            new = pp.push_only(new, lam_t, eps=dcfg.eps)
    post = jnp.mean(pp.worker_dists(new))
    return new, state, _metrics(post, pre, pull * pre,
                                lam_t if push else 0.0)


# ---------------------------------------------------------------------------
# Flat path: generic MethodSpec -> (target-weights, c0, c1) stage lowering
# ---------------------------------------------------------------------------

def as_participation_mask(mask, n_workers):
    """The membership-provider contract: canonicalize a provider's output
    (heartbeat table, chaos schedule, ``--elastic-drop`` window — anything
    that decides per-round who is in) to the ``(n_workers,)`` float32
    participation vector the masked lowering consumes: entry m is 1.0 when
    worker row m takes part in this round's consensus, 0.0 when it is out.
    Raises ``ValueError`` (never assert — survives ``python -O``) on a
    wrong shape, so a provider bug fails loudly at the boundary instead of
    broadcasting into the mixing stages."""
    act = jnp.asarray(mask, jnp.float32)
    if act.ndim != 1 or act.shape[0] != int(n_workers):
        raise ValueError(
            f"participation mask shape {act.shape} != ({int(n_workers)},) "
            "(one entry per worker row)")
    return act


def lower_stages(engine, dcfg, lam_t, *, losses=None, grad_norms=None,
                 push_from="average", mask=None, pull_scale=1.0):
    """Lower a consensus method's ``MethodSpec`` to its flat-engine stages.

    Returns ``(stages, pull)`` with each stage ``("coef", T, c0, c1)`` (a
    fused target-weight + coefficient mixing stage), ``("exact", lam_r)``
    (the Appendix E.1 two-term push) or ``("vec", cvec)`` (push along the
    external direction field — LPF-SGD's filtered gradient, executed by
    ``engine.vec_stage``). An empty list means no consensus stage (ddp,
    metrics only); ``pull`` is the effective pull coefficient (the
    ``pull_force`` metric). Public so the double-buffered trainer can read
    stage 1's target weights BEFORE the scan — the mid-scan ``stage_comm``
    chunks need T1 — and then execute the identical list via
    ``apply_round(..., first_gram=...)`` (the lowering is a pure function
    of its inputs, so lowering twice is free trace-time work).

    The per-method semantics all come from the spec:

    * ``spec.weight_fn(ctx)`` produces the row-stochastic worker
      combination w (mask semantics INSIDE the rule — the ctx carries the
      active mask and the pre-masked uniform);
    * ``spec.center_beta`` turns w into the elastic-center target
      ``beta * w + (1 - beta) * e_center`` with the aux row adopting it at
      ``spec.aux_pull`` (EASGD/Parle: center update and worker pull are
      ONE mixing stage);
    * ``spec.fuse_eq5`` fuses pull+push into one Eq. 5 stage;
    * the push stage targets the spec's leader, the Appendix E.1 exact
      form, the filtered-gradient field, or the uniform mean.

    ``mask`` is the elastic participation vector ``(M,)`` (1 = active):
    the row-stochastic target weights renormalize over ACTIVE rows only
    and every coefficient vector's inactive worker entries are zeroed, so
    an inactive row neither contributes to nor receives the consensus —
    its flat-view row passes through each mixing stage bit-exactly.
    """
    spec = get_method(dcfg.consensus)
    pull = _pull_coef(spec, dcfg, lam_t, pull_scale)
    push = dcfg.push and spec.pushes
    L = engine.layout
    M, R = L.M, L.R
    eye = np.eye(R, dtype=np.float32)        # host constants: R is static
    u = engine.uniform                       # (R,) worker mean weights
    zeros = jnp.zeros((R,), jnp.float32)
    act = gate = None
    if mask is not None:
        act = as_participation_mask(mask, M)             # (M,) 1 = active
        mfull = zeros.at[:M].set(act)
        # masked uniform: the worker mean over active rows only
        u = mfull / jnp.maximum(jnp.sum(mfull), 1.0)
        # coefficient gate: inactive worker rows get zero pull/push; aux
        # rows participate while ANY worker row is active (the elastic
        # center keeps tracking the live fleet) but freeze with the fleet
        # when everyone is out — an all-zero mask must make every mixing
        # stage a bit-exact pass-through, not shrink the center toward 0
        aux_on = (jnp.sum(act) > 0).astype(jnp.float32)
        gate = (aux_on * jnp.ones((R,), jnp.float32)).at[:M].set(act)

    def worker_T(w):
        """All worker rows target the combination w; aux rows stay put."""
        T = jnp.broadcast_to(w, (R, R))
        if L.aux:
            T = jnp.concatenate([T[:M], eye[M:]], axis=0)
        return T

    # ---- spec -> stage list -----------------------------------------------
    stages = []      # ("coef", T, c0, c1) | ("exact", lam_r) | ("vec", cvec)
    if spec.communicates:
        if spec.needs_losses and losses is None:
            # ValueError, not assert: user-facing path, must survive -O
            raise ValueError(f"{spec.name} needs per-worker losses")
        if spec.needs_grad_norms and grad_norms is None:
            raise ValueError(f"{spec.name} needs grad norms")
        w = spec.weight_fn(_methods.WeightCtx(
            M=M, R=R, eye=eye, u=u, zeros=zeros, act=act, losses=losses,
            grad_norms=grad_norms))
        c_pull = zeros.at[:M].set(pull)
        if spec.fuse_eq5 and push and not dcfg.exact_second_term \
                and push_from == "average":
            # Eq. 5: pull and push share the x_A target -> ONE fused stage
            stages.append(("coef", worker_T(w), c_pull,
                           zeros.at[:M].set(-lam_t)))
        else:
            if spec.center_beta:
                # every row targets z' = beta (w.x) + (1-beta) z; the aux
                # row adopts it at aux_pull — the center update and the
                # worker pull are ONE mixing stage
                w_z = spec.center_beta * w \
                    + (1.0 - spec.center_beta) * eye[M]
                T1 = jnp.broadcast_to(w_z, (R, R))
                c_pull = c_pull.at[M:].set(spec.aux_pull)
            else:
                T1 = worker_T(w)
            stages.append(("coef", T1, c_pull, zeros))
            if push:
                if spec.push_source == "filtered_grad":
                    stages.append(("vec", zeros.at[:M].set(-lam_t)))
                elif dcfg.exact_second_term:
                    stages.append(("exact", lam_t * M))
                elif push_from == "leader" and spec.leader:
                    stages.append(("coef", worker_T(w), zeros,
                                   zeros.at[:M].set(-lam_t)))
                else:
                    stages.append(("coef", worker_T(u), zeros,
                                   zeros.at[:M].set(-lam_t)))
    if gate is not None:
        if any(s[0] == "exact" for s in stages):
            raise ValueError("elastic mask does not support "
                             "exact_second_term stages")
        gated = []
        for s in stages:
            if s[0] == "coef":
                _, T, c0, c1 = s
                gated.append(("coef", T, c0 * gate, c1 * gate))
            else:                            # ("vec", cvec)
                gated.append(("vec", s[1] * gate))
        stages = gated
    return stages, pull


def _apply_round_flat(engine, flat, dcfg, lam_t, state, *, losses, grad_norms,
                      push_from, first_gram=None, mask=None, push_vec=None,
                      pull_scale=1.0):
    spec = get_method(dcfg.consensus)
    if engine.eps != dcfg.eps:
        # the engine's norm guard must match the config's (tree-path parity)
        engine = dataclasses.replace(engine, eps=dcfg.eps)
    stages, pull = lower_stages(engine, dcfg, lam_t, losses=losses,
                                grad_norms=grad_norms, push_from=push_from,
                                mask=mask, pull_scale=pull_scale)
    if first_gram is not None and (not stages or stages[0][0] != "coef"):
        raise ValueError("first_gram requires a leading coefficient stage "
                         "(every communicating lowering has one)")
    if any(s[0] == "vec" for s in stages) and push_vec is None:
        raise ValueError(f"{spec.name} needs push_vec (the filtered-"
                         f"gradient field) on the flat path")

    # ---- execute stages; each returns its own exact pre/post metrics ------
    # only stage 1's contraction can be precomputed: later stages contract
    # the PREVIOUS stage's output, which does not exist until the boundary
    pre = post = None
    for i, stage in enumerate(stages):
        if stage[0] == "coef":
            _, T, c0, c1 = stage
            flat, _, s_pre, s_post = engine.stage(
                flat, T, c0, c1, gram=first_gram if i == 0 else None)
        elif stage[0] == "vec":
            _, cvec = stage
            flat, _, s_pre, s_post = engine.vec_stage(flat, push_vec, cvec)
        else:
            _, lam_r = stage
            flat, _, s_pre, s_post = engine.exact_stage(flat, lam_r)
        pre = s_pre if pre is None else pre
        post = s_post

    if post is None:                        # no consensus stage: metrics only
        pre = jnp.mean(engine.dists_to_mean(flat))
        return flat, state, _metrics(pre, pre, 0.0, 0.0)

    push = dcfg.push and spec.pushes
    return flat, state, _metrics(
        post, pre, pull * pre, lam_t if push else 0.0)

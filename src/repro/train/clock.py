"""RoundClock: the single source of truth for step/round accounting.

The paper's communication-efficiency axis is the round clock — how often
workers synchronize (tau) and how hard they push (lam_t, §C.2), with §7.2
adapting tau to the LR via the Quadratic Synchronization Rule (Gu et al.
2024). Before this module, each callsite kept its own fragment of that
clock and each fragment was subtly wrong:

* the round builders derived ``round_idx = t // tau`` AFTER the scan had
  advanced ``t``, so ``lam_schedule`` never evaluated at round 0 and the
  whole "increasing" trajectory (the paper's main-results default) ran one
  round early;
* ``launch/train.py`` iterated ``steps // tau`` rounds, silently dropping
  the ``steps % tau`` remainder;
* ``schedules.qsr_tau`` was dead code reachable only from its unit test.

The ``RoundClock`` precomputes the ENTIRE round plan host-side at
construction — a tuple of ``RoundSpec(index, start, tau)`` covering every
one of ``total_steps`` steps (the final round absorbs the remainder; with
``tau_schedule="qsr"`` each round's tau comes from the cosine LR at the
round's first step) — and owns the two traced-compatible schedule reads:

* ``lam_at(round_idx)``: lam_t for the round ABOUT TO RUN, evaluated over
  ``total_rounds - 1`` so round 0 sees ``lam_schedule(·, 0, ·)`` (zero for
  "increasing") and the final round sees the full ``lam``;
* ``lr_at(t)``: the cosine LR at global step ``t``.

Drivers (``launch/train.py``, ``benchmarks/common.run_distributed``)
iterate ``clock.rounds`` and cut each round's batch to ``spec.tau`` steps
seeded by ``spec.start`` (the GLOBAL step — adaptive runs replay the same
data stream as fixed-tau runs over the same step budget). A tau change
between rounds changes the batch's leading dim, so ``jax.jit``'s
shape-keyed cache IS the per-tau compiled-step cache — no extra machinery.
The clock position (``TrainState.round``) persists through
``checkpoint/io.py`` save/resume. See DESIGN.md §Round-clock.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from functools import cached_property
from typing import Tuple

from repro.core.schedules import cosine_lr, lam_schedule, qsr_tau

TAU_SCHEDULES = ("fixed", "qsr")
OVERLAP_MODES = ("none", "staleness1", "doublebuf", "staleness_k")


@dataclass(frozen=True)
class RoundSpec:
    """One communication round of the plan (host ints, known up front)."""
    index: int      # 0-based round index
    start: int      # GLOBAL step of the round's first local step
    tau: int        # local steps this round (>= 1; the last round may be
                    # shorter — the remainder is run, never dropped)
    # inner/outer plan (Entropy-SGD): "inner" sub-rounds apply the weak
    # ``inner_pull``-scaled pull (local-entropy exploration), the final
    # "outer" piece of each base round applies the full pull. Plans
    # without an inner loop are all-"outer".
    scope: str = "outer"

    @property
    def stop(self) -> int:
        """Global step after the round (== next round's ``start``)."""
        return self.start + self.tau


def _host_cosine_lr(base_lr: float, t: int, total: int, warmup: int) -> float:
    """Pure-python twin of ``schedules.cosine_lr`` for the host-side round
    plan (no jnp dispatch per round; the traced reads go through
    ``lr_at``)."""
    if t < warmup:
        return base_lr * t / max(warmup, 1)
    frac = min(max((t - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return base_lr / 2.0 * (1.0 + math.cos(frac * math.pi))


@dataclass(frozen=True)
class RoundClock:
    """Step/round accounting for one training run (hashable, host-side).

    ``rounds`` is derived lazily (cached on first read — DDP drivers only
    touch ``lr_at`` and never pay for a plan) and covers exactly
    ``total_steps`` steps. ``lam_at``/``lr_at`` accept traced scalars and
    are the ONLY schedule reads the round builders perform.
    """
    total_steps: int
    tau: int                         # base communication period
    base_lr: float = 0.0
    warmup: int = 0
    lam: float = 0.0
    lam_kind: str = "increasing"     # fixed | increasing | decreasing (§C.2)
    tau_schedule: str = "fixed"      # fixed | qsr (§7.2)
    qsr_beta: float = 0.0            # QSR: tau_t = max(tau, floor((beta/eta)^2))
    # overlap-aware QSR: with a stale consensus ("staleness1"/"doublebuf"/
    # "staleness_k", DESIGN.md §Overlap) round r applies the consensus of
    # round r-k's iterate (k = ``staleness_depth``), so the QSR period of
    # round r is sized from the LR of the round-(r-k) start — the stale LR
    # — keeping sync frequency matched to the iterate actually being
    # synchronized. The plan stays a host-side pure function of the config
    # (static-shaped rounds).
    overlap: str = "none"
    # pipeline depth k of overlap="staleness_k" (ignored by the other
    # modes, whose depth is fixed at 1)
    staleness: int = 1
    # inner/outer plan (Entropy-SGD, from the MethodSpec registry):
    # inner_rounds > 1 splits every base round into that many sub-rounds;
    # the non-final pieces are "inner" and scale the pull coefficient by
    # inner_pull (``pull_scale_at``), the final piece is the full-pull
    # "outer" round. 0/1 = no inner loop (every round "outer").
    inner_rounds: int = 0
    inner_pull: float = 1.0

    def __post_init__(self):
        # ValueError, not assert: these guard user-facing config plumbing
        # and must survive ``python -O``
        if self.total_steps < 1:
            raise ValueError(f"total_steps must be >= 1, got {self.total_steps}")
        if self.tau < 1:
            raise ValueError(f"tau must be >= 1, got {self.tau}")
        if self.tau_schedule not in TAU_SCHEDULES:
            raise ValueError(f"unknown tau schedule {self.tau_schedule!r} "
                             f"(expected one of {TAU_SCHEDULES})")
        if self.tau_schedule == "qsr":
            if self.qsr_beta <= 0:
                raise ValueError("tau_schedule='qsr' needs qsr_beta > 0")
            if self.base_lr <= 0:
                raise ValueError("tau_schedule='qsr' adapts tau to the "
                                 "cosine LR and needs base_lr > 0")
        if self.overlap not in OVERLAP_MODES:
            raise ValueError(f"unknown overlap mode {self.overlap!r} "
                             f"(expected one of {OVERLAP_MODES})")
        if self.warmup < 0:
            raise ValueError(f"warmup must be >= 0, got {self.warmup}")
        if self.staleness < 1:
            raise ValueError(f"staleness must be >= 1, got {self.staleness}")
        if self.inner_rounds < 0:
            raise ValueError(f"inner_rounds must be >= 0, got "
                             f"{self.inner_rounds}")
        if not 0.0 < self.inner_pull <= 1.0:
            raise ValueError(f"inner_pull must be in (0, 1], got "
                             f"{self.inner_pull}")
        if self.overlap == "staleness_k" and self.warmup > 0 and \
                math.ceil(self.warmup / self.tau) < self.staleness:
            # the first k rounds are exact-consensus pipeline fill; a
            # warmup shorter than k rounds would end mid-fill, so the
            # stale-LR QSR reads would straddle the warmup boundary
            raise ValueError(
                f"overlap='staleness_k' needs warmup >= k rounds so the "
                f"pipeline fill never straddles the warmup boundary: "
                f"warmup={self.warmup} steps covers "
                f"{math.ceil(self.warmup / self.tau)} rounds at tau="
                f"{self.tau} but staleness k={self.staleness} (use "
                f"warmup=0 or warmup >= {self.staleness * self.tau})")

    @classmethod
    def from_config(cls, dcfg, *, base_lr: float, total_steps: int,
                    warmup: int = 0) -> "RoundClock":
        """Build the clock from a ``DPPFConfig`` + the LR triple. A config
        with ``qsr_beta > 0`` opts into QSR even if ``tau_schedule`` was
        left at "fixed" (the pre-clock opt-in convention)."""
        tau_schedule = getattr(dcfg, "tau_schedule", "fixed")
        if tau_schedule == "fixed" and dcfg.qsr_beta > 0:
            tau_schedule = "qsr"
        # the method registry owns the inner/outer plan (Entropy-SGD's
        # local-entropy loop is clock structure, not trainer code)
        from repro.core.methods import get_method
        spec = get_method(getattr(dcfg, "consensus", "simple_avg"))
        return cls(total_steps=total_steps, tau=dcfg.tau, base_lr=base_lr,
                   warmup=warmup, lam=dcfg.lam, lam_kind=dcfg.lam_schedule,
                   tau_schedule=tau_schedule, qsr_beta=dcfg.qsr_beta,
                   overlap=getattr(dcfg, "overlap", "none"),
                   staleness=getattr(dcfg, "staleness", 1),
                   inner_rounds=spec.inner_rounds,
                   inner_pull=spec.inner_pull)

    @classmethod
    def from_tune_plan(cls, plan, *, base_lr: float, total_steps: int,
                       warmup: int = 0, dcfg=None) -> "RoundClock":
        """Build the clock from an autotune ``TunePlan`` (the
        ``--autotune`` / ``--tune-plan`` path, DESIGN.md §Autotune). The
        plan pins tau to the searched point with ``tau_schedule="fixed"``
        — autotune already placed tau at the measured comm/compute
        crossover, so no schedule re-adapts it. With ``dcfg`` the plan is
        grafted onto the config via ``dcfg.apply_tune_plan`` and routed
        through ``from_config`` (keeping lam and the method registry's
        inner/outer plan); without, a bare fixed-tau clock. Accepts the
        dataclass or its ``to_dict()`` JSON form — replay through either
        is bit-identical (``tests/test_autotune.py`` pins it)."""
        if isinstance(plan, dict):
            tau = int(plan["chosen"]["tau"])
            overlap = str(plan.get("overlap", "none"))
            staleness = int(plan.get("staleness", 1))
        else:
            tau = int(plan.chosen.tau)
            overlap = plan.overlap
            staleness = int(plan.staleness)
        if dcfg is not None:
            return cls.from_config(dcfg.apply_tune_plan(plan),
                                   base_lr=base_lr, total_steps=total_steps,
                                   warmup=warmup)
        return cls(total_steps=total_steps, tau=tau, base_lr=base_lr,
                   warmup=warmup, tau_schedule="fixed", overlap=overlap,
                   staleness=staleness)

    @property
    def staleness_depth(self) -> int:
        """Pipeline depth of the overlap mode: 0 (no overlap), 1
        (staleness1/doublebuf) or k (staleness_k). Round r >= depth applies
        the consensus of round r - depth; rounds 0..depth-1 are fill."""
        if self.overlap == "none":
            return 0
        if self.overlap == "staleness_k":
            return self.staleness
        return 1

    # -- round plan ---------------------------------------------------------

    @cached_property
    def rounds(self) -> Tuple[RoundSpec, ...]:
        # cached_property writes the result straight into __dict__, which a
        # frozen dataclass permits; the plan is a pure function of the
        # (compared, hashed) config fields, so equality/hash are unaffected
        rounds, t = [], 0
        while t < self.total_steps:
            if self.tau_schedule == "qsr":
                if t < self.warmup:
                    # warmup-aware QSR: the warmup LR is tiny, so the raw
                    # rule (beta/eta)^2 would blow tau up exactly when the
                    # model changes fastest — warmup rounds keep the base
                    # tau (Gu et al. 2024 sync frequently during warmup)
                    # and never straddle the warmup boundary, so the first
                    # cosine-ruled round starts AT ``warmup``
                    tau_t = min(self.tau, self.warmup - t)
                else:
                    # overlap-aware QSR: under a stale consensus round r
                    # applies the round-(r-k) iterate (k = staleness
                    # depth), so its period is ruled by the STALE LR — the
                    # start of the round k back (fill rounds / the first
                    # post-warmup rounds have no stale predecessor and use
                    # their own LR)
                    t_lr = t
                    d = self.staleness_depth
                    if d >= 1 and len(rounds) >= d and \
                            rounds[-d].start >= self.warmup:
                        t_lr = rounds[-d].start
                    eta = _host_cosine_lr(self.base_lr, t_lr,
                                          self.total_steps, self.warmup)
                    tau_t = qsr_tau(eta, self.tau, self.qsr_beta)
            else:
                tau_t = self.tau
            tau_t = min(tau_t, self.total_steps - t)   # never drop remainder
            for piece, scope in self._split_inner(tau_t):
                rounds.append(RoundSpec(index=len(rounds), start=t,
                                        tau=piece, scope=scope))
                t += piece
        return tuple(rounds)

    def _split_inner(self, tau_t: int):
        """Split one base round's tau into the inner/outer sub-round plan:
        ``inner_rounds`` near-equal pieces, all but the last "inner" (weak
        pull). A tau too short to split keeps fewer (non-empty) pieces; no
        inner loop -> the single "outer" round."""
        k = self.inner_rounds
        if k <= 1 or tau_t <= 1:
            return [(tau_t, "outer")]
        k = min(k, tau_t)
        base, rem = divmod(tau_t, k)
        pieces = [base + 1] * rem + [base] * (k - rem)
        return [(p, "inner" if i < len(pieces) - 1 else "outer")
                for i, p in enumerate(pieces)]

    @property
    def total_rounds(self) -> int:
        return len(self.rounds)

    @property
    def fixed_rounds(self) -> int:
        """Rounds (= consensus all-reduces) a fixed-tau clock would pay for
        the same step budget — the baseline for QSR's savings."""
        return math.ceil(self.total_steps / self.tau)

    def round_of_step(self, t: int) -> int:
        """Round index containing global step ``t`` (== ``total_rounds``
        when ``t == total_steps``, i.e. training finished). Used by resume
        paths to recover the clock position from a step counter alone."""
        if t < 0 or t > self.total_steps:
            raise ValueError(f"step {t} outside [0, {self.total_steps}]")
        for spec in self.rounds:
            if t < spec.stop:
                return spec.index
        return self.total_rounds

    def taus(self) -> Tuple[int, ...]:
        return tuple(spec.tau for spec in self.rounds)

    # -- traced-compatible schedule reads ------------------------------------

    def lam_at(self, round_idx):
        """Push strength for round ``round_idx`` (the round ABOUT TO RUN —
        evaluate BEFORE the scan advances t). The denominator is
        ``total_rounds - 1`` so the trajectory spans both endpoints: round
        0 sees ``lam_schedule(·, 0, ·)`` and the final round sees the full
        ``lam``. A single-round plan has no trajectory to span — its one
        round is both endpoints, and it applies the FULL lam (a zero-push
        round would silently disable the paper's push term). Accepts a
        traced scalar."""
        if self.total_rounds == 1:
            return lam_schedule("fixed", self.lam, round_idx, 1)
        return lam_schedule(self.lam_kind, self.lam, round_idx,
                            self.total_rounds - 1)

    def lr_at(self, t):
        """Cosine LR at global step ``t`` (traced ok)."""
        return cosine_lr(self.base_lr, t, self.total_steps, self.warmup)

    def pull_scale_at(self, round_idx):
        """Pull-coefficient scale of round ``round_idx`` from the
        inner/outer plan: ``inner_pull`` on "inner" sub-rounds, 1.0 on
        "outer" rounds. Plans without an inner loop return the python
        float 1.0 (an IEEE-exact no-op for every caller — the round
        builders multiply it in unconditionally). Accepts a traced scalar
        (jnp.take over the host-side plan)."""
        if self.inner_rounds <= 1:
            return 1.0
        import jax.numpy as jnp
        scales = jnp.asarray(
            tuple(self.inner_pull if r.scope == "inner" else 1.0
                  for r in self.rounds), jnp.float32)
        return jnp.take(scales, jnp.clip(round_idx, 0,
                                         self.total_rounds - 1))

    def _host_lam(self, round_idx: int) -> float:
        """Pure-python twin of ``lam_at`` for the host-side plan report."""
        T = max(self.total_rounds - 1, 1)
        if self.total_rounds == 1:
            return self.lam
        frac = min(max(round_idx / T, 0.0), 1.0)
        if self.lam_kind == "fixed":
            return self.lam
        if self.lam_kind == "decreasing":
            return self.lam / 2.0 * (1.0 + math.cos(frac * math.pi))
        if self.lam_kind == "increasing":
            return self.lam / 2.0 * (1.0 - math.cos(frac * math.pi))
        raise ValueError(self.lam_kind)

    def describe(self) -> dict:
        """Machine-readable summary + full round plan (the committed
        ``BENCH_roundclock.json`` baseline and the dry-run report's table
        both render this). ``plan`` has one row per round: index, global
        start step, tau, the lam the round applies, and the LR window
        ``[lr_start, lr_end]`` its local steps sweep (floats rounded to 6
        digits so the committed baseline compares stably across hosts).

        Worked QSR example — ``RoundClock(total_steps=64, tau=4,
        base_lr=0.3, tau_schedule="qsr", qsr_beta=0.4)``: a round starting
        at step t gets ``tau_t = max(4, floor((0.4 / eta_t)^2))`` from the
        cosine LR ``eta_t``. Early rounds keep tau=4 (eta(0) = 0.3 ->
        floor(1.77) = 1 < 4); at step 32, eta = 0.15 -> floor(7.11) = 7;
        at step 39, eta ~ 0.0995 -> 16; the round at step 55 would get a
        huge tau but is capped to the 9 remaining steps. Full plan: taus
        (4,4,4,4,4,4,4,4,7,16,9) — 11 rounds vs 16 fixed, 5 consensus
        all-reduces saved (``tests/test_clock.py`` pins exactly this
        plan)."""
        taus = self.taus()
        depth = self.staleness_depth
        inner = self.inner_rounds > 1
        plan = []
        for spec in self.rounds:
            row = {
                "round": spec.index,
                "start": spec.start,
                "tau": spec.tau,
                "lam": round(self._host_lam(spec.index), 6),
                "lr_start": round(_host_cosine_lr(
                    self.base_lr, spec.start, self.total_steps,
                    self.warmup), 6),
                "lr_end": round(_host_cosine_lr(
                    self.base_lr, spec.stop - 1, self.total_steps,
                    self.warmup), 6),
                "warmup": spec.start < self.warmup,
                # staleness depth of the consensus this round applies:
                # rounds 0..depth-1 are exact fill (0), later rounds apply
                # the round-(r-depth) snapshot (depth)
                "staleness": depth if spec.index >= depth else 0,
            }
            if inner:
                # conditional key: plans without an inner loop keep the
                # exact legacy row schema (committed BENCH baselines)
                row["scope"] = spec.scope
            plan.append(row)
        out = {
            "total_steps": self.total_steps,
            "tau_base": self.tau,
            "tau_schedule": self.tau_schedule,
            "qsr_beta": self.qsr_beta,
            "warmup": self.warmup,
            "warmup_rounds": sum(1 for r in plan if r["warmup"]),
            "overlap": self.overlap,
            "staleness": depth,
            "rounds": self.total_rounds,
            "fixed_rounds": self.fixed_rounds,
            "allreduces_saved": self.fixed_rounds - self.total_rounds,
            "tau_min": min(taus),
            "tau_max": max(taus),
            "plan": plan,
        }
        if inner:
            out["inner_rounds"] = self.inner_rounds
            out["inner_pull"] = self.inner_pull
        return out

    def plan_table(self, max_rows: int = 12) -> str:
        """The round plan as a markdown table (the dry-run report prints
        this). Long plans elide the middle, keeping the first and last
        ``max_rows // 2`` rounds."""
        d = self.describe()
        rows = d["plan"]
        extra = ""
        if d["warmup"]:
            extra += (f", warmup {d['warmup']} steps = "
                      f"{d['warmup_rounds']} rounds")
        if d["overlap"] != "none":
            extra += f", overlap {d['overlap']} (k={d['staleness']})"
            if d["tau_schedule"] == "qsr":
                extra += " (stale-LR QSR)"
        if d.get("inner_rounds"):
            extra += (f", inner/outer plan x{d['inner_rounds']} "
                      f"(inner pull {d['inner_pull']})")
        head = [f"round plan: {d['rounds']} rounds over "
                f"{d['total_steps']} steps (tau_schedule="
                f"{d['tau_schedule']}, tau {d['tau_min']}..{d['tau_max']}, "
                f"all-reduces saved vs fixed: {d['allreduces_saved']}"
                f"{extra})",
                "| round | start | tau | lam | lr window | staleness |",
                "|---|---|---|---|---|---|"]
        if len(rows) > max_rows:
            half = max(max_rows // 2, 1)
            shown = list(rows[:half]) + [None] + list(rows[-half:])
        else:
            shown = rows
        for r in shown:
            if r is None:
                head.append("| ... | | | | | |")
                continue
            tau_cell = f"{r['tau']} (warm)" if r["warmup"] else f"{r['tau']}"
            if r.get("scope") == "inner":
                tau_cell += " (inner)"
            head.append(f"| {r['round']} | {r['start']} | {tau_cell} | "
                        f"{r['lam']:.4f} | {r['lr_start']:.4f} -> "
                        f"{r['lr_end']:.4f} | {r['staleness']} |")
        return "\n".join(head)


class RoundMetricsLogger:
    """Per-round metrics hook: one JSON line per communication round.

    Drivers that iterate ``clock.rounds`` call the logger with the round's
    ``RoundSpec`` and the unified round-metrics dict every round builder
    emits (``consensus_dist``/``pre_dist``/``pull_force``/``push_force``/
    ``train_loss``/``lam_t``/``staleness`` — the ddp branch included, where
    the consensus fields are zeros and the clock is the tau=1 per-step
    clock; pass a plain step index instead of a spec there). ``staleness``
    is the integer depth of the consensus the round applied (0 = exact,
    k = the round-(r-k) snapshot). Each line carries the clock position
    (round, global start step, tau) plus the metrics, so a QSR-adaptive
    run's log is self-describing. Values are converted via ``float`` —
    call it OUTSIDE jit (on the returned metrics), never inside a traced
    function. ``launch/train.py --log-every-round PATH`` wires it; the
    supervisor's events (``recompile`` among them) arrive as rows with
    an ``event`` key.
    """

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._fh = open(path, "w")

    def __call__(self, spec, metrics: dict):
        if isinstance(spec, RoundSpec):
            row = {"round": spec.index, "start": spec.start, "tau": spec.tau}
        else:   # ddp / per-step drivers: a bare global step index
            row = {"round": int(spec), "start": int(spec), "tau": 1}
        for k, v in metrics.items():
            try:
                row[k] = float(v)
            except (TypeError, ValueError):
                row[k] = str(v)
        self._fh.write(json.dumps(row) + "\n")
        self._fh.flush()
        return row

    def close(self):
        self._fh.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

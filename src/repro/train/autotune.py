"""Self-tuning performance harness: the ``--autotune`` probe search.

The paper's communication-efficiency claims only hold at a well-chosen
operating point — per-worker batch size, ``overlap_chunks``, and tau
interact through the comm/compute crossover modeled in
``launch/roofline.py::overlap_model``. Before this module that point was
hand-picked per committed hillclimb plan file; now one flag searches it
(DESIGN.md §Autotune):

1. **Batch frontier** — power-of-two scaling probes from
   ``TuneSpace.min_batch`` double until the first OOM (or ``max_batch``),
   then a binary search refines between the largest feasible and smallest
   failed size. Failed sizes are cached and NEVER re-probed; every probe
   (feasible or not) counts against ``probe_budget`` and the search
   returns its best-so-far point when the budget runs dry.
2. **Joint sweep** — at the frontier batch, every (tau, overlap_chunks)
   pair of the ladders is probed (chunks capped by tau; modes without a
   chunk dimension collapse the ladder to ``(1,)``).
3. **Reconciled scoring** — every probe records a measured round wall
   time AND the deterministic roofline model's round time
   (``roofline.probe_round_model``). The median measured/modeled ratio
   calibrates the model to this host (``roofline.reconcile_probes``) and
   candidates are ranked by calibrated-model microseconds PER SAMPLE
   (``round_us / (tau * batch)``). A single positive scale never changes
   an argmin, so the chosen point is a deterministic function of the
   feasibility frontier — noisy host timers cannot flip it, which is what
   lets CI pin the plan structurally (``BENCH_autotune.json``).

The **OOM contract**: a probe failure is any exception whose message
carries a ``RESOURCE_EXHAUSTED`` / out-of-memory token (``is_oom``) —
exactly what jaxlib's ``XlaRuntimeError`` carries on real device OOM.
Injection therefore needs no jaxlib type: ``inject_oom_above`` (the
``--tune-oom-above`` CI hook) and the test fixture raise a plain
``RuntimeError`` with the token, and the backoff path runs without real
memory pressure. Any non-OOM exception propagates — the tuner never
swallows a real bug.

The search emits a :class:`TunePlan` — a deterministic JSON artifact
(probes tried, failures, chosen point, model-vs-measured residual scale)
consumed directly by ``DPPFConfig.apply_tune_plan`` and
``RoundClock.from_tune_plan``, replacing the committed hillclimb plan
files end to end.
"""
from __future__ import annotations

import dataclasses
import json
import time
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import repro.launch.roofline as rf

PLAN_VERSION = 1

# substrings that mark an exception as device memory exhaustion; the first
# is jaxlib XlaRuntimeError's canonical status and the injection contract
OOM_TOKENS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
              "OOM")


def is_oom(exc: BaseException) -> bool:
    """The OOM contract: does this exception mean the probe ran out of
    device memory? Matched on the MESSAGE (jaxlib raises
    ``XlaRuntimeError`` whose text starts with ``RESOURCE_EXHAUSTED`` on
    real OOM), so scripted injection works with a plain RuntimeError and
    no jaxlib import. Everything else is a real bug and must propagate."""
    text = f"{type(exc).__name__}: {exc}"
    return any(tok in text for tok in OOM_TOKENS)


@dataclass(frozen=True)
class Candidate:
    """One operating point of the joint search space."""
    batch: int            # per-worker batch size
    tau: int              # local steps per communication round
    overlap_chunks: int   # mid-scan snapshot-comm chunk count


# overlap modes whose chunk ladder is meaningful (the others dispatch no
# mid-scan chunks, so their ladder collapses to (1,))
_CHUNKED_MODES = ("doublebuf", "staleness_k")


@dataclass(frozen=True)
class TuneSpace:
    """The search space + budget. ValueError (never assert) on malformed
    spaces — these guard the user-facing ``--autotune`` flags and must
    survive ``python -O`` (tests/optcheck.py)."""
    min_batch: int = 1
    max_batch: int = 256
    taus: Tuple[int, ...] = (4, 8)
    chunks: Tuple[int, ...] = (1, 2, 4)
    probe_budget: int = 16
    overlap: str = "doublebuf"
    staleness: int = 1

    def __post_init__(self):
        if self.probe_budget < 1:
            raise ValueError(
                f"probe_budget must be >= 1, got {self.probe_budget}")
        if self.min_batch < 1:
            raise ValueError(f"min_batch must be >= 1, got {self.min_batch}")
        if self.min_batch > self.max_batch:
            raise ValueError(
                f"min_batch {self.min_batch} > max_batch {self.max_batch}")
        if not self.taus or any(t < 1 for t in self.taus):
            raise ValueError(f"taus must be a non-empty tuple of ints >= 1, "
                             f"got {self.taus!r}")
        if not self.chunks or any(c < 1 for c in self.chunks):
            raise ValueError(f"chunks must be a non-empty tuple of ints >= "
                             f"1, got {self.chunks!r}")
        # OVERLAP_MODES lives in train.clock; keep the literal in sync
        if self.overlap not in ("none", "staleness1", "doublebuf",
                                "staleness_k"):
            raise ValueError(f"unknown overlap mode {self.overlap!r}")
        if self.staleness < 1:
            raise ValueError(f"staleness must be >= 1, got {self.staleness}")

    def chunk_ladder(self) -> Tuple[int, ...]:
        """The effective chunk ladder: modes without mid-scan chunk
        dispatch have nothing to tune there."""
        if self.overlap in _CHUNKED_MODES:
            return self.chunks
        return (1,)


@dataclass(frozen=True)
class ProbeResult:
    """One probe of the search: the candidate, whether it was feasible,
    the measured round wall time (timing-class — host-relative), and the
    deterministic roofline-model round time (structural)."""
    batch: int
    tau: int
    overlap_chunks: int
    ok: bool
    us_round: float = 0.0     # measured; 0.0 for failed probes
    modeled_us: float = 0.0   # roofline.probe_round_model, pure arithmetic
    error: str = ""           # the OOM message when not ok

    @property
    def candidate(self) -> Candidate:
        return Candidate(self.batch, self.tau, self.overlap_chunks)


@dataclass(frozen=True)
class TunePlan:
    """The deterministic artifact ``--autotune`` emits and
    ``RoundClock.from_tune_plan`` / ``DPPFConfig.apply_tune_plan``
    consume. Structural fields (chosen point, probe ladder, failures,
    budget accounting, ``dominates_model``) are identical on every host
    for the same feasibility frontier; ``us_round`` / ``residual_scale``
    / ``dominates_measured`` are host-relative timing fields."""
    chosen: Candidate
    probes: Tuple[ProbeResult, ...]
    failures: Tuple[int, ...]     # batch sizes that OOMed (sorted, unique)
    probe_budget: int
    probes_used: int
    overlap: str
    staleness: int
    residual_scale: float         # median(measured / modeled) over ok probes
    dominates_model: bool         # chosen beats every ok probe, calibrated model
    dominates_measured: bool      # same under raw measured time (host-noisy)
    version: int = PLAN_VERSION

    def __post_init__(self):
        # load()-path guards: a hand-edited / wrong-version plan must fail
        # loudly, not train at a garbage operating point (-O safe)
        if self.version != PLAN_VERSION:
            raise ValueError(f"TunePlan version {self.version} != "
                             f"{PLAN_VERSION} (regenerate with --autotune)")
        if self.probe_budget < 1:
            raise ValueError(
                f"probe_budget must be >= 1, got {self.probe_budget}")
        if self.chosen.batch < 1 or self.chosen.tau < 1 \
                or self.chosen.overlap_chunks < 1:
            raise ValueError(f"malformed chosen point {self.chosen}")
        if self.overlap not in ("none", "staleness1", "doublebuf",
                                "staleness_k"):
            raise ValueError(f"unknown overlap mode {self.overlap!r}")
        if self.staleness < 1:
            raise ValueError(f"staleness must be >= 1, got {self.staleness}")

    # -- deterministic JSON -------------------------------------------------

    def to_dict(self) -> dict:
        """JSON form. Floats are rounded at the source (us to 0.1, the
        modeled/scale fields to 6 digits) so the committed
        ``BENCH_autotune.json`` compares stably across hosts and a
        load -> save round-trip is byte-identical."""
        return {
            "version": self.version,
            "chosen": {"batch": self.chosen.batch, "tau": self.chosen.tau,
                       "overlap_chunks": self.chosen.overlap_chunks},
            "overlap": self.overlap,
            "staleness": self.staleness,
            "probe_budget": self.probe_budget,
            "probes_used": self.probes_used,
            "failures": list(self.failures),
            "residual_scale": round(self.residual_scale, 6),
            "dominates_model": self.dominates_model,
            "dominates_measured": self.dominates_measured,
            "probes": [
                {"batch": p.batch, "tau": p.tau,
                 "overlap_chunks": p.overlap_chunks, "ok": p.ok,
                 "us_round": round(p.us_round, 1),
                 "modeled_us": round(p.modeled_us, 6), "error": p.error}
                for p in self.probes],
        }

    @classmethod
    def from_dict(cls, d: dict) -> "TunePlan":
        try:
            chosen = Candidate(int(d["chosen"]["batch"]),
                               int(d["chosen"]["tau"]),
                               int(d["chosen"]["overlap_chunks"]))
            probes = tuple(
                ProbeResult(int(p["batch"]), int(p["tau"]),
                            int(p["overlap_chunks"]), bool(p["ok"]),
                            float(p["us_round"]), float(p["modeled_us"]),
                            str(p.get("error", "")))
                for p in d["probes"])
            return cls(chosen=chosen, probes=probes,
                       failures=tuple(int(b) for b in d["failures"]),
                       probe_budget=int(d["probe_budget"]),
                       probes_used=int(d["probes_used"]),
                       overlap=str(d["overlap"]),
                       staleness=int(d["staleness"]),
                       residual_scale=float(d["residual_scale"]),
                       dominates_model=bool(d["dominates_model"]),
                       dominates_measured=bool(d["dominates_measured"]),
                       version=int(d.get("version", -1)))
        except (KeyError, TypeError) as e:
            raise ValueError(f"malformed TunePlan payload: {e!r}") from e

    def dumps(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True) + "\n"

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.dumps())

    @classmethod
    def load(cls, path: str) -> "TunePlan":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def per_sample_us(us: float, cand: Candidate) -> float:
    """The tuner's objective: round microseconds amortized per training
    sample (GRAWA's time-constrained framing — wall time per unit of
    optimization work, not raw round time, which would always pick the
    smallest batch)."""
    return us / (cand.tau * cand.batch)


def autotune(runner: Callable[[Candidate], float],
             model_fn: Callable[[Candidate], float],
             space: TuneSpace) -> TunePlan:
    """Run the probe search. ``runner(cand)`` returns measured round
    microseconds and raises on OOM (``is_oom`` decides — anything else
    propagates); ``model_fn(cand)`` returns the deterministic roofline
    round microseconds. Raises ValueError when even ``min_batch`` OOMs
    (there is nothing below it to back off to)."""
    probes: list = []
    tried: Dict[Candidate, ProbeResult] = {}

    def probe(cand: Candidate) -> Optional[ProbeResult]:
        if cand in tried:             # never re-run — failed sizes included
            return tried[cand]
        if len(tried) >= space.probe_budget:
            return None               # budget exhausted: best-so-far wins
        modeled = float(model_fn(cand))
        try:
            res = ProbeResult(cand.batch, cand.tau, cand.overlap_chunks,
                              ok=True, us_round=float(runner(cand)),
                              modeled_us=modeled)
        except Exception as e:        # noqa: BLE001 — filtered by is_oom
            if not is_oom(e):
                raise
            res = ProbeResult(cand.batch, cand.tau, cand.overlap_chunks,
                              ok=False, modeled_us=modeled,
                              error=str(e)[:200])
        tried[cand] = res
        probes.append(res)
        return res

    # -- phase 1: power-of-two batch ladder at the base (tau, chunks) point
    base_tau, base_ch = space.taus[0], space.chunk_ladder()[0]
    b, best, first_fail = space.min_batch, 0, None
    while True:
        res = probe(Candidate(b, base_tau, base_ch))
        if res is None:
            break
        if res.ok:
            best = b
            if b >= space.max_batch:
                break
            b = min(b * 2, space.max_batch)
        else:
            first_fail = b
            break
    if best == 0:
        raise ValueError(
            f"autotune: no feasible batch — min_batch={space.min_batch} "
            f"already OOMs ({probes[-1].error if probes else 'no probe ran'}"
            f"); lower min_batch or shrink the model")

    # -- phase 2: binary refinement between largest-ok and smallest-failed.
    # Midpoints are strictly inside (lo, hi), so no tried size repeats.
    lo, hi = best, first_fail
    while hi is not None and hi - lo > 1:
        res = probe(Candidate((lo + hi) // 2, base_tau, base_ch))
        if res is None:
            break
        if res.ok:
            lo = res.batch
        else:
            hi = res.batch
    best_batch = lo

    # -- phase 3: joint (tau, chunks) sweep at the frontier batch (the base
    # point is already cached; chunk counts beyond tau cannot interleave)
    for tau in space.taus:
        for ch in space.chunk_ladder():
            if ch > tau:
                continue
            probe(Candidate(best_batch, tau, ch))

    # -- reconcile + select
    ok_probes = [p for p in probes if p.ok]
    rec = rf.reconcile_probes(
        (p.us_round, p.modeled_us) for p in ok_probes)
    scale = rec["scale"]

    def model_score(p: ProbeResult) -> float:
        return per_sample_us(p.modeled_us * scale, p.candidate)

    # candidates = the joint sweep's feasible probes at the frontier batch;
    # ties (chunking never changes the modeled payload) break to the
    # smallest tau, then fewest chunks — fully deterministic
    cands = [p for p in ok_probes if p.batch == best_batch]
    chosen_p = min(cands, key=lambda p: (model_score(p), p.tau,
                                         p.overlap_chunks))
    dominates_model = all(model_score(chosen_p) <= model_score(p)
                          for p in ok_probes)
    meas = lambda p: per_sample_us(p.us_round, p.candidate)
    dominates_measured = all(meas(chosen_p) <= meas(p) for p in ok_probes)

    return TunePlan(
        chosen=chosen_p.candidate, probes=tuple(probes),
        failures=tuple(sorted({p.batch for p in probes if not p.ok})),
        probe_budget=space.probe_budget, probes_used=len(tried),
        overlap=space.overlap, staleness=space.staleness,
        residual_scale=scale, dominates_model=dominates_model,
        dominates_measured=dominates_measured)


# ---------------------------------------------------------------------------
# probe runners
# ---------------------------------------------------------------------------

def inject_oom_above(runner: Callable[[Candidate], float],
                     max_ok_batch: int) -> Callable[[Candidate], float]:
    """Fault-injection hook (the ``--tune-oom-above`` CI leg): wrap a
    probe runner so any candidate with ``batch > max_ok_batch`` raises a
    scripted RESOURCE_EXHAUSTED BEFORE touching the device — the backoff
    path runs with zero real memory pressure and a deterministic
    frontier."""
    if max_ok_batch < 1:
        raise ValueError(
            f"injected OOM frontier must be >= 1, got {max_ok_batch}")

    def run(cand: Candidate) -> float:
        if cand.batch > max_ok_batch:
            raise RuntimeError(
                f"RESOURCE_EXHAUSTED: injected OOM at batch={cand.batch} "
                f"(frontier {max_ok_batch})")
        return runner(cand)
    return run


def make_round_probe_runner(init_fn, loss_fn, opt, dcfg, workers: int,
                            batch_fn, *, base_lr: float = 0.05,
                            total_steps: int = 100, reps: int = 2,
                            seed: int = 0):
    """The measured probe runner on the REAL round step (the same
    ``make_round_step`` the training loop runs): per candidate, swap the
    candidate's tau/overlap_chunks into ``dcfg``, init a fresh fleet, jit
    one donated round, warm twice (the second warm catches steady-state
    resharding recompiles — the ``_time_donated`` convention), and return
    the mean of ``reps`` timed rounds in microseconds.
    ``batch_fn(cand)`` builds the (tau, M, batch, ...) round batch. A
    real device OOM escapes jit as ``XlaRuntimeError`` and is caught by
    the search's ``is_oom``."""
    import jax
    from repro.train.trainer import init_train_state, make_round_step

    def run(cand: Candidate) -> float:
        dc = dataclasses.replace(dcfg, tau=cand.tau,
                                 overlap_chunks=cand.overlap_chunks)
        st = init_train_state(init_fn, opt, dc, workers,
                              jax.random.PRNGKey(seed))
        step = jax.jit(make_round_step(loss_fn, opt, dc, base_lr=base_lr,
                                       total_steps=total_steps),
                       donate_argnums=0)
        b = batch_fn(cand)
        for _ in range(2):                      # compile + steady-state warm
            st, _ = step(st, b)
            jax.block_until_ready(st.params)
        t0 = time.perf_counter()
        for _ in range(reps):
            st, _ = step(st, b)
        jax.block_until_ready(st.params)
        return (time.perf_counter() - t0) / reps * 1e6
    return run


def make_lm_model_fn(*, n_params: int, seq: int, workers: int,
                     overlap: str, device_kind: str, staleness: int = 1):
    """The roofline ``model_fn`` for the training CLI on ``device_kind``
    (``launch.roofline.PEAKS``): local-step work is the LM rule fwd+bwd
    ~ 6*N flops per token; the consensus payload is the flat engine's
    worker-row all-gather (R x n fp32) plus the (R, R) partial-Gram psum
    — the same accounting as ``microbench.bench_overlap_round``."""
    gather_bytes = workers * n_params * 4 + workers * workers * 4
    peak_flops = rf.peaks(device_kind)["flops"]

    def model_us(cand: Candidate) -> float:
        work_s = 6.0 * n_params * cand.batch * seq / peak_flops
        return rf.probe_round_model(
            work_s_per_step=work_s, tau=cand.tau,
            gather_bytes=gather_bytes, device_kind=device_kind, R=workers,
            mode=overlap, staleness=staleness) * 1e6
    return model_us

#!/usr/bin/env python3
"""Bring-up check: the DPPF trainer end to end on a TPU, in one process.

  python chip_smoke.py               # one chip (what CI on the chip runs)
  python chip_smoke.py --four-chips  # one worker row per chip, vs chip 0

One chip: ``repro.launch.train.main`` trains yi-6b at its published widths
(d_model 4096, 32:4 heads, d_ff 11008) cut to one layer and 8000 vocabulary
rows, 4 workers, tau 2, seq 2048, batch 1, 8 steps = 4 rounds of the
default ``simple_avg`` method on the flat engine, whose consensus runs the
compiled Pallas ``fused_round``. Then one ``fused_round`` call on the
trained view is checked against the engine's exact jnp stage
(``precise=True``) within the bound ``repro.core.engine`` states.

Four chips: the same run with ``--sharded`` (one worker row per chip,
``jax.shard_map``), then the same rounds from the same state and batches
unsharded on chip 0; each final worker row must agree within the fast-mode
Gram floor (DESIGN.md §Consensus-engine).

Earlier lines report the cut, n, compile seconds (XLA backend compiles,
persistent-cache reads included) apart from each round's wall seconds, and
the device's peak memory. The last line is one JSON object naming the
device. Exits non-zero, with no such line, when JAX finds no TPU or when a
phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import DPPFConfig  # noqa: E402
from repro.core import consensus  # noqa: E402
from repro.core.engine import GRAM_NOISE_FACTOR  # noqa: E402
from repro.kernels.pullpush import pullpush as pk  # noqa: E402
from repro.launch import train as launcher  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SMOKE = ["--arch", "yi-6b", "--layers", "1", "--vocab", "8000",
         "--workers", "4", "--tau", "2", "--seq", "2048", "--batch", "1",
         "--steps", "8", "--lr", "0.01", "--log-every", "1"]
OUT = ROOT / "chiprun_out"
EPS32 = float(np.finfo(np.float32).eps)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def require_tpu(devices, count=1):
    """Refuse to run anywhere but on ``count`` TPU chips."""
    if not devices or devices[0].platform != "tpu":
        kind = devices[0].platform if devices else "no device"
        raise RuntimeError(f"chip_smoke needs a TPU; JAX found {kind}")
    if len(devices) < count:
        raise RuntimeError(f"chip_smoke needs {count} TPU chips; JAX "
                           f"found {len(devices)}")


class CompileClock:
    """Sums XLA backend compile seconds (a persistent-cache hit counts its
    read) and counts persistent-cache hits, through ``jax.monitoring``."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1


def train(argv, log_name, clock):
    """One launcher run; checks every round and the eval loss finite."""
    OUT.mkdir(exist_ok=True)
    log = OUT / log_name
    c0, h0 = clock.seconds, clock.cache_hits
    run = launcher.main(argv + ["--log-every-round", str(log)])
    rows = [json.loads(line) for line in log.read_text().splitlines()]
    if len(rows) != len(run.round_s):
        raise RuntimeError(f"{len(rows)} logged rounds, "
                           f"{len(run.round_s)} timed")
    for row in rows:
        for key in ("train_loss", "consensus_dist"):
            if not math.isfinite(row[key]):
                raise RuntimeError(f"round {row['round']}: {key} = "
                                   f"{row[key]}")
    if not math.isfinite(run.eval_loss):
        raise RuntimeError(f"eval loss = {run.eval_loss}")
    print(f"compile_s {clock.seconds - c0:.3f} "
          f"cache_hits {clock.cache_hits - h0}")
    print("round_wall_s " + " ".join(f"{s:.4f}" for s in run.round_s))
    return run


def check_kernel(state, dcfg, lam_t, chunks=16):
    """One compiled ``fused_round`` on the live view against the exact
    jnp stage on the same input, chunked over columns so the reference
    fits next to the kernel's output. The bound is the one stated in
    ``repro.core.engine``'s docstring (kernel vs precise)."""
    eng = state.engine
    if not eng.use_kernel or pk._interpret(eng.interpret):
        raise RuntimeError("the engine did not pick the compiled kernel")
    view = state.params
    for leaf in jax.tree.leaves(state.opt):     # room for the reference
        leaf.delete()
    (kind, T, c0, c1), = consensus.lower_stages(eng, dcfg, lam_t)[0]
    out, r_k, _ = pk.fused_round(view, T, c0, c1, eps=eng.eps,
                                 block_cols=eng.block_cols)
    jax.block_until_ready(out)
    ref = dataclasses.replace(eng, use_kernel=False, precise=True)
    R, width = view.shape
    size = -(-width // chunks)
    bounds = [(a, min(size, width - a)) for a in range(0, width, size)]
    rho = (width / eng.block_cols + 64) * EPS32

    def piece(v, a, w):
        return jax.lax.dynamic_slice_in_dim(v, a, w, 1)

    @functools.partial(jax.jit, static_argnums=2)
    def gap_gram(v, a, w):
        return ref.stage_comm(piece(v, a, w), T)

    @functools.partial(jax.jit, static_argnums=4)
    def excess(v, o, G, a, w):
        """max over one chunk of |out_kernel - out_exact| - bound, and of
        |out_kernel - out_exact|."""
        x, o = piece(v, a, w), piece(o, a, w)
        new, r, _, _ = ref.stage(x, T, c0, c1, gram=G)
        tx = jnp.matmul(T, x, precision=jax.lax.Precision.HIGHEST)
        coef = c0 + c1 / jnp.maximum(r, eng.eps)
        bound = (jnp.abs(c1) / jnp.maximum(r, eng.eps) * 2 * rho)[:, None] \
            * jnp.abs(x - tx) + 4 * R * EPS32 \
            * (1 + jnp.abs(1 - coef))[:, None] * (jnp.abs(x) + jnp.abs(tx))
        d = jnp.abs(o - new)
        return jnp.max(d - bound), jnp.max(d)

    G = sum(gap_gram(view, a, w) for a, w in bounds)
    r_ref = np.sqrt(np.maximum(np.diag(np.asarray(G)), 0.0))
    r_rel = float(np.max(np.abs(np.asarray(r_k) - r_ref) / r_ref))
    res = [excess(view, out, G, a, w) for a, w in bounds]
    worst = max(float(e) for e, _ in res)
    diff = max(float(d) for _, d in res)
    print(f"kernel_check r_rel_err {r_rel:.3e} (bound {rho:.3e}) "
          f"max_abs_diff {diff:.3e} excess_over_bound {worst:.3e}")
    if not r_rel <= rho or not worst <= 0.0:
        raise RuntimeError("compiled fused_round disagrees with the exact "
                           "stage beyond the engine's bound")


def one_chip(argv=SMOKE):
    clock = CompileClock()
    run = train(argv, "rounds_1chip.jsonl", clock)
    eng = run.state.engine
    print(f"n {eng.layout.n} view {tuple(run.state.params.shape)}")
    dcfg = DPPFConfig(alpha=0.1, lam=0.5, engine="flat")
    check_kernel(run.state, dcfg, lam_t=dcfg.lam)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def four_chips(argv=SMOKE):
    """--sharded (one worker row per chip) against the same rounds
    unsharded on chip 0; rows must agree within the fast-mode Gram floor
    sqrt(GRAM_NOISE_FACTOR * eps32) * ||x_i||."""
    clock = CompileClock()
    sharded = train(argv + ["--sharded"], "rounds_4chip_sharded.jsonl",
                    clock)
    n = sharded.state.engine.layout.n
    x_sh = np.asarray(jax.device_get(sharded.state.params))[:, :n]
    del sharded
    single = train(argv, "rounds_4chip_single.jsonl", clock)
    x_one = np.asarray(jax.device_get(single.state.params))[:, :n]
    del single
    floor = math.sqrt(GRAM_NOISE_FACTOR * EPS32)
    for i, (a, b) in enumerate(zip(x_sh, x_one)):
        d = float(np.linalg.norm(a.astype(np.float64) - b))
        lim = floor * float(np.linalg.norm(b.astype(np.float64)))
        print(f"row {i} sharded_vs_single_l2 {d:.4e} floor {lim:.4e} "
              f"max_abs {float(np.max(np.abs(a - b))):.3e}")
        if not d <= lim:
            raise RuntimeError(f"row {i}: sharded and single-chip views "
                               "differ beyond the Gram floor")
    for i, dev in enumerate(jax.devices()):
        stats = dev.memory_stats() or {}
        print(f"peak_bytes_in_use chip{i} {stats.get('peak_bytes_in_use')}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="the sharded path on four chips and its "
                         "single-chip comparison, nothing else")
    args = ap.parse_args(argv)
    enable_compile_cache()
    count = 4 if args.four_chips else 1
    require_tpu(jax.devices(), count)
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)()
    print(f"total_s {time.perf_counter() - t0:.1f}")
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))


if __name__ == "__main__":
    main()

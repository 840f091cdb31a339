"""Continuous- vs static-batching serving microbench + prefill/decode
roofline rows. Writes ``BENCH_serving.json`` at the repo root (committed;
``benchmarks/check_bench.py`` guards it in CI like the roundclock and
overlap benches).

Field classes follow check_bench's contract:

* **structural** — step counts, occupancy, ``continuous_ge_static``, and
  the roofline rows: pure functions of the deterministic request trace /
  config arithmetic, identical on every host. The headline claim is the
  step ordering: BOTH modes run the SAME compiled decode step, so
  ``steps`` is a timer-free measure of scheduling efficiency, and on a
  mixed-length trace continuous batching needs no more steps than the
  static-batching admission barrier.
* **timing** — ``tok_s`` / ``ttft_ms`` / ``wall_s`` / ``compile_s``:
  host-relative, reported as deltas only.

The roofline rows use ``jax.eval_shape`` over ``ModelAPI.make_state`` to
MEASURE each arch's per-slot decode-state bytes from the actual state
pytree (never a hand formula), then feed ``roofline.serving_model``.

  PYTHONPATH=src:. python benchmarks/bench_serving.py --smoke
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced
from repro.launch.roofline import serving_model
from repro.models import build_model
from repro.serving import Request, SlotEngine, serve

# deterministic mixed trace: prompt lengths x per-request decode budgets
# chosen so static batches barrier on their longest member
TRACE_LENS = [40, 6, 13, 9, 40, 6, 13, 9]
TRACE_NEW = [24, 4, 8, 16, 4, 24, 16, 8]
MAX_SLOTS = 4
CHUNK = 8

ROOFLINE_ARCHS = ("gemma2-2b", "dbrx-132b", "zamba2-7b")
# the chip whose published peaks price the roofline rows
MODELLED_KIND = "TPU v5 lite"
ROOFLINE_SHAPE = {"max_slots": 64, "chunk": 256, "buf_len": 8192}


def measured_state_bytes(cfg, buf_len: int) -> int:
    """Per-slot decode-state bytes via abstract evaluation of the real
    ``make_state`` pytree (B=1): counts every cache/recurrent leaf."""
    model = build_model(cfg)
    params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32)}
    if cfg.n_enc_layers:
        batch["enc"] = jax.ShapeDtypeStruct((1, cfg.n_prefix, cfg.d_model),
                                            jnp.float32)
    elif cfg.n_prefix:
        batch["prefix"] = jax.ShapeDtypeStruct((1, cfg.n_prefix, cfg.d_model),
                                               jnp.float32)
    states, _ = jax.eval_shape(
        lambda p, b: model.make_state(p, b, buf_len), params_s, batch)
    return int(sum(int(np.prod(l.shape)) * l.dtype.itemsize
                   for l in jax.tree.leaves(states)))


def _mode_metrics(report):
    return {
        "steps": report.steps,
        "generated": report.generated,
        "occupancy": round(report.occupancy, 4),
        "wall_s": round(report.wall_s, 4),
        "tok_s": round(report.tok_s, 1),
        "ttft_ms": round(report.ttft_mean_s * 1e3, 2),
    }


def bench_serving(*, smoke=False):
    cfg = reduced(get_arch("gemma2-2b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, (l,)),
                    max_new_tokens=n)
            for i, (l, n) in enumerate(zip(TRACE_LENS, TRACE_NEW))]
    buf = max(TRACE_LENS) + max(TRACE_NEW)
    engine = SlotEngine(model, params, max_slots=MAX_SLOTS, buf_len=buf,
                        chunk=CHUNK)

    # warmup stream compiles every lane (incl. chunked prefill); timed
    # streams below are compile-free (microbench _time_donated discipline)
    t0 = time.perf_counter()
    serve(engine, [Request(rid=0, tokens=rng.integers(0, cfg.vocab_size,
                                                      (max(TRACE_LENS),)),
                           max_new_tokens=2),
                   Request(rid=1, tokens=rng.integers(0, cfg.vocab_size,
                                                      (3,)),
                           max_new_tokens=2)])
    compile_s = time.perf_counter() - t0

    cont = serve(engine, reqs, mode="continuous")
    stat = serve(engine, reqs, mode="static")

    out = {
        "arch": cfg.name,
        "max_slots": MAX_SLOTS,
        "chunk": CHUNK,
        "buf_len": buf,
        "trace_lens": TRACE_LENS,
        "trace_new": TRACE_NEW,
        "compile_s": round(compile_s, 2),
        "continuous": _mode_metrics(cont),
        "static": _mode_metrics(stat),
        # structural ordering: same compiled step in both modes, so fewer
        # steps == strictly less device work for the same tokens
        "continuous_ge_static": cont.steps <= stat.steps,
        "steps_saved_pct": round(100.0 * (stat.steps - cont.steps)
                                 / stat.steps, 2),
        "speedup_vs_static": round(stat.wall_s / cont.wall_s, 2)
        if cont.wall_s > 0 else 1.0,
    }
    return out


def bench_roofline():
    rows = {}
    for arch in ROOFLINE_ARCHS:
        cfg = get_arch(arch)
        sb = measured_state_bytes(cfg, ROOFLINE_SHAPE["buf_len"])
        r = serving_model(cfg, max_slots=ROOFLINE_SHAPE["max_slots"],
                          chunk=ROOFLINE_SHAPE["chunk"],
                          state_bytes_per_slot=sb,
                          device_kind=MODELLED_KIND)
        rows[arch] = {
            "state_bytes_per_slot": int(sb),
            "decode_bound": r["decode_bound"],
            "prefill_bound": r["prefill_bound"],
            "decode_tok_s": round(r["decode_tok_s"], 1),
            "prefill_tok_s": round(r["prefill_tok_s"], 1),
            "crossover_slots": (round(r["crossover_slots"], 1)
                                if np.isfinite(r["crossover_slots"])
                                else None),
            "prefill_tokens_per_decode_step": round(
                r["prefill_tokens_per_decode_step"], 1),
        }
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args(argv)
    result = {
        "backend": jax.default_backend(),
        "smoke": True,  # trace is fixed; flag kept for CLI symmetry
        "serving": bench_serving(smoke=args.smoke),
        "roofline": {"shape": dict(ROOFLINE_SHAPE), **bench_roofline()},
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
        f.write("\n")
    s = result["serving"]
    print(f"continuous: {s['continuous']['steps']} steps "
          f"(occ {s['continuous']['occupancy']}) vs static "
          f"{s['static']['steps']} steps (occ {s['static']['occupancy']}) "
          f"-> saved {s['steps_saved_pct']}% steps, "
          f"{s['speedup_vs_static']}x wall")
    print(f"wrote {args.out}")
    return result


if __name__ == "__main__":
    main()

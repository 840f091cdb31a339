#!/usr/bin/env python3
"""Readings of the control and of planted faults at a cell's own size.

  python3 bench/control.py --workload yi6b_tau4_1chip --seeds 11,12,13

For each seed it follows the cell's check rounds with the plain reference
in float32, then puts other runs in the program's place and prints the
numbers ``bench/run.py`` compares, each against the cell's limit:

* ``control``: the reference computed with float8 (e4m3) matrix operands,
  the precision below the configuration's bfloat16; it has to fail;
* ``half_batch``: the loss taken over the first half of each sequence;
* ``no_exchange``: the consensus left out;
* ``no_push``: the pull kept, the push left out;
* ``push_sign``: the push turned toward the worker mean.

The benchmark's own runs never run this; it sets the upper readings of
the limits (PERF.md). One JSON line per seed and variant goes to standard
output. Runs on the first device JAX finds.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

VARIANTS = {"control": dict(precision="float8_e4m3fn"),
            "half_batch": dict(fault="half_batch"),
            "no_exchange": dict(fault="no_exchange"),
            "no_push": dict(fault="no_push"),
            "push_sign": dict(fault="push_sign")}


def readings(cell, seed, variants=tuple(VARIANTS)):
    """{variant: compared numbers} for one seed."""
    from bench import reference
    from bench.run import token_batch
    cfg, tr, traffic = cell.cfg, cell.cfg["training"], cell.traffic
    make_w = reference.make_weights(cfg)
    K, K0 = traffic["check_rounds"], traffic["check_start"]

    def batches(r):
        return token_batch(seed, r, traffic["tau"], tr["workers"],
                           traffic["batch_per_worker"], traffic["seq_len"],
                           cfg["vocab_size"])

    def follow(**kw):
        return reference.ReferenceRun(cfg, tr, traffic, **kw).run(
            make_w(reference.seed_key(seed)), batches, K, start=K0)

    ref = follow()
    return {v: reference.compare(follow(**VARIANTS[v]), ref)
            for v in variants}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--variants", default=",".join(VARIANTS))
    args = ap.parse_args(argv)
    from bench.run import enable_cache, load_cell
    cell = load_cell(args.workload)
    enable_cache()
    import jax
    print(f"device {jax.devices()[0].device_kind}", file=sys.stderr)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = readings(cell, seed, tuple(args.variants.split(",")))
        for variant, check in out.items():
            failed = [k for k, v in check.items() if v > cell.limits[k]]
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "variant": variant, "check": check,
                              "fails": failed}), flush=True)
        print(f"seed {seed}: {time.perf_counter() - t:.1f} s",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()

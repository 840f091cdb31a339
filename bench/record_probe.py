#!/usr/bin/env python3
"""Record the small TPU trace that ``bench/test_trace.py`` reads.

  python3 bench/record_probe.py   # on one TPU chip

Three calls of one jitted step (a 4 x 131,072 ``fused_round`` and a
1024 x 1024 bf16 matmul with a tanh), each after a 10 ms host
``bench.batch`` span and dispatched in a ``bench.step`` span, traced with
``jax.profiler``; the ``.xplane.pb`` lands in
``bench/out/probe/plugins/profile/<time>/``.
"""
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.pullpush import pullpush as pk  # noqa: E402


def main():
    out = ROOT / "bench" / "out" / "probe"
    shutil.rmtree(out, ignore_errors=True)
    R, n = 4, 2048 * 64
    x = jax.random.normal(jax.random.PRNGKey(0), (R, n), jnp.float32)
    T = jnp.full((R, R), 1.0 / R, jnp.float32)
    c0 = jnp.full((R,), 0.1, jnp.float32)
    c1 = jnp.full((R,), -0.01, jnp.float32)
    a = jax.random.normal(jax.random.PRNGKey(1), (1024, 1024), jnp.bfloat16)

    @jax.jit
    def step(x, a):
        y, _, _ = pk.fused_round(x, T, c0, c1)
        return y, jnp.tanh(a @ a)

    x, a = jax.block_until_ready(step(x, a))
    jax.profiler.start_trace(str(out))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.batch"):
            time.sleep(0.01)
        with jax.profiler.TraceAnnotation("bench.step"):
            x, a = jax.block_until_ready(step(x, a))
    jax.profiler.stop_trace()
    print(sorted(out.glob("**/*.xplane.pb")))


if __name__ == "__main__":
    main()

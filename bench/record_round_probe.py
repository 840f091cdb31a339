#!/usr/bin/env python3
"""Record the small TPU trace that ``bench/test_scopes.py`` reads.

  python3 bench/record_round_probe.py   # on one TPU chip

Two DPPF rounds of the program's own ``make_round_step`` at the smoke
widths of yi-6b (2 layers, d_model 256; 4 workers stacked, tau 2, one
sequence of 128 tokens a worker and step, the flat engine with its
``fused_round`` kernel), driven by ``repro.train.Supervisor`` as
``bench/run.py`` drives it: ``bench.batch`` around the batch,
``bench.step`` around the dispatch, ``bench.window`` around both rounds,
and the program's own ``dppf.*`` spans and named scopes inside. The step
compiles and runs once before the trace. The ``.xplane.pb`` lands in
``bench/out/round_probe/plugins/profile/<time>/``.
"""
import shutil
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import DPPFConfig, get_arch  # noqa: E402
from repro.configs.base import reduced  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import make_optimizer  # noqa: E402
from repro.train import (RoundClock, Supervisor, init_train_state,  # noqa
                         make_round_step)

M, TAU, SEQ = 4, 2, 128


def main():
    out = ROOT / "bench" / "out" / "round_probe"
    shutil.rmtree(out, ignore_errors=True)
    mcfg = reduced(get_arch("yi-6b"))
    model = build_model(mcfg)
    dcfg = DPPFConfig(alpha=0.1, lam=0.5, tau=TAU, engine="flat",
                      consensus="simple_avg")
    opt = make_optimizer("sgd", momentum=0.9, weight_decay=1e-3)
    clock = RoundClock.from_config(dcfg, base_lr=0.01, total_steps=3 * TAU)
    state = init_train_state(model.init, opt, dcfg, M, jax.random.PRNGKey(0))
    step = jax.jit(make_round_step(model.loss, opt, dcfg, clock=clock),
                   donate_argnums=0)

    def batch_fn(spec, bs):
        with jax.profiler.TraceAnnotation("bench.batch"):
            rng = np.random.default_rng(spec.index)
            ids = rng.integers(0, mcfg.vocab_size,
                               size=(spec.tau, M, bs, SEQ + 1),
                               dtype=np.int32)
            return {"tokens": ids[..., :-1], "labels": ids[..., 1:]}

    def step_fn(st, b):
        with jax.profiler.TraceAnnotation("bench.step"):
            return step(st, b)

    def drive(st, start, stop):
        sup = Supervisor(types.SimpleNamespace(rounds=clock.rounds[:stop]),
                         workers=M, batch_size=1)
        return sup.run(st, step_fn, batch_fn, start_round=start)

    state = drive(state, 0, 1)                  # compiles the step
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        state = drive(state, 1, 3)
    jax.profiler.stop_trace()
    print(sorted(out.glob("**/*.xplane.pb")))


if __name__ == "__main__":
    main()

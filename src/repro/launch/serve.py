"""Serving launcher: continuous-batching request streams over SlotEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
      --requests 8 --max-slots 4 --prompt-len 32 --new-tokens 16 \
      [--static] [--window W] [--chunk C] [--temp 0.8 --topk 40 --topp 0.95]

The stream mixes prompt lengths (p/2, p, 2p cycling) so admissions and
evictions interleave mid-decode. A tiny warmup stream runs first so
compile time and warm throughput are reported SEPARATELY (the
``_time_donated`` discipline from benchmarks/microbench.py — a timer
started before the first call measures XLA, not serving).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint import load_pytree
from repro.configs import ARCHS, get_arch, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import GREEDY, Request, SamplingParams, SlotEngine, serve


def mixed_lengths(base: int, n: int):
    """Deterministic mixed prompt lengths: p/2, p, 2p cycling."""
    cycle = [max(1, base // 2), base, 2 * base]
    return [cycle[i % 3] for i in range(n)]


def build_requests(cfg, key, lens, new_tokens):
    rng = np.random.default_rng(int(np.asarray(key)[-1]))
    reqs = []
    for i, l in enumerate(lens):
        enc = None
        if cfg.n_enc_layers:
            enc = 0.02 * np.asarray(jax.random.normal(
                jax.random.fold_in(key, 100 + i),
                (cfg.n_prefix, cfg.d_model)))
        reqs.append(Request(
            rid=i, tokens=rng.integers(0, cfg.vocab_size, (l,)),
            max_new_tokens=new_tokens, enc=enc))
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window serving variant (ring buffer)")
    ap.add_argument("--chunk", type=int, default=0,
                    help="streaming-prefill chunk (0 = auto)")
    ap.add_argument("--buf-len", type=int, default=0,
                    help="cache positions per slot (0 = auto)")
    ap.add_argument("--temp", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--topk", type=int, default=0)
    ap.add_argument("--topp", type=float, default=1.0)
    ap.add_argument("--static", action="store_true",
                    help="static batching baseline (admission barrier)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    if args.ckpt:
        params, _ = load_pytree(args.ckpt, params)

    sampling = (GREEDY if args.temp == 0.0 else SamplingParams(
        temperature=args.temp, top_k=args.topk, top_p=args.topp))

    lens = mixed_lengths(args.prompt_len, args.requests)
    prefix = cfg.n_prefix if not cfg.n_enc_layers else 0
    buf = args.buf_len or (args.window + (args.chunk or 1)
                           if args.window
                           else prefix + max(lens) + args.new_tokens)

    example = {"tokens": np.zeros((1, 1), np.int32)}
    if cfg.n_enc_layers:
        example["enc"] = np.zeros((1, cfg.n_prefix, cfg.d_model), np.float32)
    engine = SlotEngine(model, params, max_slots=args.max_slots,
                        buf_len=buf, window=args.window, chunk=args.chunk,
                        sampling=sampling, example=example)

    # warmup stream: hits every compiled lane (incl. the chunked-prefill
    # lane via a long prompt) so the timed stream is compile-free
    warm_lens = [max(lens), min(lens)][:min(2, args.requests)]
    warm = build_requests(cfg, jax.random.fold_in(key, 1), warm_lens, 2)
    t0 = time.perf_counter()
    serve(engine, warm, mode="continuous", key=jax.random.fold_in(key, 2))
    compile_s = time.perf_counter() - t0

    reqs = build_requests(cfg, jax.random.fold_in(key, 3), lens,
                          args.new_tokens)
    mode = "static" if args.static else "continuous"
    report = serve(engine, reqs, mode=mode, key=jax.random.fold_in(key, 4))

    print(f"arch={cfg.name} mode={mode} slots={args.max_slots} "
          f"requests={args.requests} lens={lens} new={args.new_tokens} "
          f"window={args.window} buf={buf} chunk={engine.chunk} "
          f"sampling={'greedy' if sampling.greedy else sampling}")
    print(f"compile (warmup stream): {compile_s:.2f}s; lanes "
          f"{engine.compile_cache_sizes()}")
    print(f"warm: {report.tok_s:.1f} tok/s over {report.steps} steps, "
          f"occupancy {report.occupancy:.2f}, "
          f"ttft mean {report.ttft_mean_s * 1e3:.1f}ms, "
          f"{report.generated} tokens in {report.wall_s:.2f}s (host CPU)")
    r0 = report.results[0]
    print("sample rid=0:", r0.tokens[:16])
    return report


if __name__ == "__main__":
    main()

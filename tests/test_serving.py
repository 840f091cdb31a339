"""Continuous-batching serving tests: SlotEngine vs generate() parity
(greedy + ring wraparound) across all five families, zero-recompile
compile-counter pins, the decode_key sampling contract end-to-end, the
static-vs-continuous structural step ordering, fused-sampling units, and
the serving ValueError surface."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import build_model
from repro.serving import (
    GREEDY, Request, SamplingParams, SlotEngine, decode_loop_cache_size,
    generate, serve,
)
from repro.serving.sampling import NEG_INF, mask_logits, sample_batch

# one arch per ModelAPI family (dense / moe / hybrid-ssm / xlstm / enc-dec)
FAMILIES = ["yi-6b", "dbrx-132b", "zamba2-7b", "xlstm-350m",
            "seamless-m4t-medium"]


@functools.lru_cache(maxsize=None)
def _mp(arch):
    """Shared (cfg, model, params) per arch — one init, shared jit caches."""
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (l,)) for l in lens]


def _enc(cfg, rid):
    return 0.02 * np.asarray(jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(9), rid),
        (cfg.n_prefix, cfg.d_model)))


def _requests(cfg, lens, news, seed=0):
    return [Request(rid=i, tokens=t, max_new_tokens=n,
                    enc=_enc(cfg, i) if cfg.n_enc_layers else None)
            for i, (t, n) in enumerate(zip(_prompts(cfg, lens, seed), news))]


def _example(cfg):
    ex = {"tokens": np.zeros((1, 1), np.int32)}
    if cfg.n_enc_layers:
        ex["enc"] = np.zeros((1, cfg.n_prefix, cfg.d_model), np.float32)
    return ex


def _gen_batch(cfg, req):
    batch = {"tokens": np.asarray(req.tokens)[None].astype(np.int32)}
    if req.enc is not None:
        batch["enc"] = np.asarray(req.enc)[None].astype(np.float32)
    return batch


# ---------------------------------------------------------------------------
# continuous batching == generate(), per family + zero-recompile pin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", FAMILIES)
def test_continuous_matches_generate_and_never_recompiles(arch):
    """Mixed-length requests admitted/evicted mid-decode produce EXACTLY
    the tokens of per-request generate() (greedy), and a second,
    differently-mixed stream leaves every compiled lane at cache size 1."""
    cfg, model, params = _mp(arch)
    engine = SlotEngine(model, params, max_slots=2, buf_len=32, chunk=4,
                        example=_example(cfg))

    lens, news = [5, 11, 3], [6, 4, 5]
    reqs = _requests(cfg, lens, news)
    report = serve(engine, reqs)
    assert sorted(report.results) == [0, 1, 2]
    assert report.generated == sum(news)
    for req in reqs:
        want, _ = generate(model, params, _gen_batch(cfg, req),
                           max_new_tokens=req.max_new_tokens, buf_len=32)
        assert report.results[req.rid].tokens == [int(t) for t in want[0]], \
            f"{arch}: rid {req.rid} diverged from generate()"

    # every lane compiled exactly once during the first stream; a second
    # stream with a different admission/eviction mix must not retrace
    sizes = engine.compile_cache_sizes()
    assert sizes == {"fresh": 1, "chunk": 1, "decode": 1, "insert": 1}, sizes
    serve(engine, _requests(cfg, [9, 2, 6], [3, 5, 2], seed=1))
    assert engine.compile_cache_sizes() == sizes


@pytest.mark.parametrize("arch", FAMILIES)
def test_ring_wraparound_matches_generate(arch):
    """Prompts longer than buf_len stream through the ring (window mode);
    decode continues past the wrap point. Exact parity with windowed
    generate() pins the slot == pos % buf invariant and the
    buf_len >= window + chunk - 1 streaming contract."""
    cfg, model, params = _mp(arch)
    window, chunk, buf = 16, 4, 19     # buf == window + chunk - 1 exactly
    engine = SlotEngine(model, params, max_slots=2, buf_len=buf,
                        window=window, chunk=chunk, example=_example(cfg))
    reqs = _requests(cfg, [24, 20], [8, 8])   # prompt_len + new > window
    report = serve(engine, reqs)
    for req in reqs:
        want, _ = generate(model, params, _gen_batch(cfg, req),
                           max_new_tokens=8, buf_len=buf, window=window,
                           chunk=chunk)
        assert report.results[req.rid].tokens == [int(t) for t in want[0]], \
            f"{arch}: ring-wraparound rid {req.rid} diverged"


# ---------------------------------------------------------------------------
# sampled path: reproducibility, slot independence, decode_key contract
# ---------------------------------------------------------------------------

def test_sampled_stream_reproducible_and_slot_independent():
    """Per-request keys are derived from rid, so sampled outputs are a
    function of the request alone: same stream twice -> identical tokens,
    and submission order (hence slot placement / co-residents) is
    irrelevant."""
    cfg, model, params = _mp("yi-6b")
    sp = SamplingParams(temperature=0.8, top_k=8)
    engine = SlotEngine(model, params, max_slots=2, buf_len=48, chunk=4,
                        sampling=sp)
    lens, news = [7, 5, 9], [6, 6, 6]
    key = jax.random.PRNGKey(5)
    a = serve(engine, _requests(cfg, lens, news), key=key)
    b = serve(engine, _requests(cfg, lens, news), key=key)
    c = serve(engine, list(reversed(_requests(cfg, lens, news))), key=key)
    for rid in range(3):
        assert a.results[rid].tokens == b.results[rid].tokens
        assert a.results[rid].tokens == c.results[rid].tokens, \
            f"rid {rid}: tokens depend on submission order"


def test_engine_sampling_follows_decode_key_contract():
    """Manual replay: generated token 0 is sampled with the request key
    itself, token i >= 1 with fold_in(key, i) — independent of how the
    prompt was chunked into the slot."""
    from repro.serving import decode_key
    from repro.serving.sampling import sample_token

    cfg, model, params = _mp("yi-6b")
    sp = SamplingParams(temperature=0.8, top_k=8)
    engine = SlotEngine(model, params, max_slots=1, buf_len=32, chunk=4,
                        sampling=sp)
    prompt = _prompts(cfg, [6])[0]
    base = jax.random.PRNGKey(7)
    rkey = np.asarray(jax.random.fold_in(base, 0), np.uint32)
    report = serve(engine, [Request(rid=0, tokens=prompt, max_new_tokens=5)],
                   key=base)

    logits, states = model.prefill(
        params, {"tokens": prompt[None].astype(np.int32)}, buf_len=32)
    tok = int(sample_token(logits[0].astype(jnp.float32),
                           decode_key(rkey, 0), sp))
    want = [tok]
    start = prompt.size
    for i in range(1, 5):
        lg, states = model.decode_step(
            params, states, np.asarray([[tok]], np.int32),
            jnp.int32(start + i - 1))
        tok = int(sample_token(lg[0].astype(jnp.float32),
                               decode_key(rkey, i), sp))
        want.append(tok)
    assert report.results[0].tokens == want


# ---------------------------------------------------------------------------
# generate(): jitted decode loop never retraces on identical shapes
# ---------------------------------------------------------------------------

def test_generate_decode_loop_no_retrace():
    cfg, model, params = _mp("yi-6b")
    batch = {"tokens": _prompts(cfg, [10], seed=3)[0][None].astype(np.int32)}
    t1, _ = generate(model, params, batch, max_new_tokens=7, buf_len=24)
    t2, _ = generate(model, params, batch, max_new_tokens=7, buf_len=24)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert decode_loop_cache_size(model, 7, 0) == 1
    # a different prompt length reuses the SAME compile (start is traced)
    generate(model, params,
             {"tokens": _prompts(cfg, [14], seed=4)[0][None].astype(np.int32)},
             max_new_tokens=7, buf_len=24)
    assert decode_loop_cache_size(model, 7, 0) == 1


# ---------------------------------------------------------------------------
# static vs continuous: structural ordering on a mixed trace
# ---------------------------------------------------------------------------

def test_continuous_needs_no_more_steps_than_static():
    """Both modes run the same compiled decode step, so step counts are a
    timer-free efficiency metric; greedy tokens must be identical."""
    cfg, model, params = _mp("gemma2-2b")
    engine = SlotEngine(model, params, max_slots=2, buf_len=32, chunk=4)
    lens, news = [10, 3, 5, 7], [8, 2, 4, 6]
    cont = serve(engine, _requests(cfg, lens, news), mode="continuous")
    stat = serve(engine, _requests(cfg, lens, news), mode="static")
    assert cont.steps <= stat.steps
    assert cont.occupancy >= stat.occupancy
    for rid in range(4):
        assert cont.results[rid].tokens == stat.results[rid].tokens


# ---------------------------------------------------------------------------
# fused sampling units
# ---------------------------------------------------------------------------

def test_mask_logits_top_k_keeps_exactly_k():
    logits = jnp.asarray([0.1, 3.0, -1.0, 2.0, 0.5, -2.0])
    out = mask_logits(logits, SamplingParams(top_k=2))
    kept = np.flatnonzero(np.asarray(out) > NEG_INF / 2)
    np.testing.assert_array_equal(kept, [1, 3])


def test_mask_logits_top_p_never_empties_and_keeps_nucleus():
    logits = jnp.asarray([10.0, 1.0, 0.0, -1.0])
    # p tiny: the argmax alone always survives (exclusive cumsum)
    out = mask_logits(logits, SamplingParams(top_p=1e-6))
    kept = np.flatnonzero(np.asarray(out) > NEG_INF / 2)
    np.testing.assert_array_equal(kept, [0])
    # p = 1 keeps everything
    out = mask_logits(logits, SamplingParams(top_p=1.0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(logits))


def test_mask_logits_temperature_and_greedy_passthrough():
    logits = jnp.asarray([1.0, 2.0, 4.0])
    np.testing.assert_allclose(
        np.asarray(mask_logits(logits, SamplingParams(temperature=2.0))),
        np.asarray(logits) / 2.0, rtol=1e-6)
    # greedy and the no-op params return the input bit-identically
    assert mask_logits(logits, GREEDY) is logits
    assert mask_logits(logits, SamplingParams()) is logits


def test_sample_batch_independent_rows():
    logits = jnp.tile(jnp.asarray([0.0, 0.0, 0.0, 5.0]), (3, 1))
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(3)])
    toks = sample_batch(logits, keys, SamplingParams(temperature=1e-3))
    np.testing.assert_array_equal(np.asarray(toks), [3, 3, 3])
    assert sample_batch(logits, keys, GREEDY).dtype == jnp.int32


# ---------------------------------------------------------------------------
# ring cache unit: wrap-scatter
# ---------------------------------------------------------------------------

def test_cache_update_chunk_wraps_around_ring_seam():
    from repro.models.attention import cache_update, init_cache
    cache = init_cache(1, 1, 8, 4, jnp.float32)
    k = jnp.arange(4 * 4, dtype=jnp.float32).reshape(1, 4, 1, 4)
    out = cache_update(cache, k, k, 6)          # positions 6..9
    np.testing.assert_array_equal(
        np.asarray(out["pos"]), [8, 9, -1, -1, -1, -1, 6, 7])
    # slot p % buf holds position p's row
    np.testing.assert_array_equal(np.asarray(out["k"][0, 6, 0]),
                                  np.asarray(k[0, 0, 0]))
    np.testing.assert_array_equal(np.asarray(out["k"][0, 1, 0]),
                                  np.asarray(k[0, 3, 0]))


# ---------------------------------------------------------------------------
# ValueError surface (mirrored under python -O by tests/optcheck.py)
# ---------------------------------------------------------------------------

def test_sampling_params_validation():
    for bad in (dict(temperature=-0.1), dict(top_k=-1), dict(top_p=0.0),
                dict(top_p=1.5)):
        with pytest.raises(ValueError):
            SamplingParams(**bad)


def test_generate_validation():
    cfg, model, params = _mp("yi-6b")
    batch = {"tokens": np.zeros((1, 6), np.int32)}
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(model, params, batch, max_new_tokens=0, buf_len=16)
    with pytest.raises(ValueError, match="window"):
        generate(model, params, batch, max_new_tokens=2, buf_len=8, window=9)
    with pytest.raises(ValueError, match="silently truncate"):
        # prompt exceeds buf_len and no sliding window
        generate(model, params, {"tokens": np.zeros((1, 20), np.int32)},
                 max_new_tokens=2, buf_len=16)


def test_slot_engine_validation():
    cfg, model, params = _mp("yi-6b")
    for kw in (dict(max_slots=0, buf_len=8), dict(max_slots=1, buf_len=0),
               dict(max_slots=1, buf_len=8, window=-1),
               dict(max_slots=1, buf_len=8, window=9),
               # chunk write would clobber live ring slots
               dict(max_slots=1, buf_len=16, window=16, chunk=8)):
        with pytest.raises(ValueError):
            SlotEngine(model, params, **kw)
    ecfg, emodel, eparams = _mp("seamless-m4t-medium")
    with pytest.raises(ValueError, match="example"):
        SlotEngine(emodel, eparams, max_slots=1, buf_len=8)

    engine = SlotEngine(model, params, max_slots=2, buf_len=16)
    slots = engine.blank_slots()
    state, start = engine.request_state({"tokens": np.asarray([[0]], np.int32)})
    with pytest.raises(ValueError, match="slot"):
        engine.insert(slots, state, 2, 0, 0, 4, np.zeros(2, np.uint32))
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.insert(slots, state, 0, 0, 0, 0, np.zeros(2, np.uint32))
    with pytest.raises(ValueError, match="empty prompt"):
        engine.prefill_chunks(state, np.zeros((0,), np.int64), start)


def test_scheduler_and_request_validation():
    from repro.serving import Scheduler
    cfg, model, params = _mp("yi-6b")
    with pytest.raises(ValueError, match="max_slots"):
        Scheduler(0)
    with pytest.raises(ValueError, match="mode"):
        Scheduler(1, mode="adaptive")
    with pytest.raises(ValueError, match="empty prompt"):
        Request(rid=0, tokens=np.zeros((0,)), max_new_tokens=1)
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(rid=0, tokens=np.ones((3,)), max_new_tokens=0)
    # window == 0 capacity check at submit time
    engine = SlotEngine(model, params, max_slots=1, buf_len=16)
    sched = Scheduler(1)
    with pytest.raises(ValueError, match="buf_len"):
        sched.submit(Request(rid=0, tokens=np.ones((10,), np.int64),
                             max_new_tokens=10), engine)


def test_cache_update_rejects_oversized_write():
    from repro.models.attention import cache_update, init_cache
    cache = init_cache(1, 1, 4, 2, jnp.float32)
    k = jnp.zeros((1, 5, 1, 2))
    with pytest.raises(ValueError, match="buf_len"):
        cache_update(cache, k, k, 0)


def test_serving_roofline_validation_and_bounds():
    from repro.launch.roofline import serving_model
    cfg = ARCHS["gemma2-2b"]
    with pytest.raises(ValueError):
        serving_model(cfg, max_slots=0, chunk=1, state_bytes_per_slot=1,
                      device_kind="TPU v5 lite")
    with pytest.raises(ValueError):
        serving_model(cfg, max_slots=1, chunk=0, state_bytes_per_slot=1,
                      device_kind="TPU v5 lite")
    r = serving_model(cfg, max_slots=64, chunk=256,
                      state_bytes_per_slot=10 ** 9, device_kind="TPU v5 lite")
    assert r["decode_bound"] in ("memory", "compute")
    assert r["prefill_tok_s"] > r["decode_tok_s"]
    assert r["prefill_tokens_per_decode_step"] > 0

"""The correctness check, driven end to end on the CPU at a small size.

The harness's look for a chip is skipped; ``bench.run.execute`` then runs
everything else a run does (weights, the program's state and round step,
the supervisor loop, the window, the plain reference, the comparison)
under the limits of the cell ``yi6b_tau4_1chip``. A sound run has to come
out correct, and each fault a training cell can have, planted in the
timed path, has to come out not correct, as has a step with its push left
out. The control (the reference in float8 in the program's place) has to
fail too, and so has the reference with its push left out or turned
round.
"""
import jax
import pytest

from bench import control, run

TINY = {
    "name": "tiny", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 1, "vocab_size": 97, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "hidden_act": "silu",
    "tie_word_embeddings": False, "attention_bias": False,
    "torch_dtype": "bfloat16", "program": {"arch": "yi-6b"},
    "training": {"workers": 4, "consensus": "simple_avg", "engine": "flat",
                 "alpha": 0.1, "lam": 0.5, "lam_schedule": "increasing",
                 "optimizer": "sgd", "momentum": 0.9, "weight_decay": 1e-3,
                 # the CPU's consensus path floors worker distances under
                 # about 0.4% of the parameter norm (the chip's kernel does
                 # not); at this size lr 0.1 keeps them above that floor
                 "lr": 0.1}}
TRAFFIC = {"tau": 4, "seq_len": 32, "batch_per_worker": 1,
           "plan_rounds": 1000, "check_start": 500, "check_rounds": 3,
           "trace_rounds": 2}
SEED = 2 ** 33 + 7


@pytest.fixture(scope="module")
def cell():
    real = run.load_cell("yi6b_tau4_1chip")
    return run.Cell("tiny", 1, TINY, TRAFFIC, real.limits, real.metrics)


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch",
                                   "no_exchange", "no_push"])
def test_fault_makes_the_run_incorrect(cell, fault):
    rec, check, correct, meta = run.execute(
        cell, SEED, 0.2, False, jax.devices()[:1], fault=fault,
        log=lambda *a: None)
    assert correct == (fault is None), check
    assert meta["attempted"] >= 1 and rec.window_tokens > 0
    line = run.result_line(cell, rec, check, correct, meta, False)
    assert list(line)[-1] == "check"
    assert set(line["metrics"]) == {"tokens_per_s_chip", "setup_s"}


@pytest.mark.parametrize("variant", ["control", "no_push", "push_sign"])
def test_control_fails(cell, variant):
    """The control, and the reference with its push left out or turned
    round (the check rounds see the push), in the program's place."""
    out = control.readings(cell, SEED, (variant,))[variant]
    assert any(out[k] > cell.limits[k] for k in cell.limits), out

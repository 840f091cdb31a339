"""The architecture contract: a configuration file names its architecture
module under ``"reference"``, and the harness reaches architecture code
only through ``bench.reference.architecture``.

* A second architecture (``testdata/dense_qkv_bias.py``: the dense decoder
  with q/k/v biases) runs end to end through ``run.execute`` on the CPU,
  as ``test_check.py`` drives the dense one.
* The dense decoder's weights, loss and one reference round are pinned to
  values recorded before it moved out of ``bench/reference.py`` into
  ``bench/dense.py`` (``testdata/dense_reference_pin.json``), bit for bit.
* ``run.py``, ``control.py`` and ``flops.py`` name no dense-only symbol.
"""
import ast
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import dense, flops, reference, run
from bench.test_check import SEED, TINY, TRAFFIC

BENCH = Path(__file__).parent
PIN = json.loads((BENCH / "testdata" / "dense_reference_pin.json")
                 .read_text())
BIASED = dict(TINY, attention_bias=True,
              reference="bench.testdata.dense_qkv_bias")


def biased_cell(cfg):
    real = run.load_cell("yi6b_tau4_1chip")
    return run.Cell("tiny_bias", 1, cfg, TRAFFIC, real.limits, real.metrics)


def execute(cfg, fault=None):
    return run.execute(biased_cell(cfg), SEED, 0.2, False,
                       jax.devices()[:1], fault=fault, log=lambda *a: None)


def test_resolver():
    assert reference.architecture(TINY) is dense
    arch = reference.architecture(BIASED)
    assert arch.__name__ == "bench.testdata.dense_qkv_bias"
    assert run.program_config(BIASED).qkv_bias
    # bq over 4 heads of 16, bk and bv over 2
    assert flops.n_params(BIASED) == flops.n_params(TINY) + 64 + 2 * 32
    assert flops.flops_per_token(BIASED, 32) == \
        dense.flops_per_token(TINY, 32)
    with pytest.raises(ValueError, match="bench.<module>"):
        reference.architecture(dict(TINY, reference="os"))


@pytest.mark.parametrize("fault", [None, "no_push"])
def test_second_architecture_end_to_end(fault):
    rec, check, correct, meta = execute(BIASED, fault)
    assert correct == (fault is None), check
    assert rec.cfg is BIASED and rec.traffic is TRAFFIC
    assert meta["attempted"] >= 1 and rec.window_tokens > 0


def test_second_architecture_needs_its_reference():
    """Without its ``reference`` key the file is read as the dense
    decoder, whose leaves the program's view does not hold."""
    cfg = {k: v for k, v in BIASED.items() if k != "reference"}
    with pytest.raises(ValueError, match="reference's leaves"):
        execute(cfg)


def test_dense_weights_pinned():
    w = reference.make_weights(TINY)(reference.seed_key(SEED))
    digest = hashlib.sha256(b"".join(
        np.asarray(a).tobytes() for a in jax.tree.leaves(w))).hexdigest()
    assert digest == PIN["weights_sha256"]


@pytest.mark.parametrize("precision,key", [("float32", "loss"),
                                           ("float8_e4m3fn", "loss_float8")])
def test_dense_loss_pinned(precision, key):
    w = reference.make_weights(TINY)(reference.seed_key(SEED))
    tok, lab = run.token_batch(SEED, 0, 4, 4, 1, 32, 97)
    params = jax.tree.map(lambda a: a.astype(np.float32), w)
    loss = dense.loss_fn(params, tok[0, 0], lab[0, 0], TINY, precision)
    assert float(loss) == PIN[key]


def test_dense_reference_round_pinned():
    def batches(r):
        return run.token_batch(SEED, r, TRAFFIC["tau"], 4, 1,
                               TRAFFIC["seq_len"], 97)
    ref = reference.ReferenceRun(TINY, TINY["training"], TRAFFIC).run(
        reference.make_weights(TINY)(reference.seed_key(SEED)), batches, 1,
        start=TRAFFIC["check_start"])
    assert ref["losses"] == PIN["losses"]
    assert ref["dists"] == PIN["dists"]
    assert np.asarray(ref["mu_norms"]).tolist() == PIN["mu_norms"]
    assert np.asarray(ref["change_norms"]).tolist() == PIN["change_norms"]


DENSE_ONLY = {"dims", "MATMUL_LEAVES", "loss_fn", "param_shapes"}


@pytest.mark.parametrize("name", ["run.py", "control.py", "flops.py"])
def test_harness_reaches_architecture_through_the_resolver(name):
    """A dense-only symbol appears only as an attribute of a value that
    ``reference.architecture(...)`` returned, and no file imports
    ``bench.dense``."""
    tree = ast.parse((BENCH / name).read_text())

    def is_resolver(node):
        return isinstance(node, ast.Call) and (
            getattr(node.func, "attr", None) == "architecture"
            or getattr(node.func, "id", None) == "architecture")

    resolved = {t.id for node in ast.walk(tree)
                if isinstance(node, ast.Assign) and is_resolver(node.value)
                for t in node.targets if isinstance(t, ast.Name)}
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [a.name for a in node.names] + [
                getattr(node, "module", None) or ""]
            assert not any(n == "dense" or n.endswith(".dense")
                           for n in names), ast.dump(node)
            assert not DENSE_ONLY & set(names), ast.dump(node)
        elif isinstance(node, ast.Name):
            assert node.id not in DENSE_ONLY, node.id
        elif isinstance(node, ast.Attribute) and node.attr in DENSE_ONLY:
            base = node.value
            assert is_resolver(base) or (isinstance(base, ast.Name)
                                         and base.id in resolved), \
                f"{name}:{node.lineno} {ast.unparse(node)}"

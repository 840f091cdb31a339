"""CPU checks of the benchmark's data and arithmetic (no TPU needed)."""
import json
import math
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import flops, reference, run

ROOT = Path(__file__).resolve().parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
CONFIGS = sorted(p.stem for p in (ROOT / "bench" / "configs").glob("*.json"))


def config_file(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    """Each cell finds its configuration, traffic, limits and metrics."""
    cell = run.load_cell(name)
    assert cell.chips in (1, 4)
    assert set(cell.limits) == {"loss_gap", "dist_gap", "grad_gap",
                                "change_gap"}
    assert all(0 < v < 1 for v in cell.limits.values())
    for key in ("tau", "seq_len", "batch_per_worker", "plan_rounds",
                "check_start", "check_rounds", "trace_rounds"):
        assert cell.traffic[key] >= 1
    assert cell.cfg["training"]["workers"] % cell.chips == 0
    names = [m["name"] for kind in cell.metrics for m in cell.metrics[kind]]
    assert "setup_s" in names
    for metric in names:
        assert callable(getattr(
            __import__(f"bench.metrics.{metric}", fromlist=["read"]),
            "read"))


def test_spec_names_and_lengths():
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert c["file"].startswith("bench/")
        assert 0 < len(c["why"]) <= 200
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 0 < len(w["why"]) <= 200
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_is_the_published_cut(name):
    from repro.configs import cut, get_arch
    cfg = config_file(name)
    prog = cfg["program"]
    want = cut(get_arch(prog["arch"]), layers=prog["layers"],
               vocab=prog["vocab"])
    assert run.program_config(cfg) == want
    for key, (published, kept) in cfg["reduced"].items():
        assert cfg[key] == kept and published > kept


def test_yi_counts():
    cfg = config_file("yi-6b.l1.w4")
    n = flops.n_params(cfg)
    assert n == 238_563_328
    matmul = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008 \
        + 4096 * 8000
    assert flops.matmul_params(cfg) == matmul
    assert flops.flops_per_token(cfg, 2048) == 6 * matmul \
        + 12 * 1 * 4096 * 2048
    assert flops.view_width(cfg) == n          # 116,486 blocks of 2048


def test_internlm2_counts():
    cfg = config_file("internlm2-20b.l1.w2")
    assert flops.n_params(cfg) == 532_236_288
    assert flops.view_width(cfg) == 532_236_288


@pytest.mark.parametrize("rows,width", [(4, 238_563_328), (2, 2048)])
def test_consensus_bytes(rows, width):
    assert flops.consensus_bytes(rows, width) == 12 * rows * width


def test_token_batch_is_the_seeds():
    a = run.token_batch(2 ** 33 + 5, 3, 4, 2, 1, 16, 97)
    b = run.token_batch(2 ** 33 + 5, 3, 4, 2, 1, 16, 97)
    c = run.token_batch(2 ** 33 + 5, 4, 4, 2, 1, 16, 97)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert not (a[0] == c[0]).all()
    assert (a[0][..., 1:] == a[1][..., :-1]).all()
    assert a[0].shape == (4, 2, 1, 16) and a[0].max() < 97


def test_refuses_a_cpu():
    """On a CPU backend the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())
    assert "TPU" in p.stderr


@pytest.mark.parametrize("name", CELLS)
def test_check_rounds_see_the_push(name):
    """The check rounds lie where the increasing push strength is at least
    a quarter of its final value, and the plan holds the window after
    them."""
    cell = run.load_cell(name)
    tr, traffic = cell.cfg["training"], cell.traffic
    lam_t = reference.increasing_lam(tr["lam"], traffic["check_start"],
                                     traffic["plan_rounds"])
    assert lam_t >= 0.25 * tr["lam"]
    after = traffic["check_start"] + traffic["check_rounds"] + 1
    assert traffic["plan_rounds"] - after >= 100 + traffic["trace_rounds"]


def test_mfu_arithmetic():
    from bench.metrics import mfu
    rec = type("R", (), dict(window_s=2.0, window_tokens=4000, chips=2,
                             flops_per_token=1e9,
                             peak={"bf16_flops_per_s": 1e13}))()
    assert math.isclose(mfu.read(rec), 100.0 * 1e9 * 1000 / 1e13)

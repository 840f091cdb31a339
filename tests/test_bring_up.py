"""CPU tests of what running on the chip added: the chip-share cut of a
published config, the compile-cache placement, the peaks table, and
``chip_smoke.py``'s refusal to run anywhere but on a TPU."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import cut, get_arch
from repro.launch import roofline as rf
from repro.launch.compile_cache import (DEFAULT_DIR, ENV_VAR,
                                        enable_compile_cache)
from repro.launch.train import main
from repro.models import build_model

ROOT = Path(__file__).resolve().parents[1]


def test_cut_keeps_published_widths():
    """yi-6b cut to one layer and 1/8 of its vocabulary, as chip_smoke.py
    trains it: depth and vocabulary rows shrink, every width stays, and
    the parameter count is the smoke's n (shapes only, no arrays)."""
    pub = get_arch("yi-6b")
    cfg = cut(pub, layers=1, vocab=8000)
    assert (cfg.n_layers, cfg.vocab_size) == (1, 8000)
    for k in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "dtype", "layer_pattern"):
        assert getattr(cfg, k) == getattr(pub, k), k
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    assert sum(l.size for l in jax.tree.leaves(shapes)) == 238_563_328
    assert shapes["embed"].shape == (8000, 4096)
    assert cut(pub) == pub


@pytest.mark.parametrize("arch,layers,vocab,match", [
    ("yi-6b", 0, 7999, "1/8"),               # below the vocabulary floor
    ("yi-6b", 0, 64001, "--vocab"),          # more rows than published
    ("yi-6b", 33, 0, "--layers"),            # deeper than published
    ("gemma2-2b", 1, 0, "period"),           # half a local/global period
])
def test_cut_rejects_below_floors(arch, layers, vocab, match):
    with pytest.raises(ValueError, match=match):
        cut(get_arch(arch), layers=layers, vocab=vocab)


@pytest.mark.parametrize("flags", [
    ["--vocab", "7999"],
    ["--layers", "3", "--arch", "gemma2-2b"],
    ["--d-model", "512"],                    # a width: --smoke only
])
def test_launcher_refuses_bad_cut(flags, capsys):
    """The launcher turns a bad cut into a usage error before any model
    work."""
    with pytest.raises(SystemExit):
        main(["--arch", "yi-6b", "--steps", "1"] + flags)
    assert "error" in capsys.readouterr().err


def test_chip_smoke_refuses_cpu():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        smoke.require_tpu(jax.devices("cpu"))
    with pytest.raises(RuntimeError, match="needs a TPU"):
        smoke.require_tpu([])


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_respects_env(monkeypatch, cache_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # JAX reads it


def test_compile_cache_fixed_path(monkeypatch, cache_config):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert enable_compile_cache() == str(ROOT / ".jax_cache")
    assert DEFAULT_DIR == ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    assert enable_compile_cache() == str(DEFAULT_DIR)     # never moves


def test_peaks_table_refuses_unknown_kind():
    assert rf.peaks("TPU v5 lite")["flops"] == 197e12
    assert rf.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        rf.peaks("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        rf.probe_round_model(work_s_per_step=1e-4, tau=2, gather_bytes=1e6,
                             device_kind="TPU v4")

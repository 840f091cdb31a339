"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler ships with jaxlib's TPU plug-in, so it can compile for a
``v5e:2x2`` topology it is only told about. That refuses what the
interpreter accepts: kernels that need more fast memory than a core has,
programs that do not fit the chip's 16 GB, and ops the backend cannot
lower (an abort of the whole process, not an exception). Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a module fixture — never on import, in
a ``skipif`` or in ``parametrize`` — because only one process may load
the TPU library and every test worker imports this file. Code that asks
``jax.default_backend()`` still sees the CPU here, so every kernel is
compiled with ``interpret=False`` and the engine with its kernel flags set
explicitly.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import DPPFConfig
from repro.core import consensus
from repro.core.engine import ConsensusEngine
from repro.kernels.pullpush import pullpush as pk

# the yi-6b chip share chip_smoke.py trains (1 layer, 8000 vocab rows)
N = 238_563_328
R = 4
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, F32, sharding=sharding)


def _kernel_call(name):
    """(function, donated arg index or None, arg shapes) per kernel."""
    if name == "fused_round":
        return (lambda x, T, c0, c1: pk.fused_round(
            x, T, c0, c1, interpret=False), 0,
            [(R, N), (R, R), (R,), (R,)])
    if name == "partial_gram":
        return (lambda x: pk.partial_gram(x, interpret=False), None,
                [(R, N)])
    return (lambda x, T, c: pk.mix_shard(x, T, c, interpret=False), 0,
            [(R, N), (R, R), (R,)])


@pytest.mark.parametrize("name", ["fused_round", "partial_gram",
                                  "mix_shard"])
def test_consensus_kernel_compiles_for_v5e(name, one_chip):
    """Each consensus kernel compiles (not interpreted) at the smoke's n,
    and its program needs no temporary beyond one (R, n) fp32 buffer plus
    64 MiB: the kernels pad no rows, copy no view, and write their output
    over the donated input."""
    fn, donate, shapes = _kernel_call(name)
    jitted = jax.jit(fn, donate_argnums=() if donate is None else donate)
    compiled = jitted.lower(*[_sds(s, one_chip) for s in shapes]).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the instruction, and so the device trace's op, keeps the kernel's
    # name, which the benchmark's consensus_kernel_ms matches
    assert re.search(rf"%{name}\.\d+ = ", compiled.as_text())
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= R * N * 4 + 64 * 2 ** 20, \
        mem.temp_size_in_bytes
    if donate is not None:
        # the (R, n) result is written into the donated view
        assert mem.alias_size_in_bytes >= R * N * 4, mem.alias_size_in_bytes


@pytest.mark.parametrize("use_kernel", [False, True])
def test_two_worker_stage_compiles_for_v5e(use_kernel, one_chip):
    """A 2-worker simple_avg consensus round through the engine, on the
    kernel and the jnp path. Its (R, R) identity and uniform weights are
    host constants: as traced iotas the TPU compiler aborted the process
    on the f32[2,2] subtraction."""
    stacked = {"w": jax.ShapeDtypeStruct((2, 8192), F32)}
    eng = ConsensusEngine.from_stacked(stacked, use_kernel=use_kernel,
                                       interpret=False)
    dcfg = DPPFConfig(alpha=0.1, lam=0.5, engine="flat")

    def round_(flat):
        new, _, metrics = consensus.apply_round(
            flat, dcfg, 0.5, {}, engine=eng,
            losses=jnp.zeros((2,), F32), grad_norms=jnp.ones((2,), F32))
        return new, metrics

    compiled = jax.jit(round_, donate_argnums=0).lower(
        _sds((2, eng.layout.width), one_chip)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel

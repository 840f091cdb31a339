"""The trace reducer, on a trace recorded on one TPU v5e and on made-up
timelines.

``testdata/fused_round_probe.xplane.pb`` was recorded by
``bench/record_probe.py`` on one TPU v5e: three calls of one jitted step
(a 4 x 131,072 ``fused_round`` and a 1024^2 bf16 matmul with a tanh),
each preceded by a host ``bench.batch`` span and dispatched in a
``bench.step`` span.
"""
from pathlib import Path

import pytest

from bench import trace
from bench.metrics import (consensus_kernel_ms, consensus_kernel_roofline,
                           device_idle_share, exposed_collective_ms,
                           local_step_ms)

PROBE = Path(__file__).parent / "testdata" / "fused_round_probe.xplane.pb"


@pytest.fixture(scope="module")
def probe():
    return trace.load(str(PROBE))


def test_probe_has_one_chip_and_spans(probe):
    assert len(probe.chips) == 1
    assert probe.chips[0].name == "/device:TPU:0"
    names = {n for _, _, n in probe.spans}
    assert names == {"bench.batch", "bench.step"}
    assert probe.window_ns > 0


def test_probe_kernel_time(probe):
    chip = probe.chips[0]
    fused = [(a, b) for a, b, op in chip.ops
             if trace.base_name(op) == "fused_round"]
    assert len(fused) == 3
    ns = probe.op_ns(chip, lambda op: trace.base_name(op) == "fused_round")
    assert ns == pytest.approx(sum(b - a for a, b in fused))
    assert 3 * 20e3 < ns < 3 * 100e3          # about 36 us a call


def test_probe_busy_and_gaps(probe):
    chip = probe.chips[0]
    busy = probe.busy_ns(chip)
    assert 0 < busy < probe.window_ns
    gaps = probe.idle_gaps(chip)
    idle = sum(b - a for a, b, _ in gaps)
    assert idle + busy == pytest.approx(probe.window_ns)
    assert {n for _, _, n in gaps} <= {"batch", "dispatch", "supervisor"}
    # the 10 ms host sleep in bench.batch leaves the device idle
    assert any(n == "batch" and b - a > 5e6 for a, b, n in gaps)
    assert probe.exposed_collective_ns(chip) == 0


def test_probe_op_totals(probe):
    tops = dict(probe.op_totals())
    assert "fused_round" in tops and "convolution_tanh_fusion" in tops
    assert tops["fused_round"] > tops["convolution_tanh_fusion"] * 0.5


def test_no_module_runs_before_its_dispatch(probe):
    steps = sorted(a for a, _, n in probe.spans if n == "bench.step")
    for a, _, _ in probe.chips[0].modules:
        prior = [s for s in steps if s <= a + 5e6]
        assert prior and a >= prior[-1]


@pytest.mark.parametrize("ivs,merged", [
    ([(0, 2), (1, 3), (5, 6)], [(0, 3), (5, 6)]),
    ([(4, 5), (0, 1), (1, 2)], [(0, 2), (4, 5)]),
    ([(3, 3)], []),
])
def test_union(ivs, merged):
    assert trace.union(ivs) == merged


def test_subtract_and_measure():
    a = [(0, 10), (20, 30)]
    b = [(2, 3), (8, 22), (29, 40)]
    assert trace.subtract(a, b) == [(0, 2), (3, 8), (22, 29)]
    assert trace.measure(trace.subtract(a, b)) == 14
    assert trace.subtract(a, []) == a


def made_up():
    """Two chips, 1000 ns window, 2 rounds, tau 2."""
    c0 = trace.Chip("/device:TPU:0", ops=[
        (0, 300, "fusion.1"), (300, 400, "fused_round.2"),
        (400, 450, "all-gather-done.3"), (500, 900, "fusion.4")],
        async_ops=[(380, 480, "all-gather-start.3")])
    c1 = trace.Chip("/device:TPU:1", ops=[
        (0, 200, "fusion.1"), (250, 300, "partial_gram.5"),
        (300, 340, "mix_shard.6")])
    return trace.Trace(window=(0, 1000), chips=[c0, c1],
                       spans=[(0, 1000, "bench.window"),
                              (450, 500, "bench.batch")])


class Rec:
    traced_rounds, tau = 2, 2
    consensus_bytes_per_round = 819e9 * 50e-9      # 50 ns at the roofline
    peak = {"hbm_bytes_per_s": 819e9}

    def __init__(self, tr):
        self.trace = tr


def test_made_up_metrics():
    tr = made_up()
    rec = Rec(tr)
    # chip 0 busy 850, chip 1 busy 290 -> mean idle 1 - 570/1000
    assert device_idle_share.read(rec) == pytest.approx(43.0)
    # kernels: chip 0 100 ns, chip 1 90 ns -> max 100 ns / 2 rounds
    assert consensus_kernel_ms.read(rec) == pytest.approx(50e-6)
    assert consensus_kernel_roofline.read(rec) == pytest.approx(100.0)
    # chip 0: collective 380-480, other ops cover 380-400 -> 80 exposed
    assert tr.exposed_collective_ns(tr.chips[0]) == 80
    assert exposed_collective_ms.read(rec) == pytest.approx(40e-6)
    # chip 0 local: 300 + 400 = 700 ns over 4 steps
    assert local_step_ms.read(rec) == pytest.approx(175e-6)
    gaps = tr.idle_gaps(tr.chips[0])
    assert [(a, b, n) for a, b, n in gaps] == [
        (450, 500, "batch"), (900, 1000, "supervisor")]

"""The scope reducer, on traces recorded on one TPU v5e and on made-up
timelines.

``testdata/round_scopes_probe.xplane.pb`` was recorded by
``bench/record_round_probe.py`` on one TPU v5e: two DPPF rounds of the
program's ``make_round_step`` at yi-6b's smoke widths, with the
benchmark's ``bench.*`` spans, the supervisor's ``dppf.*`` spans and the
trainer's named scopes. ``testdata/fused_round_probe.xplane.pb`` is the
older probe of ``bench/test_trace.py``, recorded before the scopes.
"""
from pathlib import Path

import pytest

from bench import scopes, trace
from bench.metrics import reader

DATA = Path(__file__).parent / "testdata"
ROUND_PROBE = DATA / "round_scopes_probe.xplane.pb"
OLD_PROBE = DATA / "fused_round_probe.xplane.pb"
STEP_METRICS = ("forward_ms", "backward_ms", "update_ms", "view_ms",
                "local_other_ms")
ROUND_METRICS = STEP_METRICS + ("consensus_ms", "unscoped_ms",
                                "input_wait_ms")


class Rec:
    def __init__(self, tr, rounds, tau):
        self.trace, self.traced_rounds, self.tau = tr, rounds, tau


@pytest.fixture
def out_dir(tmp_path, monkeypatch):
    """A ``bench/out`` stand-in that ``scopes.for_trace`` searches."""
    monkeypatch.setattr(scopes, "OUT", str(tmp_path))

    def place(src, cell="cell"):
        dest = tmp_path / f"trace-{cell}" / "plugins" / "profile" / "1"
        dest.mkdir(parents=True)
        (dest / "t.xplane.pb").write_bytes(Path(src).read_bytes())
        return trace.load(str(dest / "t.xplane.pb"))
    return place


def test_old_probe_tf_op_of_the_kernel():
    sc = scopes.load(str(OLD_PROBE))
    paths = sc.paths["/device:TPU:0"]
    assert len(paths) == 1
    ops = next(iter(paths.values()))
    # the file holds 'jit(step)/jit(fused_round)/pallas_call:'
    assert ops["fused_round.1"] == "jit(step)/jit(fused_round)/pallas_call"
    assert ops["convolution_tanh_fusion"] == "jit(step)/dot_general"


def test_old_probe_spans_match_profile_data():
    sc = scopes.load(str(OLD_PROBE))
    tr = trace.load(str(OLD_PROBE))
    assert sc.spans == tr.spans
    assert sc.window == tr.window


def test_old_probe_has_no_layers(out_dir):
    """A program without the scopes and spans: every reader is silent."""
    rec = Rec(out_dir(OLD_PROBE), 3, 1)
    assert scopes.for_trace(rec.trace) is not None
    for name in ROUND_METRICS:
        assert reader(name)(rec) is None, name


@pytest.fixture
def round_probe(out_dir):
    return Rec(out_dir(ROUND_PROBE), 2, 2)


def test_round_probe_metrics_read(round_probe):
    for name in ROUND_METRICS:
        v = reader(name)(round_probe)
        assert v is not None and v >= 0, name
    for name in STEP_METRICS + ("consensus_ms",):
        assert reader(name)(round_probe) > 0, name


def test_round_probe_partitions_busy_time(round_probe):
    tr = round_probe.trace
    busy = tr.busy_ns(tr.chips[0]) / round_probe.traced_rounds / 1e6
    per_step = sum(reader(n)(round_probe) for n in STEP_METRICS)
    total = round_probe.tau * per_step + reader("consensus_ms")(round_probe) \
        + reader("unscoped_ms")(round_probe)
    assert total == pytest.approx(busy, rel=1e-9)


def test_round_probe_input_wait_within_idle(round_probe):
    tr = round_probe.trace
    idle = (tr.window_ns - tr.busy_ns(tr.chips[0])) \
        / round_probe.traced_rounds / 1e6
    assert 0 <= reader("input_wait_ms")(round_probe) <= idle


def test_round_probe_spans(round_probe):
    sc = scopes.for_trace(round_probe.trace)
    names = {n for _, _, n in sc.spans}
    assert {"bench.window", "bench.batch", "bench.step", "dppf.round",
            "dppf.batch", "dppf.dispatch", "dppf.wait",
            "dppf.report"} <= names
    assert len(sc.program_spans("dppf.round")) == 2
    # the supervisor's dppf.batch wraps the benchmark's batch_fn, whose
    # bench.batch sits inside it
    outer = sc.program_spans("dppf.batch")
    for a, b in sc.program_spans("bench.batch"):
        assert any(s <= a and b <= e for s, e in outer)


def test_round_probe_kernel_keeps_its_name(round_probe):
    """``name=`` on the pallas_call keeps ``%fused_round.<n>``, so
    ``consensus_kernel_ms`` still finds the kernel, now under the
    consensus scope."""
    tr = round_probe.trace
    sc = scopes.for_trace(tr)
    paths = sc.op_paths(tr.chips[0], tr.window)
    kernels = [op for op in paths if trace.base_name(op) == "fused_round"]
    assert kernels
    assert all(scopes.layer_of(paths[op]) == "consensus" for op in kernels)
    assert reader("consensus_kernel_ms")(round_probe) > 0


def test_for_trace_finds_the_run_by_its_window(out_dir):
    out_dir(OLD_PROBE, "a")
    tr = out_dir(ROUND_PROBE, "b")
    sc = scopes.for_trace(tr)
    assert sc is not None and "dppf.round" in {n for _, _, n in sc.spans}


def test_for_trace_skips_a_cut_off_file(out_dir, tmp_path):
    tr = out_dir(OLD_PROBE, "a")
    (path,) = (tmp_path / "trace-a").rglob("*.xplane.pb")
    data = path.read_bytes()
    path.write_bytes(data[:len(data) // 2])
    assert scopes.for_trace(tr) is None


# ---------------------------------------------------------------------------
# the classifier, on made-up paths and timelines
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path,layer", [
    ("jit(round_step)/while/body/closed_call/vmap(jvp(dppf.model))/while/"
     "body/dot_general", "forward"),
    ("jit(round_step)/while/body/closed_call/vmap(transpose(jvp("
     "dppf.model)))/while/body/dot_general", "backward"),
    ("jit(round_step)/while/body/closed_call/vmap(jvp(dppf.view))/slice",
     "view"),
    ("jit(round_step)/while/body/closed_call/vmap(transpose(jvp("
     "dppf.view)))/pad", "view"),
    ("jit(round_step)/while/body/closed_call/vmap(dppf.update)/mul",
     "update"),
    ("jit(round_step)/dppf.consensus/jit(fused_round)/fused_round/"
     "pallas_call", "consensus"),
    ("jit(round_step)/shard_map/dppf.consensus/dppf.exchange/all_gather",
     "consensus"),
    ("jit(round_step)/while/body/dynamic_slice", None),
    ("", None),
    # inside the local steps' loop the innermost scope decides
    ("jit(round_step)/dppf.local/while/body/closed_call/vmap(transpose("
     "jvp(dppf.model)))/while/body/dot_general", "backward"),
    ("jit(round_step)/dppf.local/while/body/closed_call/vmap(jvp("
     "dppf.view))/convert_element_type", "view"),
    # an op the compiler made in the loop, named after the loop
    ("jit(round_step)/dppf.local/while", "local"),
])
def test_layer_of(path, layer):
    assert scopes.layer_of(path) == layer


@pytest.mark.parametrize("tf_op,path", [
    ("jit(step)/jit(fused_round)/pallas_call:", "jit(step)/jit(fused_round)/"
     "pallas_call"),
    ("jit(f)/vmap(jvp(dppf.model))/dot_general:dot_general;"
     "jit(f)/vmap(dppf.update)/mul:mul", "jit(f)/vmap(jvp(dppf.model))/"
     "dot_general"),
    ("jit(f)/bqkgh,bskh->bkgqs/dot_general:", "jit(f)/bqkgh,bskh->bkgqs/"
     "dot_general"),
])
def test_normalize_takes_the_first_path(tf_op, path):
    assert scopes.normalize(tf_op) == path


def made_up():
    """One chip, 1000 ns window: a while (0-700) around the local steps'
    leaf ops (one of them named only after the loop), a consensus kernel,
    and an op the scopes do not name."""
    chip = trace.Chip("/device:TPU:0", ops=[
        (0, 700, "while.1"),
        (0, 100, "fusion.1"), (100, 300, "fusion.2"),
        (300, 350, "multiply_subtract_fusion"), (350, 400, "copy.1"),
        (400, 650, "fusion.3"), (650, 700, "convert.5"),
        (720, 800, "fused_round.1"), (800, 830, "copy.2")],
        modules=[(0, 830, "jit_round_step(7)")])
    tr = trace.Trace(window=(0, 1000), chips=[chip],
                     spans=[(0, 1000, "bench.window")])
    paths = {"/device:TPU:0": {"7": {
        "while.1": "jit(round_step)/while",
        "fusion.1": "jit(round_step)/while/body/vmap(jvp(dppf.model))/dot",
        "fusion.2": "jit(round_step)/while/body/vmap(transpose(jvp("
                    "dppf.model)))/dot",
        "multiply_subtract_fusion": "jit(round_step)/while/body/"
                                    "vmap(dppf.update)/sub",
        "copy.1": "jit(round_step)/while/body/vmap(jvp(dppf.view))/convert",
        "fusion.3": "jit(round_step)/while/body/vmap(transpose(jvp("
                    "dppf.model)))/dot",
        "convert.5": "jit(round_step)/dppf.local/while",
        "fused_round.1": "jit(round_step)/dppf.consensus/pallas_call"},
        "8": {"fusion.1": "jit(other)/dppf.consensus/dot"}}}
    spans = [(0, 1000, "bench.window"), (700, 720, "dppf.batch"),
             (830, 900, "dppf.wait"), (950, 990, "dppf.batch")]
    return tr, scopes.Scopes(paths, spans)


def test_made_up_partition_counts_leaf_ops_only():
    tr, sc = made_up()
    (row,) = scopes.partition(tr, sc)
    # the enclosing while counts only through its leaf ops
    assert row == {"forward": 100, "backward": 450, "update": 50,
                   "view": 50, "local": 50, "consensus": 80,
                   "unscoped": 30}
    assert sum(row.values()) == tr.busy_ns(tr.chips[0])


def test_made_up_programs_that_did_not_run_are_ignored():
    tr, sc = made_up()
    paths = sc.op_paths(tr.chips[0], tr.window)
    assert scopes.layer_of(paths["fusion.1"]) == "forward"


def test_made_up_layer_and_wait_ms(monkeypatch):
    tr, sc = made_up()
    monkeypatch.setattr(scopes, "for_trace", lambda t: sc)
    rec = Rec(tr, 1, 2)
    assert scopes.layer_ms(rec, "backward") == pytest.approx(225e-6)
    assert scopes.layer_ms(rec, "local") == pytest.approx(25e-6)
    assert scopes.layer_ms(rec, "consensus") == pytest.approx(80e-6)
    assert scopes.layer_ms(rec, scopes.UNSCOPED) == pytest.approx(30e-6)
    # idle 700-720 and 950-990 lie in dppf.batch spans; 830-900 in wait
    assert scopes.span_idle_ms(rec, "dppf.batch") == pytest.approx(60e-6)
    assert scopes.span_idle_ms(rec, "dppf.wait") == pytest.approx(70e-6)
    assert scopes.span_idle_ms(rec, "dppf.recover") is None


def test_an_op_another_starts_inside_is_not_a_leaf():
    """An op that a later op starts inside counts as enclosing
    (``bench.trace.leaf_ops``): only the later op's layer gets time, the
    rest is unscoped, and the partition still sums to busy time."""
    chip = trace.Chip("/device:TPU:0", ops=[(0, 60, "a"), (40, 100, "b")])
    tr = trace.Trace(window=(0, 100), chips=[chip], spans=[])
    sc = scopes.Scopes({"/device:TPU:0": {"": {
        "a": "f/vmap(dppf.update)/x", "b": "f/vmap(jvp(dppf.model))/y"}}},
        [])
    (row,) = scopes.partition(tr, sc)
    assert row["forward"] == 60 and row["update"] == 0
    assert row["unscoped"] == 40
    assert sum(row.values()) == 100


def test_scope_ms_is_the_layer_of_a_leaf_scope(round_probe):
    """``update`` holds no other ``dppf.`` scope, so its time under the
    path rule is its layer's; an absent scope reads None."""
    assert scopes.scope_ms(round_probe, "update", True) == \
        scopes.layer_ms(round_probe, "update")
    assert scopes.scope_ms(round_probe, "update", False) == pytest.approx(
        2 * scopes.layer_ms(round_probe, "update"))
    assert scopes.scope_ms(round_probe, "experts", True) is None

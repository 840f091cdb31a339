"""Split a traced run's device time by the program's named scopes, and its
idle time by the program's host spans.

The program marks its layers with ``jax.named_scope`` (``dppf.view``,
``dppf.model``, ``dppf.update``, ``dppf.consensus``; see
``repro.train.trainer``) and its round loop with ``TraceAnnotation`` spans
(``dppf.round``, ``dppf.batch``, ``dppf.dispatch``, ``dppf.wait``, ...;
see ``repro.train.supervisor``). A device op's scope path reaches the
``.xplane.pb`` as the ``tf_op`` stat of the op's *event metadata* (for
example ``jit(step)/jit(fused_round)/pallas_call:``), which
``jax.profiler.ProfileData`` does not expose; so this module reads the
file's protobuf wire format itself (the XSpace schema of the profiler,
fields below), with no dependency beyond the standard library.

``bench.trace.load`` has already reduced the run's trace to
``ctx.trace`` (device ops shifted onto the host clock, clipped to
``bench.window``). Here the run's file is found again by that window, its
``tf_op`` paths are joined onto ``ctx.trace``'s ops by op name, and:

* each leaf op's time goes to the layer of its path (``layer_of``):
  ``forward`` (``jvp(dppf.model)``), ``backward``
  (``transpose(jvp(dppf.model))``), ``update``, ``view``, ``local`` (ops
  the compiler made in the local steps' loop without a layer's name, which
  the trace names after the loop), ``consensus``; busy time under none of
  them is ``unscoped``, so the seven partition the device's busy time;
* each stretch of device idle time goes to the innermost program span
  around it on the host (``dppf.batch`` is the chip waiting for input).

A program without these scopes or spans (an older checkout) gives
nothing to read, and the readers return None.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import sys

from bench import trace as trace_mod

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")
LAYERS = ("forward", "backward", "update", "view", "local", "consensus")
UNSCOPED = "unscoped"
# per local step; the others are per round
STEP_LAYERS = ("forward", "backward", "update", "view", "local")
SPAN_PREFIXES = ("bench.", "dppf.")
SCOPE = re.compile(r"dppf\.([A-Za-z_]+)")
PROGRAM = re.compile(r"\((\d+)\)$")

# -- XSpace field numbers (tsl/profiler/protobuf/xplane.proto) -------------
# XSpace:  planes 1
# XPlane:  name 2, lines 3, event_metadata 4 (map), stat_metadata 5 (map)
# XLine:   name 2, timestamp_ns 3, events 4
# XEvent:  metadata_id 1, offset_ps 2, duration_ps 3
# XEventMetadata: id 1, name 2, stats 5
# XStatMetadata:  id 1, name 2
# XStat:   metadata_id 1, str_value 5, ref_value 7 (an XStatMetadata id
#          whose name is the value)
# a map<int64, M> entry: key 1, value 2


def _varint(buf, pos):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf, pos=0, end=None):
    """Yield ``(field, value)`` of one message: an int for varint and
    fixed-width fields, a ``(start, end)`` slice for length-delimited."""
    end = len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = (pos, pos + n), pos + n
        elif wire == 1:
            val, pos = int.from_bytes(buf[pos:pos + 8], "little"), pos + 8
        elif wire == 5:
            val, pos = int.from_bytes(buf[pos:pos + 4], "little"), pos + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, val


def _str(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, span):
    key, value = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat_metadata(buf, spans):
    names = {}
    for span in spans:
        key, value = _map_entries(buf, span)
        if value is not None:
            names[key] = next((_str(buf, v) for f, v in _fields(buf, *value)
                               if f == 2), "")
    return names


def _event_metadata(buf, spans, stat_names, wanted=()):
    """id -> (name, {stat name: value}) for the stats named in
    ``wanted`` (string and reference values; integers as ints)."""
    ids = {k for k, n in stat_names.items() if n in wanted}
    out = {}
    for span in spans:
        key, value = _map_entries(buf, span)
        if value is None:
            continue
        name, stats = "", {}
        for f, v in _fields(buf, *value):
            if f == 2:
                name = _str(buf, v)
            elif f == 5 and ids:
                sid, sval = None, None
                for sf, sv in _fields(buf, *v):
                    if sf == 1:
                        sid = sv
                    elif sf == 5:
                        sval = _str(buf, sv)
                    elif sf == 7:
                        sval = stat_names.get(sv)
                    elif sf in (3, 4):
                        sval = sv
                if sid in ids and sval is not None:
                    stats[stat_names[sid]] = sval
        out[key] = (name, stats)
    return out


def _line_events(buf, span, names):
    """(start_ns, end_ns, name) of one line's events, on the clock
    ``ProfileData`` reports (the line's timestamp plus the offset)."""
    t0, events = 0, []
    for f, v in _fields(buf, *span):
        if f == 3:
            t0 = v
        elif f == 4:
            events.append(v)
    out = []
    for ev in events:
        mid = off = dur = 0
        for f, v in _fields(buf, *ev):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        start = t0 + off / 1000.0
        out.append((start, start + dur / 1000.0, names.get(mid, ("",))[0]))
    return out


class Scopes:
    """What one ``.xplane.pb`` says beyond ``bench.trace``: per device
    plane and XLA program, op name -> scope path (``normalize``d
    ``tf_op``), and the host's ``bench.*``/``dppf.*`` spans."""

    def __init__(self, paths, spans):
        self.paths = paths        # {plane: {program id: {op name: path}}}
        self.spans = spans        # sorted [(start, end, name)]

    @property
    def window(self):
        """The ``bench.window`` span; where there is none, the extent of
        the ``bench.*`` spans (as ``bench.trace.load`` takes it)."""
        bench = [(a, b, n) for a, b, n in self.spans
                 if n.startswith("bench.")]
        win = [(a, b) for a, b, n in bench if n == trace_mod.WINDOW]
        if win:
            return win[0]
        if bench:
            return (min(a for a, _, _ in bench), max(b for _, b, _ in bench))
        return None

    def program_spans(self, name):
        return [(a, b) for a, b, n in self.spans if n == name]

    def op_paths(self, chip, window=None):
        """Op name -> path on ``chip`` (a ``bench.trace.Chip``), from the
        programs that ran in ``window`` (``chip.modules`` events name the
        program id, ``jit_round_step(<id>)``); from every program where
        the trace has no module events there."""
        progs = self.paths.get(chip.name, {})
        ran = {m.group(1) for a, b, mod in chip.modules
               if window is None or (b > window[0] and a < window[1])
               for m in [PROGRAM.search(mod)] if m}
        out = {}
        for pid, ops in progs.items():
            if not ran or pid in ran:
                for op, path in ops.items():
                    out.setdefault(op, path)
        return out


def normalize(tf_op):
    """``'a/b:c;d/e:f'`` -> ``'a/b'``: the first path, without the op
    type after its last ``:``."""
    first = tf_op.split(";", 1)[0]
    return re.sub(r":[^/]*$", "", first)


def parse(path):
    """Read one ``.xplane.pb`` into ``Scopes``."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    paths, spans = {}, []
    for f, plane_span in _fields(buf):
        if f != 1:
            continue
        name, lines, ev_meta, st_meta = "", [], [], []
        for pf, v in _fields(buf, *plane_span):
            if pf == 2:
                name = _str(buf, v)
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                ev_meta.append(v)
            elif pf == 5:
                st_meta.append(v)
        if trace_mod._device_index(name) is not None:
            paths[name] = _device_paths(
                _event_metadata(buf, ev_meta, _stat_metadata(buf, st_meta),
                                ("tf_op", "program_id",
                                 "deduplicated_name")))
        elif name.startswith("/host:"):
            meta = _event_metadata(buf, ev_meta, {})
            for line in lines:
                spans.extend(e for e in _line_events(buf, line, meta)
                             if e[2].startswith(SPAN_PREFIXES))
    return Scopes(paths, sorted(spans))


def _device_paths(meta):
    """{program id: {op name: path}} of one device plane. An op whose
    metadata XLA deduplicated (``deduplicated_name``) takes the path of
    the op it names."""
    progs, dedup = {}, []
    for name, stats in meta.values():
        op, pid = trace_mod.op_name(name), str(stats.get("program_id", ""))
        if "tf_op" in stats:
            progs.setdefault(pid, {}).setdefault(op, normalize(stats["tf_op"]))
        elif "deduplicated_name" in stats:
            dedup.append((pid, op, stats["deduplicated_name"]))
    for pid, op, other in dedup:
        ops = progs.setdefault(pid, {})
        if other in ops:
            ops.setdefault(op, ops[other])
    return progs


@functools.lru_cache(maxsize=8)
def _parse_cached(path, mtime_ns, size):
    return parse(path)


def load(path):
    st = os.stat(path)
    return _parse_cached(os.path.abspath(path), st.st_mtime_ns, st.st_size)


def for_trace(tr):
    """The ``Scopes`` of the file ``tr`` was reduced from: the
    ``.xplane.pb`` under ``OUT/trace-*/`` whose ``bench.window`` span is
    ``tr.window``. None when there is none."""
    files = sorted(glob.glob(os.path.join(OUT, "trace-*", "**",
                                          "*.xplane.pb"), recursive=True))
    for path in files:
        try:
            sc = load(path)
        except (OSError, ValueError, IndexError) as e:   # a cut-off file
            print(f"bench.scopes: cannot read {path}: {e}", file=sys.stderr)
            continue
        w = sc.window
        if w is not None and abs(w[0] - tr.window[0]) < 1.0 \
                and abs(w[1] - tr.window[1]) < 1.0:
            return sc
    return None


def layer_of(path):
    """The layer of a scope path: the innermost ``dppf.`` scope on it
    decides; ``dppf.model`` is ``backward`` under a ``transpose(``, else
    ``forward``; ``dppf.exchange`` belongs to the consensus; ``dppf.local``
    alone (the local steps' loop, no layer inside it) is ``local``. None
    for a path under no ``dppf.`` scope."""
    for comp in reversed(path.split("/")):
        m = SCOPE.search(comp)
        if m is None:
            continue
        scope = m.group(1)
        if scope == "model":
            return "backward" if "transpose(" in comp else "forward"
        if scope in ("view", "update", "local", "consensus"):
            return scope
        if scope == "exchange":
            return "consensus"
    return None


def partition(tr, scopes):
    """Per chip, device busy ns in the window by layer (``LAYERS`` and
    ``unscoped``); the values of a chip sum to its busy time. A leaf op
    (``bench.trace.leaf_ops``: one that no later op starts inside; leaf
    ops do not overlap) counts under its path's layer."""
    out = []
    for chip in tr.chips:
        paths = scopes.op_paths(chip, tr.window)
        by_layer = {k: [] for k in LAYERS}
        for a, b, op in trace_mod.leaf_ops(chip.ops):
            layer = layer_of(paths.get(op, ""))
            if layer is not None:
                by_layer[layer].append((a, b))
        row = {k: trace_mod.measure(trace_mod.clip(trace_mod.union(iv),
                                                   *tr.window))
               for k, iv in by_layer.items()}
        row[UNSCOPED] = tr.busy_ns(chip) - sum(row.values())
        out.append(row)
    return out


def idle_in_spans(tr, chip, spans):
    """Idle ns of ``chip`` in the window inside the given host spans."""
    idle = trace_mod.subtract([tr.window], tr.intervals(chip))
    outside = trace_mod.subtract([tr.window], trace_mod.union(spans))
    return trace_mod.measure(trace_mod.subtract(idle, outside))


# -- what the readers in bench/metrics share ------------------------------

def _scopes(ctx):
    tr = ctx.trace
    if tr is None or not ctx.traced_rounds or not tr.chips:
        return None
    return for_trace(tr)


def layer_ms(ctx, layer):
    """Device ms of ``layer``, max over chips: a local step's for
    ``STEP_LAYERS``, a round's for the others. None where the program
    has no scopes."""
    sc = _scopes(ctx)
    if sc is None:
        return None
    rows = partition(ctx.trace, sc)
    if not any(r[k] for r in rows for k in LAYERS):
        return None
    per = ctx.traced_rounds * (ctx.tau if layer in STEP_LAYERS else 1)
    return max(r[layer] for r in rows) / per / 1e6


def span_idle_ms(ctx, name):
    """Device idle ms a round inside the program's host span ``name``,
    max over chips. None where the program has no such span."""
    sc = _scopes(ctx)
    spans = sc.program_spans(name) if sc is not None else []
    if not spans:
        return None
    tr = ctx.trace
    worst = max(idle_in_spans(tr, c, spans) for c in tr.chips)
    return worst / ctx.traced_rounds / 1e6

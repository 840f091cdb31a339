"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

A TPU trace holds one plane per chip (``/device:TPU:<k>``) whose ``XLA
Ops`` line has one event per executed HLO operation, named by its HLO text
(``%fused_round.1 = (f32[4,...]) custom-call(...)``), and an ``Async XLA
Ops`` line with the in-flight spans of asynchronous operations. The host
plane (``/host:CPU``) carries the benchmark's ``TraceAnnotation`` spans
(``bench.window``, ``bench.batch``, ``bench.step``). Both are on one clock,
up to the offset the runtime leaves between them; ``load`` shifts the
device side so that no round's first operation starts before the host
dispatched it.

Everything is in nanoseconds and clipped to the ``bench.window`` span.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW = "bench.window"
SPAN_NAMES = {"bench.batch": "batch", "bench.step": "dispatch"}
OUTSIDE_SPANS = "supervisor"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all)")


def op_name(event_name):
    """``'%fused_round.1 = (...) ...'`` -> ``'fused_round.1'``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def base_name(op):
    """``'fused_round.1'`` -> ``'fused_round'``."""
    return re.sub(r"\.\d+$", "", op)


def is_collective(op):
    return bool(COLLECTIVE.match(op))


# ---------------------------------------------------------------------------
# Interval arithmetic on sorted, disjoint [start, end) lists
# ---------------------------------------------------------------------------

def union(intervals):
    out = []
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def measure(merged):
    return sum(b - a for a, b in merged)


def subtract(merged, remove):
    """Parts of ``merged`` not covered by ``remove`` (both merged lists)."""
    out, j = [], 0
    for a, b in merged:
        cur = a
        while j < len(remove) and remove[j][1] <= cur:
            j += 1
        k = j
        while k < len(remove) and remove[k][0] < b:
            ra, rb = remove[k]
            if ra > cur:
                out.append((cur, ra))
            cur = max(cur, rb)
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


# ---------------------------------------------------------------------------
# The reduced trace
# ---------------------------------------------------------------------------

@dataclass
class Chip:
    name: str
    ops: list = field(default_factory=list)        # (start, end, op)
    async_ops: list = field(default_factory=list)  # (start, end, op)
    modules: list = field(default_factory=list)    # (start, end, module)


@dataclass
class Trace:
    window: tuple                     # (start, end) of bench.window
    chips: list                       # Chip per device plane, in order
    spans: list                       # (start, end, name) host bench.* spans

    @property
    def window_ns(self):
        return self.window[1] - self.window[0]

    def intervals(self, chip, pred=None, *, with_async=False):
        evs = chip.ops + (chip.async_ops if with_async else [])
        return clip(union((a, b) for a, b, op in evs
                          if pred is None or pred(op)), *self.window)

    def busy_ns(self, chip):
        """Time in the window in which some operation ran on ``chip``."""
        return measure(self.intervals(chip))

    def op_ns(self, chip, pred):
        """Time in the window covered by operations matching ``pred``."""
        return measure(self.intervals(chip, pred))

    def exposed_collective_ns(self, chip):
        """Time in which a collective was in flight or running on ``chip``
        and no other operation ran there."""
        coll = self.intervals(chip, is_collective, with_async=True)
        other = self.intervals(chip, lambda op: not is_collective(op))
        return measure(subtract(coll, other))

    def idle_gaps(self, chip):
        """Idle intervals of ``chip`` in the window, each named by what the
        host did for most of it: ``batch``, ``dispatch`` (the host spans),
        or ``supervisor`` (the loop's wait and bookkeeping outside both)."""
        gaps = subtract([self.window], self.intervals(chip))
        inner = [(a, b, SPAN_NAMES[n]) for a, b, n in self.spans
                 if n in SPAN_NAMES]
        out = []
        for a, b in gaps:
            share = {}
            for s, e, name in inner:
                if e > a and s < b:
                    share[name] = share.get(name, 0) + min(e, b) - max(s, a)
            share[OUTSIDE_SPANS] = (b - a) - sum(share.values())
            out.append((a, b, max(share, key=share.get)))
        return out

    def op_totals(self):
        """Seconds per operation base name, summed over the window and
        averaged over the chips, longest first. An operation that encloses
        others (a ``while`` around the local steps' scan body) counts only
        through the operations inside it."""
        tot = {}
        for chip in self.chips:
            for a, b, op in leaf_ops(chip.ops):
                a, b = max(a, self.window[0]), min(b, self.window[1])
                if b > a:
                    key = base_name(op)
                    tot[key] = tot.get(key, 0.0) + (b - a)
        n = max(len(self.chips), 1)
        return sorted(((k, v / n / 1e9) for k, v in tot.items()),
                      key=lambda kv: -kv[1])


def leaf_ops(ops):
    """The operations that enclose no other operation of the same line."""
    ops = sorted(ops, key=lambda e: (e[0], -e[1]))
    return [e for i, e in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][0] >= e[1]]


def find_xplane(log_dir):
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _device_index(name):
    m = re.match(r"^/device:TPU:(\d+)$", name)
    return int(m.group(1)) if m else None


def load(path):
    """Read one ``.xplane.pb`` into a ``Trace``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chips, spans = {}, []
    for plane in pd.planes:
        idx = _device_index(plane.name)
        if idx is not None:
            chip = Chip(plane.name)
            for line in plane.lines:
                dest = {"XLA Ops": chip.ops, "Async XLA Ops": chip.async_ops,
                        "XLA Modules": chip.modules}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    name = op_name(e.name) if dest is not chip.modules \
                        else e.name
                    dest.append((e.start_ns, e.end_ns, name))
            chips[idx] = chip
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.start_ns, e.end_ns, e.name))
    windows = [(a, b) for a, b, n in spans if n == WINDOW]
    if windows:
        window = windows[0]
    elif spans:            # a trace with spans but no window: all of them
        window = (min(a for a, _, _ in spans), max(b for _, b, _ in spans))
    else:
        raise ValueError(f"{path}: no bench.* span")
    ordered = [chips[k] for k in sorted(chips)]
    shift = _dispatch_shift(ordered, spans, window)
    if shift:
        for chip in ordered:
            for lst in (chip.ops, chip.async_ops, chip.modules):
                lst[:] = [(a + shift, b + shift, n) for a, b, n in lst]
    return Trace(window=window, chips=ordered, spans=sorted(spans))


def _dispatch_shift(chips, spans, window):
    """The least shift that puts every executed module of the window at or
    after the host dispatch (``bench.step``) that launched it."""
    steps = sorted(a for a, b, n in spans if n == "bench.step"
                   and window[0] <= a <= window[1])
    worst = 0.0
    for chip in chips:
        for a, _, _ in chip.modules:
            prior = [s for s in steps if s <= a + 5e6]   # within 5 ms
            if prior:
                worst = min(worst, a - prior[-1])
    return -worst

"""Roofline-term derivation from compiled dry-run artifacts.

Hardware model: the published peaks of the chip named by ``device_kind``
(``PEAKS``); every modelled caller names the kind it models.

XLA's ``cost_analysis`` counts while-loop (scan) bodies ONCE, which
undercounts layer-stacked models by ~L*tau (verified: gemma2 raw HLO flops
= model flops / ~14). We therefore run our own static analysis over the
post-partitioning HLO: walk the computation call graph, multiply every
op by the product of enclosing ``known_trip_count``s, and accumulate
  * dot FLOPs         (2 * numel(result) * contracted-dim product)
  * fusion-boundary bytes (operands + results of top-level ops — an HBM
    traffic model where each fusion is one pass over its buffers)
  * collective payload bytes per kind.
All numbers are PER DEVICE (the compiled module is the SPMD-partitioned
per-device program; verified against a hand-sharded matmul).
"""
from __future__ import annotations

import re
from collections import defaultdict

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s of HBM
# bandwidth, 1,600 Gbit/s of chip-to-chip interconnect (4 links, ~50 GB/s
# each, the per-link figure the collective terms use).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip kind; a kind not in ``PEAKS`` is an error,
    never a default."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9a-z]*)\[([0-9,]*)\]")
_SKIP_BYTES_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "after-all", "partition-id", "replica-id",
                   "iota"}


def _type_info(type_str):
    """(bytes, [shapes]) for a (possibly tuple) HLO type string."""
    total, shapes = 0, []
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        shape = [int(d) for d in dims.split(",")] if dims else []
        n = 1
        for d in shape:
            n *= d
        total += n * _DTYPE_BYTES[dt]
        shapes.append((dt, shape))
    return total, shapes


_OP_RE = re.compile(r"^\s*(%[\w\.\-]+)\s*=\s*(.*)$")
_GROUP_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
# IotaReplicaGroupList: [G,S]<=[d0,d1,..]T(p0,p1,..) — groups formed by
# arange(prod(d)).reshape(d).transpose(p).reshape(G, S)
_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_PAIR_RE = re.compile(r"source_target_pairs=\{\{(\d+),(\d+)\}")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s+\(")
_ATTR_COMP_RE = re.compile(
    r"(?:body|condition|to_apply|calls)=\{?%([\w\.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count\":\{\"n\":\"(\d+)\"')
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERAND_RE = re.compile(r"%[\w\.\-]+")


class HloOp:
    __slots__ = ("name", "op", "result_bytes", "result_shapes", "operands",
                 "callees", "trip", "contract_dims", "axis", "line")

    def __init__(self, name, op, result_bytes, result_shapes, operands,
                 callees, trip, contract_dims, axis, line):
        self.name, self.op = name, op
        self.result_bytes, self.result_shapes = result_bytes, result_shapes
        self.operands, self.callees = operands, callees
        self.trip, self.contract_dims = trip, contract_dims
        self.axis = axis
        self.line = line


def _classify_axis(line, n_model):
    """Which mesh axis a collective spans: 'model' (ids within one TP row),
    'data' (worker/pod axes; ids congruent mod n_model), or 'mixed'.
    Device order is row-major (..., data, model)."""
    ids = None
    m = _GROUP_RE.search(line)
    if m:
        ids = [int(x) for x in m.group(1).split(",")]
    if ids is None:
        m = _IOTA_RE.search(line)
        if m:
            import numpy as _np
            g, s = int(m.group(1)), int(m.group(2))
            dims = [int(x) for x in m.group(3).split(",")]
            arr = _np.arange(int(_np.prod(dims))).reshape(dims)
            if m.group(4):
                perm = [int(x) for x in m.group(4).split(",")]
                arr = arr.transpose(perm)
            ids = arr.reshape(g, s)[0].tolist()
    if ids is None:
        p = _PAIR_RE.search(line)
        if p:
            ids = [int(p.group(1)), int(p.group(2))]
    if not ids or len(ids) < 2:
        return "unknown"
    if all(i // n_model == ids[0] // n_model for i in ids):
        return "model"
    if all(i % n_model == ids[0] % n_model for i in ids):
        return "data"
    return "mixed"


def _parse_op(line, n_model=16):
    m = _OP_RE.match(line)
    if not m or "=" not in line:
        return None
    name, rest = m.group(1), m.group(2)
    # result type: leading tuple-or-scalar type, then "op-name(".
    if rest.startswith("("):
        depth, i = 0, 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        type_str, tail = rest[:i + 1], rest[i + 1:].strip()
    else:
        sp = rest.find(" ")
        type_str, tail = rest[:sp], rest[sp + 1:].strip()
    om = re.match(r"([\w\-\.]+)\((.*)$", tail)
    if not om:
        return None
    op = om.group(1)
    body = om.group(2)
    # strip metadata / backend_config payloads before scanning attributes
    attr_part = body
    for cut in ("metadata={", "backend_config="):
        j = attr_part.find(cut)
        if j >= 0:
            attr_part = attr_part[:j]
    operand_part = attr_part.split(")", 1)[0]
    operands = _OPERAND_RE.findall(operand_part)
    callees = _ATTR_COMP_RE.findall(attr_part)
    trip = None
    tm = _TRIP_RE.search(body)
    if tm:
        trip = int(tm.group(1))
    cd = None
    cm = _CONTRACT_RE.search(attr_part)
    if cm:
        cd = [int(x) for x in cm.group(1).split(",") if x]
    rb, rs = _type_info(type_str)
    axis = None
    base = op.replace("-start", "")
    if base in COLLECTIVES:
        axis = _classify_axis(body, n_model)
    return HloOp(name, op, rb, rs, operands, callees, trip, cd, axis, line)


def parse_hlo(text, n_model=16):
    """-> (computations: {name: [HloOp]}, entry name)"""
    comps, cur, cur_name = {}, None, None
    entry = None
    for line in text.splitlines():
        if line.startswith("}"):
            cur = None
            continue
        cm = _COMP_RE.match(line)
        if cm and line.rstrip().endswith("{"):
            cur_name = cm.group(2)
            cur = comps.setdefault(cur_name, [])
            if cm.group(1):
                entry = cur_name
            continue
        if cur is None:
            continue
        op = _parse_op(line, n_model)
        if op:
            cur.append(op)
    return comps, entry


def _multipliers(comps, entry):
    """Computation -> dynamic execution count (trip-count products)."""
    mult = defaultdict(float)
    mult[entry] = 1.0
    order = [entry]
    seen = {entry}
    # propagate breadth-first; the call graph is a DAG in compiled HLO
    i = 0
    while i < len(order):
        c = order[i]
        i += 1
        for op in comps.get(c, []):
            trip = op.trip if (op.op == "while" and op.trip) else 1
            for callee in op.callees:
                mult[callee] += mult[c] * trip
                if callee not in seen:
                    seen.add(callee)
                    order.append(callee)
    return mult


def _fusion_targets(comps):
    targets = set()
    for ops in comps.values():
        for op in ops:
            if op.op in ("fusion",):
                targets.update(op.callees)
            if op.op in ("reduce", "reduce-window", "scatter", "sort",
                         "map", "select-and-scatter"):
                targets.update(op.callees)  # scalar apply fns
    return targets


def analyze_hlo(text, n_model=16):
    comps, entry = parse_hlo(text, n_model)
    mult = _multipliers(comps, entry)
    fusion_targets = _fusion_targets(comps)

    # symbol tables for operand shape lookup (per computation)
    shapes = {}
    for cname, ops in comps.items():
        for op in ops:
            shapes[(cname, op.name)] = op.result_shapes

    flops = 0.0
    bytes_acc = 0.0
    coll = {k: {"bytes": 0.0, "count": 0.0} for k in COLLECTIVES}
    axis_bytes = {"model": 0.0, "data": 0.0, "mixed": 0.0, "unknown": 0.0}

    for cname, ops in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        is_fusion_body = cname in fusion_targets
        for op in ops:
            base = op.op.replace("-start", "").replace("-done", "")
            if base in COLLECTIVES and not op.op.endswith("-done"):
                coll[base]["bytes"] += op.result_bytes * m
                coll[base]["count"] += m
                axis_bytes[op.axis or "unknown"] += op.result_bytes * m
            if op.op == "dot":
                k = 1
                if op.contract_dims and op.operands:
                    lhs = shapes.get((cname, op.operands[0]))
                    if lhs and lhs[0][1]:
                        for dim in op.contract_dims:
                            if dim < len(lhs[0][1]):
                                k *= lhs[0][1][dim]
                numel = 0
                for _, shp in op.result_shapes:
                    n = 1
                    for d in shp:
                        n *= d
                    numel += n
                flops += 2.0 * numel * k * m
            if not is_fusion_body and op.op not in _SKIP_BYTES_OPS:
                b = op.result_bytes
                for o in op.operands:
                    info = shapes.get((cname, o))
                    if info:
                        for dt, shp in info:
                            n = 1
                            for d in shp:
                                n *= d
                            b += n * _DTYPE_BYTES.get(dt, 0)
                bytes_acc += b * m
    return {"flops": flops, "bytes": bytes_acc, "collectives": coll,
            "collective_axis_bytes": axis_bytes}


def roofline(flops, bytes_accessed, coll, *, device_kind,
             seconds_scale=1.0):
    """Three roofline terms in seconds on ``device_kind`` (optionally
    scaled, e.g. 1/tau to amortize a fused round over its local steps)."""
    pk = peaks(device_kind)
    total_coll = sum(v["bytes"] for v in coll.values())
    terms = {
        "compute_s": flops / pk["flops"] * seconds_scale,
        "memory_s": bytes_accessed / pk["hbm_bw"] * seconds_scale,
        "collective_s": total_coll / pk["ici_bw"] * seconds_scale,
    }
    terms["bottleneck"] = max(
        [k for k in terms if k.endswith("_s")], key=lambda k: terms[k])
    return terms


def overlap_model(terms, axis_bytes, *, device_kind, R=8,
                  seconds_scale=1.0):
    """Modeled round time per overlap mode against the comm/compute
    crossover (DESIGN.md §Overlap).

    The consensus traffic is the worker-axis ("data") collective payload:
    the worker-row all-gather (O(R x n_local) bytes) plus the (R, R)
    partial-Gram psum. Tensor-parallel ("model"-axis) collectives fire
    INSIDE the local steps and are serial with compute in every mode.
    Per round, with ``work = compute_s + memory_s`` the overlappable
    window:

    * ``exact``      — all consensus traffic lands serially at the
      boundary:          ``work + model_s + data_s``
    * ``staleness1`` — the stale (R, R) psum hides behind the scan, but
      the FRESH worker-row gather (the delta is applied to the gathered
      view) stays on the boundary critical path:
                         ``work + model_s + max(data_s - psum_s, 0)
                          + max(psum_s - work, 0)``
    * ``doublebuf``  — gather AND psum belong to the round-(k-1) snapshot
      and dispatch chunk-by-chunk under the scan; the boundary is local:
                         ``work + model_s + max(data_s - work, 0)``
    * ``staleness_k`` — the doublebuf recursion generalized to a k-deep
      snapshot ring whose worker-row gather runs as a ppermute ring
      (R-1 hops of one row each instead of one bisection-limited
      all-gather). Each hop moves ``gather_bytes / R`` and the ring's
      wire time is ``ring_s = data_s * (R-1)/R``; with k rounds of
      compute to hide it behind:
                         ``work + model_s + max(ring_s - k*work, 0)``

    ``crossover = data_s / work``: below 1 the double-buffered round hides
    its entire consensus cost; above 1 the round is communication-bound
    and hiding saturates at the compute window — which staleness-k widens
    k-fold. ``psum_s`` uses the engine's (R, R) fp32 payload.

    Returned ring fields: ``gather_bytes`` (the worker-axis consensus
    payload), ``ring_bytes_per_hop = gather_bytes / R`` (structurally
    <= gather_bytes), ``ring_hops = R - 1``, ``ring_s``, and
    ``staleness_k_s`` — a ``{str(k): seconds}`` dict for k in {1, 2, 4}.
    By construction ``staleness_k_s[k] <= doublebuf_s <= staleness1_s <=
    exact_s`` (check_bench pins the ordering on the committed records).
    """
    ici_bw = peaks(device_kind)["ici_bw"]
    work = terms["compute_s"] + terms["memory_s"]
    model_s = axis_bytes.get("model", 0.0) / ici_bw * seconds_scale
    gather_bytes = (axis_bytes.get("data", 0.0)
                    + axis_bytes.get("mixed", 0.0)
                    + axis_bytes.get("unknown", 0.0))
    data_s = gather_bytes / ici_bw * seconds_scale
    psum_s = min(R * R * 4 / ici_bw * seconds_scale, data_s)
    ring_s = data_s * (R - 1) / max(R, 1)
    rows = {
        "exact_s": work + model_s + data_s,
        "staleness1_s": (work + model_s + max(data_s - psum_s, 0.0)
                         + max(psum_s - work, 0.0)),
        "doublebuf_s": work + model_s + max(data_s - work, 0.0),
        "gather_bytes": gather_bytes,
        "ring_bytes_per_hop": gather_bytes / max(R, 1),
        "ring_hops": R - 1,
        "ring_s": ring_s,
        "staleness_k_s": {str(k): work + model_s + max(ring_s - k * work,
                                                       0.0)
                          for k in (1, 2, 4)},
    }
    rows["crossover"] = data_s / work if work > 0 else float("inf")
    rows["overlap_gain"] = (rows["exact_s"] / rows["doublebuf_s"]
                            if rows["doublebuf_s"] > 0 else 1.0)
    return rows


def probe_round_model(*, work_s_per_step: float, tau: int,
                      gather_bytes: float, device_kind: str, R: int = 8,
                      mode: str = "none", staleness: int = 1) -> float:
    """One overlap mode's modeled round seconds for an autotune probe
    (``train/autotune.py``): tau local steps of ``work_s_per_step``
    against a ``gather_bytes`` worker-axis consensus payload, routed
    through ``overlap_model`` so probes, the microbench's ``modeled_us``,
    and the committed roofline tables share ONE formula set. Pure
    arithmetic — structural for check_bench. ValueError on an unknown
    mode (user-facing via ``--overlap``)."""
    if mode not in ("none", "staleness1", "doublebuf", "staleness_k"):
        raise ValueError(f"unknown overlap mode {mode!r}")
    if tau < 1:
        raise ValueError(f"tau must be >= 1, got {tau}")
    if staleness < 1:
        raise ValueError(f"staleness must be >= 1, got {staleness}")
    rows = overlap_model(
        {"compute_s": work_s_per_step * tau, "memory_s": 0.0},
        {"data": float(gather_bytes)}, device_kind=device_kind, R=R)
    if mode == "none":
        return rows["exact_s"]
    if mode == "staleness1":
        return rows["staleness1_s"]
    if mode == "doublebuf":
        return rows["doublebuf_s"]
    by_k = rows["staleness_k_s"].get(str(staleness))
    if by_k is not None:
        return by_k
    work = work_s_per_step * tau
    return work + max(rows["ring_s"] - staleness * work, 0.0)


def reconcile_probes(pairs):
    """Model-vs-measured reconciliation for the autotune search:
    ``pairs`` yields (measured_us, modeled_us). Returns the median
    measured/modeled ratio as the calibration ``scale`` (a single
    positive scale never changes a per-sample-score argmin, so the
    chosen point stays a deterministic function of the feasibility
    frontier), plus the worst-case log residual AFTER calibration —
    how far any probe sits from the scaled model, the TunePlan's
    model-quality record. Empty/degenerate input -> identity scale."""
    import math as _math
    ratios = sorted(m / md for m, md in pairs if md > 0 and m > 0)
    if not ratios:
        return {"scale": 1.0, "max_abs_log_residual": 0.0, "n": 0}
    n = len(ratios)
    if n % 2:
        scale = ratios[n // 2]
    else:
        scale = 0.5 * (ratios[n // 2 - 1] + ratios[n // 2])
    worst = max(abs(_math.log(r / scale)) for r in ratios)
    return {"scale": scale, "max_abs_log_residual": worst, "n": n}


def model_flops(cfg, shape, *, mode: str) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); decode D = batch
    tokens (1 new token per sequence). Global, all chips."""
    n = cfg.active_param_count()
    if mode in ("train", "ddp"):
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch  # decode: one token per sequence
    return 2.0 * n * tokens


def serving_model(cfg, *, max_slots: int, chunk: int,
                  state_bytes_per_slot: float, device_kind: str,
                  dtype_bytes: int = 2):
    """Prefill-vs-decode roofline for the continuous-batching engine
    (DESIGN.md §Serving).

    Decode is the memory-bound regime: one token per active slot reads
    EVERY live parameter plus each slot's decode state (read + write), so
    arithmetic intensity grows with slot occupancy and the engine only
    turns compute-bound past ``crossover_slots``. A prefill chunk is the
    compute-bound regime: C tokens of one request against one slot's
    state. ``prefill_tokens_per_decode_step`` — how many chunked-prefill
    tokens cost the same as ONE full decode step — is the admission-
    packing guidance: below it, admitting mid-decode is (roofline-)free.

    ``state_bytes_per_slot`` must be MEASURED from a blank request state
    pytree (benchmarks/bench_serving.py does), not guessed from shapes.
    Pure arithmetic — structural for check_bench.
    """
    if max_slots < 1:
        raise ValueError(f"max_slots must be >= 1, got {max_slots}")
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    pk = peaks(device_kind)
    peak_flops, hbm_bw = pk["flops"], pk["hbm_bw"]
    n_act = cfg.active_param_count()
    param_bytes = cfg.param_count() * dtype_bytes

    dec_compute = 2.0 * n_act * max_slots / peak_flops
    dec_memory = (param_bytes
                  + 2.0 * max_slots * state_bytes_per_slot) / hbm_bw
    decode_s = max(dec_compute, dec_memory)

    pre_compute = 2.0 * n_act * chunk / peak_flops
    pre_memory = (param_bytes + 2.0 * state_bytes_per_slot) / hbm_bw
    prefill_s = max(pre_compute, pre_memory)

    # slots needed before a decode step stops being a parameter stream
    denom = 2.0 * n_act / peak_flops - 2.0 * state_bytes_per_slot / hbm_bw
    crossover = (param_bytes / hbm_bw) / denom if denom > 0 else float("inf")

    return {
        "params_bytes": float(param_bytes),
        "state_bytes_per_slot": float(state_bytes_per_slot),
        "decode_s": decode_s,
        "decode_bound": "compute" if dec_compute >= dec_memory else "memory",
        "decode_tok_s": max_slots / decode_s,
        "prefill_s": prefill_s,
        "prefill_bound": "compute" if pre_compute >= pre_memory else "memory",
        "prefill_tok_s": chunk / prefill_s,
        "crossover_slots": crossover,
        "prefill_tokens_per_decode_step": decode_s / (prefill_s / chunk),
    }


DISK_BW = 1.2e9  # checkpoint restore stream (NVMe-class sequential read)


def supervisor_model(*, rounds: int, tau: int, work_s_per_step: float,
                     gather_bytes: float, device_kind: str, R: int = 8,
                     staleness: int = 1,
                     degraded_rounds: int = 0, retried_rounds: int = 0,
                     restores: int = 0, restore_bytes: float = 0.0,
                     backoff_s: float = 0.0):
    """Fault-timeline accounting for the round supervisor
    (``train/supervisor.py``), priced with the same ``probe_round_model``
    formula set the autotuner and microbench use.

    A healthy staleness-k round costs ``round_s`` (tau local steps plus
    whatever ring-gather tail the k-deep carry could not hide). The
    supervisor's recovery actions then perturb the timeline three ways:

    * a DEGRADED round (below quorum, ``sync=0``) skips the consensus
      application, so its boundary never waits on the ring tail — it
      costs only the ``tau * work_s_per_step`` local window and SAVES
      ``round_s - local_s`` against the healthy price;
    * a RETRIED round (failed step, restored, replayed) re-executes in
      full — one extra ``round_s`` each, plus the restore's checkpoint
      read (``restore_bytes / DISK_BW`` per restore);
    * deterministic backoff sleeps add straight wall time (``backoff_s``
      totals them; CI runs on virtual time and passes 0).

    Returns fault-free vs faulted wall seconds and the net overhead
    fraction. Pure arithmetic — structural for check_bench; all guards
    ValueError (python -O)."""
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    if not 0 <= degraded_rounds <= rounds:
        raise ValueError(
            f"degraded_rounds must be in [0, rounds], got "
            f"{degraded_rounds} of {rounds}")
    if retried_rounds < 0 or restores < 0:
        raise ValueError(
            f"retried_rounds ({retried_rounds}) and restores ({restores}) "
            "must be >= 0")
    if restore_bytes < 0 or backoff_s < 0:
        raise ValueError(
            f"restore_bytes ({restore_bytes}) and backoff_s ({backoff_s}) "
            "must be >= 0")
    round_s = probe_round_model(
        work_s_per_step=work_s_per_step, tau=tau,
        gather_bytes=gather_bytes, device_kind=device_kind, R=R,
        mode="staleness_k", staleness=staleness)
    local_s = work_s_per_step * tau
    fault_free_s = rounds * round_s
    degraded_saved_s = degraded_rounds * (round_s - local_s)
    restore_s = restores * (float(restore_bytes) / DISK_BW)
    retry_s = retried_rounds * round_s
    faulted_s = (fault_free_s - degraded_saved_s + retry_s + restore_s
                 + float(backoff_s))
    out = {
        "round_s": round_s,
        "local_s": local_s,
        "fault_free_s": fault_free_s,
        "degraded_saved_s": degraded_saved_s,
        "retry_s": retry_s,
        "restore_s": restore_s,
        "backoff_s": float(backoff_s),
        "faulted_s": faulted_s,
        "overhead_frac": (faulted_s / fault_free_s - 1.0
                          if fault_free_s > 0 else 0.0),
    }
    return {k: round(v, 6) for k, v in out.items()}


# retained for backward compatibility with simple parsing callers
def collective_bytes(hlo_text: str):
    return analyze_hlo(hlo_text)["collectives"]

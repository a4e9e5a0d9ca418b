"""Roofline-term extraction from the compiled dry-run artifact.

Three terms per (arch × shape × mesh), in seconds (lower-bound estimates):

    compute    = HLO_FLOPs_total / (chips × peak_FLOP/s)
    memory     = HLO_bytes_total / (chips × HBM_bw)
    collective = wire_bytes_total / (chips × link_bw)

Methodology — all three terms are derived from the *optimized HLO text* of
the compiled SPMD module (one device's program), weighted by while-loop trip
counts.  ``compiled.cost_analysis()`` is NOT trusted for looped programs: the
XLA CPU cost model counts a while body ONCE regardless of its trip count
(verified experimentally), which under-counts scanned-layer models by ~the
layer count.  Instead:

* **FLOPs** — every ``dot`` op contributes 2 × prod(result dims) ×
  prod(contracting dims), times the product of enclosing while trip counts
  (trip counts recovered from the loop-condition constants).  Elementwise
  FLOPs are ignored: matmul-dominated workloads, stated lower bound.
* **bytes** — per top-level instruction (post-fusion!), result bytes +
  operand bytes, skipping bookkeeping ops (tuple/gte/bitcast/parameter/
  constant) and fusion-internal instructions — i.e. an HBM-traffic model of
  the fused module, trip-weighted.
* **collective wire bytes** — per collective op, ring-model wire bytes:
    all-gather          result × (N−1)/N
    all-reduce          operand × 2(N−1)/N
    reduce-scatter      operand × (N−1)/N
    all-to-all          operand × (N−1)/N
    collective-permute  operand
  with N the participating group size, trip-weighted.

``cost_analysis`` numbers are still recorded (``xla_*``) for reference.

Hardware constants come from :data:`PEAKS`, keyed by the chip's
``device_kind``; a kind not in the table is an error, never a default.
The dry run models a TPU v5e (``"TPU v5 lite"``) whatever host compiles it.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional



@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    ici_bw: float  # bytes/s per inter-chip link (one link charged per chip)


#: Published per-chip peaks by ``jax.Device.device_kind``.  TPU v5e: Google
#: Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM,
#: 1,600 Gbit/s of inter-chip interconnect (charged here as one ~50 GB/s
#: link per chip: conservative).
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

#: The chip the dry run models.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

_DTYPE_BYTES = {
    "pred": 1,
    "s4": 0.5,
    "u4": 0.5,
    "s8": 1,
    "u8": 1,
    "s16": 2,
    "u16": 2,
    "s32": 4,
    "u32": 4,
    "s64": 8,
    "u64": 8,
    "f8e4m3fn": 1,
    "f8e5m2": 1,
    "bf16": 2,
    "f16": 2,
    "f32": 4,
    "f64": 8,
    "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: dict[str, int]  # static op counts (loop bodies counted once)
    wire_bytes: dict[str, float]  # trip-count-weighted wire bytes

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())


_WHILE_RE = re.compile(
    r"\bwhile\(.*?\),\s*condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)"
)
_COMP_START_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?.*\{\s*(?:/\*.*\*/)?$"
)
_CONST_RE = re.compile(r"constant\((\d+)\)")
_CALLS_RE = re.compile(
    r"(?:calls|to_apply|condition|body|true_computation|false_computation)=%?([\w\.\-]+)"
)
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
_OPNAME_RE = re.compile(r"\s([a-z][a-z0-9\-_\.]*)\(")
_LEAF_TYPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_BOOKKEEPING_OPS = {
    "parameter",
    "constant",
    "tuple",
    "get-tuple-element",
    "bitcast",
    "while",
    "conditional",
    "call",
    "after-all",
    "iota",
    "partition-id",
    "replica-id",
}


def _collective_bytes_of_line(line: str):
    """Ring-model wire bytes from the collective's RESULT type.

    Operand types are not printed inline at call sites in optimized HLO text,
    but every collective's wire traffic is derivable from its result:
    all-reduce/all-to-all/permute results equal their operands; a
    reduce-scatter's operand is result × N.
    """
    m = _COLLECTIVE_RE.search(line)
    if not m:
        return None
    op = m.group(1)
    n = 1
    g = _GROUPS_RE.search(line)
    if g:
        n = len([x for x in g.group(1).split(",") if x.strip() != ""])
    else:
        gi = _GROUPS_IOTA_RE.search(line)
        if gi:
            n = int(gi.group(2))
    n = max(n, 2)
    lhs, rhs = line.split("=", 1)
    result_part = rhs[: m.end() - len(lhs) - 1]
    result_bytes = _shape_bytes(result_part)
    ring = (n - 1) / n
    if op == "all-gather":
        b = result_bytes * ring
    elif op == "all-reduce":
        b = result_bytes * 2 * ring
    elif op == "reduce-scatter":
        b = result_bytes * (n - 1)  # operand = result × N
    elif op == "all-to-all":
        b = result_bytes * ring
    else:  # collective-permute
        b = result_bytes
    return op, b


def _split_computations(hlo_text: str) -> dict[str, list[str]]:
    """Computation name → its lines (coarse brace-depth split)."""
    comps: dict[str, list[str]] = {}
    cur: Optional[str] = None
    depth = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if cur is None:
            if stripped.endswith("{") and ("=" not in stripped.split("(")[0]):
                m = _COMP_START_RE.match(stripped)
                if m:
                    cur = m.group(1)
                    comps[cur] = []
                    depth = 1
            continue
        depth += stripped.count("{") - stripped.count("}")
        if depth <= 0:
            cur = None
            continue
        comps[cur].append(line)
    return comps


@dataclasses.dataclass
class HloAnalysis:
    flops: float  # trip-weighted dot FLOPs (per device)
    hbm_bytes: float  # trip-weighted post-fusion traffic (per device)
    collectives: CollectiveStats
    num_dots: int


def _parse_module(hlo_text: str):
    """Split into computations, build shape map, edges, trip multipliers."""
    comps = _split_computations(hlo_text)

    shapes: dict[str, list[tuple[str, list[int]]]] = {}
    parsed: dict[str, list[tuple[str, str, str]]] = {}  # comp → [(name, op, line)]
    while_edges: dict[str, list[tuple[str, str]]] = {}
    call_edges: dict[str, list[str]] = {}
    called_as_fusion: set[str] = set()

    for cname, lines in comps.items():
        instrs = []
        wh = []
        calls = []
        for line in lines:
            m = _INSTR_RE.match(line)
            if not m:
                continue
            name, rhs = m.group(1), m.group(2)
            opm = _OPNAME_RE.search(" " + rhs)
            if not opm:
                continue
            op = opm.group(1)
            type_str = rhs[: opm.start()]
            leaves = [
                (dt, [int(x) for x in dims.split(",") if x])
                for dt, dims in _LEAF_TYPE_RE.findall(type_str)
            ]
            shapes[name] = leaves
            instrs.append((name, op, line))
            w = _WHILE_RE.search(line)
            if w:
                wh.append((w.group(1), w.group(2)))
            for ref in _CALLS_RE.findall(line):
                if "condition=" not in line or ref not in (w.groups() if w else ()):
                    calls.append(ref)
                if f"calls={ref}" in line or f"calls=%{ref}" in line:
                    called_as_fusion.add(ref)
                if f"to_apply={ref}" in line or f"to_apply=%{ref}" in line:
                    called_as_fusion.add(ref)
        parsed[cname] = instrs
        while_edges[cname] = wh
        call_edges[cname] = calls

    def trip_count(cond_name: str) -> int:
        best = 1
        for line in comps.get(cond_name, []):
            for c in _CONST_RE.findall(line):
                best = max(best, int(c))
        return best

    # Multiplier propagation: roots = computations never referenced.
    referenced = set(called_as_fusion)
    for whs in while_edges.values():
        for cond, body in whs:
            referenced.add(cond)
            referenced.add(body)
    for cs in call_edges.values():
        referenced.update(cs)
    mult: dict[str, float] = {n: 1.0 for n in comps if n not in referenced}
    frontier = list(mult)
    while frontier:
        nxt = []
        for name in frontier:
            base = mult.get(name, 1.0)
            for cond, body in while_edges.get(name, []):
                m = base * trip_count(cond)
                for tgt in (body, cond):
                    if mult.get(tgt, 0.0) < m:
                        mult[tgt] = m
                        nxt.append(tgt)
            for ref in call_edges.get(name, []):
                if mult.get(ref, 0.0) < base:
                    mult[ref] = base
                    nxt.append(ref)
        frontier = nxt
    return comps, parsed, shapes, mult, called_as_fusion


def _bytes_of_leaves(leaves: list[tuple[str, list[int]]]) -> float:
    total = 0.0
    for dt, dims in leaves:
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def analyze_hlo(hlo_text: str) -> HloAnalysis:
    comps, parsed, shapes, mult, fusion_bodies = _parse_module(hlo_text)

    flops = 0.0
    hbm = 0.0
    ndots = 0
    counts: dict[str, int] = {}
    wire: dict[str, float] = {}

    # Def-op map: which op produced each value (loop-param detection below).
    def_op: dict[str, str] = {}
    for instrs in parsed.values():
        for name, op, _ in instrs:
            def_op[name] = op

    for cname, instrs in parsed.items():
        m = mult.get(cname, 1.0)
        in_fusion = cname in fusion_bodies
        for name, op, line in instrs:
            # -- FLOPs: dots everywhere (incl. fusion bodies) ---------------
            if op == "dot":
                operands = _OPERAND_RE.findall(
                    line.split(op + "(", 1)[1].split(")", 1)[0]
                )
                result = 1
                for _, dims in shapes.get(name, []):
                    for d in dims:
                        result *= d
                contract = 1
                cd = _CDIMS_RE.search(line)
                if cd and operands:
                    lhs_leaves = shapes.get(operands[0], [])
                    if lhs_leaves:
                        lhs_dims = lhs_leaves[0][1]
                        for idx in cd.group(1).split(","):
                            if idx and int(idx) < len(lhs_dims):
                                contract *= lhs_dims[int(idx)]
                flops += 2.0 * result * contract * m
                ndots += 1
            # -- collectives -------------------------------------------------
            got = _collective_bytes_of_line(line)
            if got:
                cop, b = got
                counts[cop] = counts.get(cop, 0) + 1
                wire[cop] = wire.get(cop, 0.0) + b * m
            # -- HBM traffic: top-level (non-fusion-body) instructions ------
            if not in_fusion and op not in _BOOKKEEPING_OPS:
                b = _bytes_of_leaves(shapes.get(name, []))
                if op == "fusion" and "dynamic-update-slice" in name:
                    # In-place scatter into a loop-carried buffer (scan ys
                    # stacking): physical traffic is the updated window, not
                    # the full buffer — count 2× the small operands only.
                    res = b
                    small = 0.0
                    arg_seg = line.split("(", 1)
                    if len(arg_seg) > 1:
                        end = arg_seg[1].find(")")
                        for oname in _OPERAND_RE.findall(
                            arg_seg[1][: end if end > 0 else None]
                        ):
                            ob = _bytes_of_leaves(shapes.get(oname, []))
                            if 0 < ob < res / 4:
                                small += ob
                    hbm += 2.0 * small * m
                    continue
                if op == "dynamic-slice":
                    # Reads only the slice (= result), not the operand array;
                    # counting the operand inflates scan-sliced xs by the trip
                    # count (measured 40× on per-timestep recurrences).
                    b *= 2.0  # read slice + write result
                elif op == "dynamic-update-slice":
                    # Reads + writes the updated window only (in-place alias).
                    upd = 0.0
                    arg_seg = line.split("(", 1)
                    if len(arg_seg) > 1:
                        end = arg_seg[1].find(")")
                        ops_ = _OPERAND_RE.findall(
                            arg_seg[1][: end if end > 0 else None]
                        )
                        if len(ops_) >= 2:
                            upd = _bytes_of_leaves(shapes.get(ops_[1], []))
                    b = 2.0 * upd if upd else b
                else:
                    arg_seg = line.split("(", 1)
                    if len(arg_seg) > 1:
                        end = arg_seg[1].find(")")
                        for oname in _OPERAND_RE.findall(
                            arg_seg[1][: end if end > 0 else None]
                        ):
                            ob = _bytes_of_leaves(shapes.get(oname, []))
                            if m > 1 and def_op.get(oname) in (
                                "parameter",
                                "get-tuple-element",
                            ):
                                # Loop-carried / xs buffer: each element is
                                # touched once across the loop, not in full
                                # per iteration (a stacked-weights or
                                # timestep-xs array would otherwise count
                                # trips× too much traffic).
                                ob = ob / m
                            b += ob
                hbm += b * m
    return HloAnalysis(
        flops=flops,
        hbm_bytes=hbm,
        collectives=CollectiveStats(counts=counts, wire_bytes=wire),
        num_dots=ndots,
    )


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Trip-count-aware collective accounting (see analyze_hlo)."""
    return analyze_hlo(hlo_text).collectives


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops_per_device: float
    hlo_bytes_per_device: float
    wire_bytes_per_device: float
    model_flops: float
    compute_s: float
    memory_s: float
    collective_s: float
    collectives: dict[str, int]
    bytes_per_device_hbm: Optional[float] = None  # from memory_analysis

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)  # type: ignore[arg-type]

    @property
    def useful_flops_ratio(self) -> float:
        total = self.hlo_flops_per_device * self.chips
        return self.model_flops / total if total > 0 else float("nan")

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "hlo_flops_total": self.hlo_flops_per_device * self.chips,
            "useful_ratio": self.useful_flops_ratio,
            "wire_bytes_per_device": self.wire_bytes_per_device,
            "hbm_bytes_per_device": self.bytes_per_device_hbm,
            "collectives": self.collectives,
        }


def model_flops_estimate(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active params."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def build_report(
    *,
    arch: str,
    shape,
    cfg,
    mesh_name: str,
    chips: int,
    compiled,
    hlo_text: Optional[str] = None,
) -> RooflineReport:
    peaks = chip_peaks(DRYRUN_DEVICE_KIND)
    text = hlo_text if hlo_text is not None else compiled.as_text()
    analysis = analyze_hlo(text)
    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = float(
            getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
        )
    except Exception:
        pass
    return RooflineReport(
        arch=arch,
        shape=shape.name,
        mesh=mesh_name,
        chips=chips,
        hlo_flops_per_device=analysis.flops,
        hlo_bytes_per_device=analysis.hbm_bytes,
        wire_bytes_per_device=analysis.collectives.total_wire_bytes,
        model_flops=model_flops_estimate(cfg, shape),
        compute_s=analysis.flops / peaks.flops,
        memory_s=analysis.hbm_bytes / peaks.hbm_bw,
        collective_s=analysis.collectives.total_wire_bytes / peaks.ici_bw,
        collectives=analysis.collectives.counts,
        bytes_per_device_hbm=mem,
    )

"""Reduction of a profiler trace (``*.xplane.pb``) to device numbers.

Read with ``jax.profiler.ProfileData``: device planes (``/device:TPU:n``)
carry the operations that ran on each chip on their ``XLA Ops`` line; the
host plane carries the benchmark's own spans (``TraceAnnotation``), on the
same clock.  The traced window is the benchmark's ``window`` span.

* busy: the union of the operation intervals inside the window, averaged
  over the chips that ran any; idle share = 1 - busy / window;
* device_ops: device self seconds per operation (a ``while`` loop's own
  time, not its body's), named ``module:instruction`` (the jitted program
  an operation ran in, from the ``XLA Modules`` line, and its HLO
  instruction name without the numeric suffix);
* idle_gaps: idle seconds attributed to the benchmark span the host was in
  (``other`` where it was in none);
* kernels: the operations whose instruction name marks a kernel of
  :data:`KERNELS`, with the shapes of their operands.

An operation's event name on a TPU is its HLO text,
``%name.N = <result shape> custom-call(<operand shape> %x, ...), ...``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

#: HLO instruction names of the engine's Pallas kernels (the ``pallas_call``
#: custom calls keep their jitted function's name, also when inlined into a
#: larger program, as the radix passes of the keyed running sums are).
KERNELS = {
    "keygroup_partition": "keygroup_partition_pallas",
    "bucket_argsort": "bucket_argsort_pallas",
}
DEVICE_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
_SHAPE = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def instruction(op_text: str) -> str:
    """``%fusion.12 = ...`` → ``fusion``."""
    head = op_text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def kernel_of(op_text: str):
    name = instruction(op_text)
    for kernel, mark in KERNELS.items():
        if name == mark:
            return kernel
    return None


def operand_shapes(op_text: str) -> list:
    """Dimensions of each operand of an HLO instruction's text."""
    if "(" not in op_text or " = " not in op_text:
        return []
    rhs = op_text.split(" = ", 1)[1]
    # The operand list: the parenthesised group after the opcode.
    m = re.search(r"[a-z-]+\((.*?)\)(,|$)", rhs)
    if not m:
        return []
    return [
        tuple(int(d) for d in dims.split(",") if d)
        for dims in _SHAPE.findall(m.group(1))
    ]


def _merge(iv: list) -> list:
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _overlap(a: list, b: list) -> float:
    """Total overlap of two sorted, disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def reduce(pd, span_names, window: str = "window") -> dict:
    """Device numbers of the traced window; ``None`` fields where the trace
    holds nothing to read."""
    spans: dict[str, list] = {n: [] for n in span_names}
    spans.setdefault(window, [])
    device: dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns)
                        )
        elif plane.name.startswith("/device:"):
            mods = []
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    mods = sorted(
                        (ev.start_ns, ev.name.split("(")[0]) for ev in line.events
                    )
            starts = [m[0] for m in mods]
            for line in plane.lines:
                if line.name != DEVICE_LINE:
                    continue
                evs = device.setdefault(plane.name, [])
                for ev in line.events:
                    i = bisect.bisect_right(starts, ev.start_ns) - 1
                    mod = mods[i][1] if i >= 0 else "?"
                    evs.append((mod, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    if not spans[window]:
        return {"window_s": None, "busy_s": None}
    lo, hi = min(spans[window])[0], max(e for _, e in spans[window])
    busy_per_chip, ops, kernels = [], {}, []
    merged_all = []
    for evs in device.values():
        iv = []
        stack = []  # enclosing operations: [end, name, self time]
        for mod, text, s, e in sorted(evs, key=lambda x: (x[2], -x[3])):
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            iv.append((s, e))
            while stack and stack[-1][0] <= s:
                _, name, own = stack.pop()
                ops[name] = ops.get(name, 0.0) + own
            if stack:
                stack[-1][2] -= e - s
            stack.append([e, f"{mod}:{instruction(text)}", e - s])
            k = kernel_of(text)
            if k is not None:
                kernels.append((k, operand_shapes(text), s, e))
        for _, name, own in stack:
            ops[name] = ops.get(name, 0.0) + own
        if iv:
            merged = _merge(iv)
            busy_per_chip.append(sum(e - s for s, e in merged))
            merged_all = merged if not merged_all else merged_all
    window_ns = hi - lo
    busy = sum(busy_per_chip) / len(busy_per_chip) if busy_per_chip else 0.0
    # Idle gaps of the first chip that ran anything, by host span.
    gaps, prev = [], lo
    for s, e in merged_all:
        if s > prev:
            gaps.append([prev, s])
        prev = max(prev, e)
    if prev < hi:
        gaps.append([prev, hi])
    idle = {}
    covered = 0.0
    for name, iv in spans.items():
        if name == window or not iv:
            continue
        ov = _overlap(gaps, _merge(iv))
        if ov > 0:
            idle[name] = ov
            covered += ov
    rest = sum(e - s for s, e in gaps) - covered
    if rest > 0:
        idle["other"] = rest
    top = lambda d: [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda x: -x[1])[:10]]  # noqa: E731
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy / 1e9 if busy_per_chip else None,
        "chips": len(busy_per_chip),
        "device_ops": top(ops),
        "idle_gaps": top(idle),
        "kernels": [(k, shapes, (e - s) / 1e9) for k, shapes, s, e in kernels],
        "op_names": len(ops),
    }

"""The program's own spans in a traced window, and the device's idle time
put down to them.

The engine records spans inside its tick and period (``repro.engine.tracing``)
as profiler annotations on its Python thread, the thread that also carries
the benchmark's spans around the calls into each layer
(:data:`chipbench.harness.SPANS`); all of them lie on the device's clock.
:func:`reduce` reads that thread inside the window:

* per program span name: how many, total seconds, and self seconds (the
  total less the time of the spans nested in it);
* ``idle_by_span``: each piece of the device's idle time put down to the
  innermost span open on the host at that moment, a program or benchmark
  span, else ``other``;
* ``fetch_events``: jaxlib's own device→host read events
  (``np.asarray(jax.Array)``) on that thread, to set beside the program's
  ``device_fetches`` count.

Run as a script, it runs one cell traced on the chip and prints one JSON
line: the reduction, the counters and every per-layer reading, including
the readers of program spans below, which ``run.py`` does not take::

    python chipbench/spans.py --workload job3.steady --seed 7 --seconds 30 \\
        [--keep chiprun_out/job3_steady.xplane.pb.gz] [--out chiprun_out/spans.jsonl]

Refuses any platform but a TPU.
"""

from __future__ import annotations

import argparse
import bisect
import gzip
import json
import os
import shutil
import sys
import tempfile

if __name__ == "__main__":  # run as a script: find chipbench and the engine
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import cache, harness, registry, trace  # noqa: E402
from chipbench.verdict import verdict  # noqa: E402

#: The engine's span names, as ``repro.engine.tracing.SPANS`` has them (a
#: test holds the two lists equal).
SPANS = (
    "engine.drain",
    "jit.call",
    "jit.prepare",
    "jit.grow",
    "jit.launch",
    "jit.compile",
    "jit.fetch",
    "sink.collect",
    "engine.flush_outputs",
    "route",
    "route.partition",
    "route.argsort",
    "route.stats",
    "period.sync_store",
    "period.fold",
    "controller.decide",
    "migration.pause",
    "gc",
)
#: jaxlib's host event for one device→host array read.
FETCH_EVENT = "np.asarray(jax.Array)"
#: Per-layer metrics read from the program's spans (``metrics/<name>.py``).
PROGRAM_METRICS = (
    "jit_fetch_ms_per_tick",
    "route_ms_per_tick",
    "route_kernel_ms_per_tick",
    "sink_ms_per_tick",
    "gc_ms_per_tick",
    "sync_store_ms_per_tick",
    "period_fold_ms_per_tick",
    "idle_untraced_share",
)


def _main_thread(pd, window: str):
    """The host line that holds the ``window`` span, and that span."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == window:
                    return line, (ev.start_ns, ev.start_ns + ev.duration_ns)
    return None, None


def _busy(pd, lo: int, hi: int) -> list:
    """Merged operation intervals of the first chip that ran any in
    ``[lo, hi]`` (the chip whose idle gaps ``trace.reduce`` reports)."""
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        clipped = (
            (max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi))
            for line in plane.lines if line.name == trace.DEVICE_LINE
            for ev in line.events
        )
        merged = trace._merge([(s, e) for s, e in clipped if e > s])
        if merged:
            return merged
    return []


def _gaps(busy: list, lo: int, hi: int) -> list:
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if prev < hi:
        gaps.append((prev, hi))
    return gaps


def _innermost(events: list, lo: int, hi: int, stats: dict) -> list:
    """``[lo, hi)`` cut into ``(start, end, name)`` pieces where the
    innermost open span changes (``None``: no span open).  ``events`` are
    ``(start, end, name)`` clipped to the window, sorted by start, outer
    first; each one's self time is added to ``stats[name]``."""
    pieces = []
    stack = []  # open spans: [end, name, self time]
    t = lo

    def close_until(to):
        nonlocal t
        while stack and stack[-1][0] <= to:
            end, name, own = stack.pop()
            stats[name][2] += own
            if end > t:
                pieces.append((t, end, name))
                t = end
        if to > t:
            pieces.append((t, to, stack[-1][1] if stack else None))
            t = to

    for s, e, name in events:
        close_until(s)
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, name, e - s])
        stats[name][0] += 1
        stats[name][1] += e - s
    close_until(hi)
    return pieces


def _overlap_by_name(gaps: list, pieces: list) -> dict:
    """Seconds of ``gaps`` under each piece's name (both sorted, disjoint)."""
    out: dict = {}
    starts = [p[0] for p in pieces]
    for gs, ge in gaps:
        i = max(bisect.bisect_right(starts, gs) - 1, 0)
        while i < len(pieces) and pieces[i][0] < ge:
            ps, pe, name = pieces[i]
            ov = min(ge, pe) - max(gs, ps)
            if ov > 0:
                key = "other" if name is None else name
                out[key] = out.get(key, 0) + ov
            i += 1
    return {k: v / 1e9 for k, v in out.items()}


def reduce(pd, window: str = "window"):
    """The program's spans in the traced window (see the module's doc);
    ``None`` where the trace holds no window or no program span."""
    line, win = _main_thread(pd, window)
    if line is None:
        return None
    lo, hi = win
    names = set(SPANS) | set(harness.SPANS)
    events, fetches = [], 0
    for ev in line.events:
        if ev.name == FETCH_EVENT:
            fetches += lo <= ev.start_ns < hi
        elif ev.name in names:
            s, e = max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi)
            if e > s:
                events.append((s, e, ev.name))
    if not any(name in SPANS for _, _, name in events):
        return None
    events.sort(key=lambda x: (x[0], -x[1]))
    stats = {name: [0, 0, 0] for name in names}  # count, total ns, self ns
    pieces = _innermost(events, lo, hi, stats)
    idle = _overlap_by_name(_gaps(_busy(pd, lo, hi), lo, hi), pieces)
    return {
        "window_s": (hi - lo) / 1e9,
        "spans": {
            name: {"count": c, "total_s": tot / 1e9, "self_s": own / 1e9}
            for name, (c, tot, own) in stats.items()
            if name in SPANS and c
        },
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "fetch_events": fetches,
    }


def ms_per_tick(run, *names):
    """Host milliseconds per window tick in the program spans ``names``
    (``None`` where the run's trace holds no program span)."""
    program = (run.trace or {}).get("program")
    if program is None or not run.window_ticks:
        return None
    spans = program["spans"]
    total = sum(spans[n]["total_s"] for n in names if n in spans)
    return 1e3 * total / run.window_ticks


def window_sum(run, counter: str):
    """A counter the controller records per period, summed over the window
    (``None`` where the program records no such counter)."""
    values = [getattr(p, counter, None) for p in run.periods]
    return None if None in values else sum(values)


def traced_run(workload: str, seed: int, seconds: float, *, keep=None,
               overrides=None) -> dict:
    """Run one cell traced; the line :func:`main` prints (``keep``: gzip the
    trace to this path; ``overrides`` as :func:`harness.execute` takes)."""
    trace_dir = tempfile.mkdtemp(prefix="spans_")
    try:
        run = harness.execute(workload, seed, seconds, trace_dir=trace_dir,
                              overrides=overrides)
        path = trace.find(trace_dir)
        if keep:
            os.makedirs(os.path.dirname(os.path.abspath(keep)), exist_ok=True)
            with open(path, "rb") as src, gzip.open(keep, "wb") as dst:
                shutil.copyfileobj(src, dst)
        pd = trace.load(path)
        run.trace = trace.reduce(pd, harness.SPANS)
        run.trace["program"] = reduce(pd)
        del pd
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    names = [m["name"] for m in registry.cell_metrics(workload, "per_layer")]
    metrics = {}
    for name in names + list(PROGRAM_METRICS):
        value = registry.metric_reader(name)(run)
        if value is not None:
            metrics[name] = value
    correct, attempted, failed, _ = verdict(run)
    return {
        "workload": workload,
        "seed": seed,
        "kind": run.device_kind,
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "window_s": run.t1 - run.t0,
        "window_ticks": run.window_ticks,
        "periods_in_window": len(run.periods),
        "counters_in_window": run.counters,
        # Absent from a program without the counters.
        "device_fetches_in_window": window_sum(run, "device_fetches"),
        "table_grows_in_window": window_sum(run, "table_grows"),
        "metrics": metrics,
        "busy_s": run.trace["busy_s"],
        "idle_gaps": run.trace["idle_gaps"],
        "program": run.trace["program"],
    }


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None, help="gzip the trace to this path")
    ap.add_argument("--out", default=None, help="append the line to this file")
    args = ap.parse_args(argv)
    cache.enable()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"platform {dev.platform!r}: no TPU, refusing to run", file=sys.stderr)
        return 2
    line = traced_run(args.workload, args.seed, args.seconds, keep=args.keep)
    text = json.dumps(line, default=str)
    print(text, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

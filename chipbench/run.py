"""Run one benchmark cell once and print its JSON result line.

    python chipbench/run.py --workload job3.steady --seed 7 --seconds 30 --trace 0

Refuses any platform but a TPU, and fewer chips than the cell asks for,
before it prints a result.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` traces the window with the profiler and reports its
per-layer metrics, the device's busy and window seconds and a breakdown.
Earlier stdout lines are run facts (compiles and cache traffic, counters,
timings); the last stderr lines and the result's last key, ``checks``, give
every number compared with its limit.
"""

from __future__ import annotations

import time

T_SCRIPT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# The TPU runtime logs to /tmp/tpu_logs unless told otherwise: keep its
# logs under this run's own temporary directory.
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))

from chipbench import cache, harness, registry  # noqa: E402
from chipbench.verdict import verdict  # noqa: E402

TRACE_DIR = os.path.join(HERE, ".traces")


def device_check(chips: int):
    """The devices, or ``None`` (with the reason on stderr) where JAX finds
    no TPU or fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[device] platform {devices[0].platform!r}: no TPU, refusing to run",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"[device] {len(devices)} chips, the cell asks for {chips}",
              file=sys.stderr)
        return None
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = T_SCRIPT - harness.process_age_s()

    bench = registry.load_benchmark()
    cell = registry.cell(args.workload, bench)
    entries = cache.entries()
    cache.enable()
    devices = device_check(int(cell["chips"]))
    if devices is None:
        return 2
    mon = cache.monitor()
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(TRACE_DIR, args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = harness.execute(
        args.workload, args.seed, args.seconds,
        trace_dir=trace_dir, t_start=t_start, monitor=mon,
    )
    dev0 = devices[0]
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(devices),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    result = {}
    if args.trace:
        from chipbench import trace as tr

        t_red = time.perf_counter()
        path = tr.find(trace_dir)
        pd = tr.load(path)
        run.trace = tr.reduce(pd, harness.SPANS)
        del pd
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.info["trace_reduce_s"] = time.perf_counter() - t_red
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace["device_ops"],
            "idle_gaps": run.trace["idle_gaps"],
        }
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in registry.cell_metrics(args.workload, kind, bench):
        value = registry.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct, attempted, failed, checks = verdict(run)
    facts = {
        "workload": args.workload,
        "seed": args.seed,
        "window_s": run.t1 - run.t0,
        "window_ticks": run.window_ticks,
        "window_events": list(run.window_events),
        "warmup_events": run.warmup_events,
        "periods_in_window": len(run.periods),
        "migrations_in_window": sum(p.num_migrations for p in run.periods),
        "solver_seconds": [p.solver_seconds for p in run.periods],
        "compiles_in_window": run.compiles,
        "jit_compiles_in_window": run.counters.get("jit_compiles"),
        "counters_in_window": run.counters,
        "cache_dir": cache.cache_dir(),
        "cache_entries_at_start": entries,
        "cache_entries_at_end": cache.entries(),
        **run.info,
    }
    print("[run] " + json.dumps(facts, default=str), flush=True)
    line = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
        **result,
        "checks": checks,
    }
    for name, c in checks.items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings behind the benchmark's limits and rates, in one process.

The program's readings: a cell run on many seeds, each line giving every
number the comparison produced and the device it ran on.  With
``--control``, also the control's on each seed: the plain reference in
float32 put in the program's place, computed from the same seed's events
(as many as the program's run offered, drawn anew from the generator: no
engine runs for it), compared and judged as the program is.  With
``--rates``, the knee sweep of an open-loop cell: one run per rate, each
line telling whether the source's backlog grew.

    python chipbench/readings.py --workload job3.steady --seeds 1,2,3 \\
        --seconds 30 --control --out chiprun_out/readings.jsonl

Refuses any platform but a TPU.  One process keeps the compiled programs,
so only the first run compiles.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from chipbench import cache, harness, registry  # noqa: E402
from chipbench.verdict import judge, verdict  # noqa: E402


def _metrics(run, kind: str) -> dict:
    out = {}
    for m in registry.cell_metrics(run.cell["name"], kind):
        v = registry.metric_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = v
    return out


def control(run) -> tuple[bool, dict]:
    """The float32 control on the events ``run`` offered, judged."""
    config, mix = run.config, run.traffic
    stream = registry.generator(mix["generator"]).Stream(config, mix, run.seed)
    events = stream.columns(0, run.info["offered"])
    ref = registry.reference(config["reference"])
    low, _ = ref.check(config, events, *ref.control(config, events, np.float32))
    correct, checks = judge(config, dict(low, unfinished=0))
    return correct, {k: c["value"] for k, c in checks.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default=None, help="comma-separated events/s")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cache.enable()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"platform {dev.platform!r}: no TPU, refusing to take readings",
              file=sys.stderr)
        return 2
    mon = cache.monitor()
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    out = open(args.out, "a") if args.out else None
    try:
        for rate in rates:
            for seed in seeds:
                overrides = {"traffic": {"rate_per_s": rate}} if rate else None
                t = time.perf_counter()
                run = harness.execute(
                    args.workload, seed, args.seconds, overrides=overrides, monitor=mon
                )
                correct, attempted, failed, checks = verdict(run)
                line = {
                    "workload": args.workload,
                    "seed": seed,
                    "rate": rate,
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "correct": correct,
                    "attempted": attempted,
                    "failed": failed,
                    "program": {k: c["value"] for k, c in checks.items()},
                    "metrics": _metrics(run, "end_to_end"),
                    "per_layer": _metrics(run, "per_layer"),
                    "window_s": run.t1 - run.t0,
                    "window_ticks": run.window_ticks,
                    "compiles_in_window": run.compiles,
                    "run_s": time.perf_counter() - t,
                    **run.info,
                }
                if args.control:
                    t = time.perf_counter()
                    line["control_correct"], line["control"] = control(run)
                    line["control_s"] = time.perf_counter() - t
                text = json.dumps(line, default=str)
                print(text, flush=True)
                if out:
                    out.write(text + "\n")
                    out.flush()
                del run
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

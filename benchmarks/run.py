"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--quick`` shrinks problem
sizes for CI-style runs; the full run reproduces the paper's configurations.
``--json PATH`` additionally writes the rows as a JSON document (the format
``benchmarks/compare.py`` consumes for the CI perf-regression gate).
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time

from repro.compile_cache import enable_compile_cache

# fault_recovery forks worker processes: it runs before any module that
# initializes jax in this process (a forked child must not inherit a live
# backend; on a TPU host the parent holds the chip).
MODULES = [
    "solver_perf",          # Figs 2–4
    "fault_recovery",       # MTTR + tuple loss/duplication under faults
    "engine_throughput",    # data-plane tuples/sec + MILP assembly time
    "integrated_scaling",   # Fig 5
    "milp_vs_flux_potc",    # Figs 6–7
    "unrestricted",         # Figs 8–9
    "albic_vs_cola",        # Figs 10–11
    "real_jobs",            # Figs 12–14
    "skew_grid",            # skew scenarios × mitigation strategies
    "roofline_bench",       # dry-run roofline table (this build)
]


def parse_row(row: str) -> dict:
    """``name,us_per_call,derived`` → {"name", "us_per_call", "derived"}."""
    name, us, derived = row.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def git_sha() -> str:
    try:
        return (
            subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True,
                text=True,
                timeout=10,
            ).stdout.strip()
            or "unknown"
        )
    except Exception:
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sizes")
    ap.add_argument("--only", default=None, help="comma-separated module names")
    ap.add_argument("--json", default=None, help="also write rows as JSON to PATH")
    args = ap.parse_args()
    enable_compile_cache()

    names = args.only.split(",") if args.only else MODULES
    print("name,us_per_call,derived")
    rows: list[dict] = []
    failures = 0
    for name in names:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.perf_counter()
        try:
            for row in mod.run(quick=args.quick):
                print(row, flush=True)
                rows.append(parse_row(row))
        except Exception as e:  # keep the harness going; record the failure
            failures += 1
            print(f"{name}/ERROR,0,{type(e).__name__}:{str(e)[:120]}", flush=True)
        print(
            f"# {name} finished in {time.perf_counter()-t0:.1f}s",
            file=sys.stderr,
            flush=True,
        )
    if args.json:
        doc = {
            "schema": 1,
            "sha": git_sha(),
            "quick": args.quick,
            "modules": names,
            "rows": rows,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()

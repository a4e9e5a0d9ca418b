"""The CI perf-regression gate must fail on real slowdowns and stay quiet
otherwise — including on an injected 25% slowdown (the acceptance scenario
for the benchmark-gated pipeline)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmarks.compare import DEFAULT_THRESHOLD, compare, load_rows, main
from benchmarks.run import parse_row

BASE = {
    "engine_throughput/pipeline_d4_64kg_b512": 2000.0,
    "engine_throughput/milp_assembly_60x1200": 6000.0,
    "solver_perf/fig2_20n_400kg/v20/m20/t2s": 2_000_000.0,
    "albic_vs_cola/fig10": 900.0,  # not a gated module
    "engine_throughput/tiny_row": 10.0,  # below the --min-us noise floor
}


def _doc(rows: dict) -> dict:
    return {
        "schema": 1,
        "rows": [{"name": k, "us_per_call": v, "derived": ""} for k, v in rows.items()],
    }


def test_gate_passes_within_threshold():
    new = {k: v * 1.10 for k, v in BASE.items()}  # 10% < 20% threshold
    gated, regressions = compare(BASE, new)
    assert len(gated) == 4  # albic row not gated
    assert regressions == []


def test_gate_fails_on_injected_25pct_slowdown():
    new = {k: v * 1.25 for k, v in BASE.items()}
    gated, regressions = compare(BASE, new)
    names = {c.name for c in regressions}
    assert "engine_throughput/pipeline_d4_64kg_b512" in names
    assert "engine_throughput/milp_assembly_60x1200" in names
    assert "solver_perf/fig2_20n_400kg/v20/m20/t2s" in names
    # Non-gated module and sub-noise-floor rows never fail the gate.
    assert "albic_vs_cola/fig10" not in names
    assert "engine_throughput/tiny_row" not in names
    assert all(c.ratio > DEFAULT_THRESHOLD for c in regressions)


def test_gate_ignores_renamed_rows_and_improvements():
    new = {
        "engine_throughput/pipeline_d4_64kg_b512": 900.0,  # 2.2x faster
        "engine_throughput/renamed_row": 1.0,
    }
    gated, regressions = compare(BASE, new)
    assert [c.name for c in gated] == ["engine_throughput/pipeline_d4_64kg_b512"]
    assert regressions == []


def test_cli_exit_codes(tmp_path: Path):
    base_p = tmp_path / "baseline.json"
    ok_p = tmp_path / "ok.json"
    slow_p = tmp_path / "slow.json"
    base_p.write_text(json.dumps(_doc(BASE)))
    ok_p.write_text(json.dumps(_doc({k: v * 0.95 for k, v in BASE.items()})))
    slow_p.write_text(json.dumps(_doc({k: v * 1.25 for k, v in BASE.items()})))
    assert main([str(base_p), str(ok_p)]) == 0
    assert main([str(base_p), str(slow_p)]) == 1
    # No comparable rows → distinct exit code so CI misconfig is loud.
    empty_p = tmp_path / "empty.json"
    empty_p.write_text(json.dumps(_doc({})))
    assert main([str(base_p), str(empty_p), "--modules", "does_not_exist"]) == 2


def test_load_rows_roundtrip(tmp_path: Path):
    p = tmp_path / "bench.json"
    p.write_text(json.dumps(_doc(BASE)))
    assert load_rows(str(p)) == BASE


def test_derived_us_per_tick_entries_gate_as_sub_rows(tmp_path: Path):
    """``*_us_per_tick`` derived entries load as ``<row>:<key>`` sub-rows and
    regress the gate independently of the row's wall-clock us_per_call."""
    def doc(xchg_us: float) -> dict:
        return {
            "schema": 1,
            "rows": [
                {
                    "name": "engine_throughput/multiworker",
                    "us_per_call": 9000.0,
                    "derived": (
                        f"w2_vs_single=0.25;xchg_us_per_tick={xchg_us};"
                        "xchg_speedup=3.2;xchg_kb_per_tick=104.5"
                    ),
                }
            ],
        }

    base_p = tmp_path / "base.json"
    new_p = tmp_path / "new.json"
    base_p.write_text(json.dumps(doc(200.0)))
    new_p.write_text(json.dumps(doc(300.0)))  # exchange 1.5x slower, row flat

    rows = load_rows(str(base_p))
    assert rows["engine_throughput/multiworker:xchg_us_per_tick"] == 200.0
    assert "engine_throughput/multiworker:xchg_speedup" not in rows  # ratio, not a time

    gated, regressions = compare(rows, load_rows(str(new_p)))
    assert [c.name for c in regressions] == [
        "engine_throughput/multiworker:xchg_us_per_tick"
    ]
    assert main([str(base_p), str(new_p)]) == 1


def test_parse_row_matches_csv_format():
    row = parse_row("engine_throughput/pipeline,4306.5,tuples_per_sec=2377796")
    assert row == {
        "name": "engine_throughput/pipeline",
        "us_per_call": 4306.5,
        "derived": "tuples_per_sec=2377796",
    }
    # derived may itself contain commas (solver rows do)
    row = parse_row("solver_perf/fig2,12.0,a=1;b=2,c=3")
    assert row["derived"] == "a=1;b=2,c=3"


def test_committed_baseline_is_loadable_and_gated():
    """The repo baseline must cover every gated module (CI depends on it) —
    compare.py silently skips rows missing from the baseline, so a refresh
    run with a stale --only list would disarm part of the gate unnoticed."""
    baseline = load_rows(
        str(Path(__file__).parent.parent / "benchmarks" / "baseline.json"),
    )
    modules = {name.split("/", 1)[0] for name in baseline}
    from benchmarks.compare import DEFAULT_MODULES

    for module in DEFAULT_MODULES:
        assert module in modules, f"baseline.json lacks gated module {module!r}"
    # The per-job throughput rows are the gated real_jobs signal.
    for job in ("job1", "job2", "job3", "job4"):
        assert f"real_jobs/{job}_seg_throughput" in baseline


@pytest.mark.slow
def test_quick_run_writes_json(tmp_path: Path):
    """End to end: --json emits a document compare.py can consume."""
    out = tmp_path / "bench.json"
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "benchmarks.run",
            "--quick",
            "--only",
            "engine_throughput",
            "--json",
            str(out),
        ],
        capture_output=True,
        text=True,
        cwd=str(Path(__file__).parent.parent),
        # Minimal env, pinned to the CPU backend: this checks the harness's
        # JSON output, not device numbers, and a subprocess must never take
        # an accelerator its parent may hold.
        env={
            "PYTHONPATH": "src",
            "PATH": "/usr/bin:/bin:/usr/local/bin",
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
        },
        timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = load_rows(str(out))
    assert any(name.startswith("engine_throughput/") for name in rows)

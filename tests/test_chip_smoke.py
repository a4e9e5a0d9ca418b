"""chip_smoke.py: refuses any backend but a TPU, and its engine phases (the
device runs, the recorded-plan replay on the ``.typed()`` reference, and
the comparison) agree at a tiny size on the CPU."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(nodes=4, kgs=8, events=400, periods=3, ticks=3)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
        cwd=REPO,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("job", [2, 3])
def test_job_phase_matches_reference(job):
    info = chip_smoke.phase_job(job, 0, **TINY)
    assert info["migrations"] > 0
    assert info["sink_outputs"] > 0 and info["keygroup_states"] > 0


def test_superstep_phase_fuses_and_matches_reference():
    info = chip_smoke.phase_superstep(0, scan_ticks=3, **TINY)
    assert info["fused_ticks_controller"] > 0
    assert info["fused_ticks"] >= info["fused_ticks_controller"] + 3


def test_compare_rejects_a_diverging_state():
    from repro.data import real_job_2
    from repro.engine import ExecutionConfig

    batches = chip_smoke.airline_batches(0, 2, 200)
    engines = []
    for _ in range(2):
        eng = chip_smoke.make_engine(
            real_job_2(keygroups_per_op=4), ExecutionConfig.typed(), 0, 2
        )
        for keys, values, ts in batches:
            eng.push_source("airline", keys, values, ts)
            eng.tick()
        chip_smoke.drain(eng)
        engines.append(eng)
    dev, ref = engines
    kg, state = next((k, s) for k, s in dev.store.items() if s.get("sums"))
    key = next(iter(state["sums"]))
    state["sums"][key] += 1e-3
    with pytest.raises(AssertionError, match=f"key group {kg}"):
        chip_smoke.compare(dev, ref)

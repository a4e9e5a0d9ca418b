"""The plain reference agrees with the jit tier on small Job 2 and Job 3
runs, migrations included, and the comparison fails its control (the
reference in float32) and every fault a cell can have: state left
unchanged, half of each batch left out, an answer altered where it is
produced.  (One chip: no exchange between chips to leave out.)"""

import numpy as np
import pytest

from chipbench import harness, registry
from chipbench.tests.tiny import tiny
from chipbench.verdict import verdict

STEADY = tiny([[512, 8]])
FLASH = tiny([[512, 4], [128, 1], [256, 1], [512, 2]])


JOB2 = "job2_airline_20n400kg"
JOB3 = "job3_airline_20n400kg"


def _run(config, traffic, overrides, seconds=0.4, seed=2**31 + 5):
    cell = {"name": f"{config}.{traffic}", "config": config, "traffic": traffic}
    return harness.execute(cell, seed, seconds, overrides=overrides)


@pytest.mark.parametrize(
    "config,traffic,overrides",
    [(JOB2, "steady", STEADY), (JOB3, "steady", STEADY), (JOB3, "flash", FLASH)],
)
def test_reference_agrees_with_the_jit_tier(config, traffic, overrides):
    run = _run(config, traffic, overrides)
    correct, attempted, failed, checks = verdict(run)
    assert correct, checks
    assert attempted > 0 and failed == 0
    assert checks["lost"]["value"] == 0 and checks["misplaced"]["value"] == 0
    if traffic == "flash":
        assert sum(p.num_migrations for p in run.periods) > 0


def test_float32_control_fails_and_float64_reference_matches_itself():
    from chipbench import readings

    config = registry.config(JOB3)
    config.update(STEADY["config"])
    mix = dict(registry.traffic("steady"), **STEADY["traffic"])
    stream = registry.generator("airline").Stream(config, mix, 9)
    events = stream.columns(0, 300_000)
    ref = registry.reference("airline")
    limits = config["limits"]
    low, _ = ref.check(config, events, *ref.control(config, events, np.float32))
    assert low["sum_gap"] > 10 * limits["sum_gap"]
    assert low["state_gap"] > 10 * limits["state_gap"]

    class Run:  # what the readings' control takes of a program run
        seed, traffic, info = 9, mix, {"offered": 300_000}

    Run.config = config
    correct, values = readings.control(Run())
    assert not correct
    assert values["sum_gap"] == low["sum_gap"] and values["unfinished"] == 0
    same, bad = ref.check(config, events, *ref.control(config, events, np.float64))
    assert same == {"lost": 0, "misplaced": 0, "sum_gap": 0.0, "state_gap": 0.0}
    assert not bad.any()


def _stale_state(fn):
    def body(state, kgs, starts, ends, keys, values, ts):
        _, out, counts = fn(state, kgs, starts, ends, keys, values, ts)
        return state, out, counts

    return body


def _altered_answer(fn):
    def body(state, kgs, starts, ends, keys, values, ts):
        state, (k, out, t), counts = fn(state, kgs, starts, ends, keys, values, ts)
        out = dict(out, sum=out["sum"].at[0].add(1.0))
        return state, (k, out, t), counts

    return body


def _half_batch(push):
    def push_half(self, op, keys, values, ts):
        n = len(keys) // 2
        push(self, op, keys[:n], values[:n], ts[:n])
        return len(keys)

    return push_half


@pytest.mark.parametrize("fault", ["stale_state", "half_batch", "altered_answer"])
def test_every_fault_comes_out_not_correct(monkeypatch, fault):
    from repro.data import jobs
    from repro.engine import Engine

    if fault == "stale_state":
        monkeypatch.setattr(jobs, "_sum_delay_jit", _stale_state(jobs._sum_delay_jit))
    elif fault == "half_batch":
        monkeypatch.setattr(Engine, "push_source", _half_batch(Engine.push_source))
    else:
        monkeypatch.setattr(jobs, "_route_delay_jit", _altered_answer(jobs._route_delay_jit))
    run = _run(JOB3, "steady", STEADY)
    correct, attempted, failed, checks = verdict(run)
    assert not correct, checks
    assert failed > 0

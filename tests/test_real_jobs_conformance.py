"""The real jobs' fn_seg ports (and their schema-typed columnar edges) must
be bit-identical to the per-run fn, and the SoA queue to the deque oracle,
under every drive pattern.

Each test runs one job through the six execution configurations
(soa+seg+schema+jit+superstep, soa+seg+schema+jit, soa+seg+schema, soa+seg,
soa+fn, deque+fn — see tests/conformance.py) and requires identical tuple
flow, sink outputs,
per-key-group state and SPL statistics (the jit configuration with the
documented float tolerance on reduction-order-sensitive running sums):

* ``steady``   — unconstrained budgets, pure data-plane equivalence;
* ``migrate``  — three random mid-run migrations: tuples buffered in flight,
  queue extraction rebuilds segments non-contiguous, fn_seg must fall back
  to fn without diverging;
* ``pressure`` — a binding service budget (partial drains, cursor
  resumption, mixed seg/fn interleavings) plus one migration.
"""

import numpy as np
import pytest

from conformance import JOBS, Scenario, assert_equivalent, run_configs

SCENARIOS = {
    "steady": Scenario("steady"),
    "migrate": Scenario("migrate", migrate_at=(3, 6, 9)),
    "pressure": Scenario("pressure", service_rate=260.0, migrate_at=(5,), ticks=16),
}


# Jobs with fn_jit-ported operators (job4 extends job3, so it inherits the
# ported flight-delay operators; q17 is NEXmark's, on a seven-column
# table): the +jit configuration must actually exercise the compiled tier
# there (and never anywhere else).
JIT_JOBS = {"job2", "job3", "job4", "pipeline", "q17"}


@pytest.mark.parametrize("scenario", list(SCENARIOS), ids=str)
@pytest.mark.parametrize("job", list(JOBS), ids=str)
def test_job_conformance(job, scenario):
    topo_factory, feeder_factory = JOBS[job]
    results = run_configs(topo_factory, feeder_factory, SCENARIOS[scenario])
    assert_equivalent(results)
    # The production configuration actually exercised the vectorized path
    # and routed schema-typed batches; the oracle configurations stayed on
    # per-run fn / object arrays (equivalence over nothing is vacuous).
    assert results["soa+seg+schema"]["seg_calls"] > 0
    assert results["soa+seg+schema"]["typed_batches"] > 0
    assert results["soa+seg"]["seg_calls"] > 0
    assert results["soa+seg"]["typed_batches"] == 0
    assert results["soa+fn"]["seg_calls"] == 0
    assert results["deque+fn"]["seg_calls"] == 0
    assert results["deque+fn"]["typed_batches"] == 0
    assert results["soa+seg+schema"]["metrics"]["processed_tuples"] > 0
    # Compiled tier: fires exactly on the +jit configuration of ported jobs,
    # with compile counts bounded by padding buckets, not calls.
    jit = results["soa+seg+schema+jit"]
    if job in JIT_JOBS:
        assert jit["jit_calls"] > 0
        assert 0 < jit["jit_compiles"] < jit["jit_calls"]
    else:
        assert jit["jit_calls"] == 0
    assert results["soa+seg+schema"]["jit_calls"] == 0
    assert results["deque+fn"]["jit_calls"] == 0


def test_jobs_produce_sink_output_and_state():
    """The conformance drive is not vacuous: sinks emit and state accretes."""
    for job, (topo_factory, feeder_factory) in JOBS.items():
        res = run_configs(topo_factory, feeder_factory, SCENARIOS["steady"])
        seg = res["soa+seg+schema"]
        assert seg["metrics"]["sink_tuples"] > 0, job
        non_empty = sum(1 for s in seg["states"] if s != ("dict", []))
        assert non_empty > 0, job


def test_migration_actually_interleaved():
    """The migrate scenario really moves key groups mid-run (allocation
    differs from the initial random table) on every configuration."""
    topo_factory, feeder_factory = JOBS["job2"]
    plain = run_configs(topo_factory, feeder_factory, SCENARIOS["steady"])
    moved = run_configs(topo_factory, feeder_factory, SCENARIOS["migrate"])
    assert_equivalent(moved)
    assert moved["soa+seg+schema"]["alloc"] != plain["soa+seg+schema"]["alloc"]


def test_pressure_scenario_is_binding():
    """The backpressure scenario leaves a different drain trajectory than the
    steady one — the budget was really binding somewhere."""
    topo_factory, feeder_factory = JOBS["job4"]
    steady = run_configs(topo_factory, feeder_factory, SCENARIOS["steady"])
    pressed = run_configs(topo_factory, feeder_factory, SCENARIOS["pressure"])
    assert_equivalent(pressed)
    # Same total work eventually drains, but the per-tick interleaving (and
    # hence the number of whole-segment fn_seg calls) must differ.
    seg = "soa+seg+schema"
    assert pressed[seg]["seg_calls"] != steady[seg]["seg_calls"]


def test_normalize_pins_dict_insertion_order():
    """The harness' state comparison is order-sensitive: two dicts with equal
    items in different insertion order are different states (tie-breaks and
    pickle bytes depend on it)."""
    from conformance import normalize

    assert normalize({"a": 1, "b": 2}) != normalize({"b": 2, "a": 1})
    assert normalize({"a": np.int64(1)}) == normalize({"a": 1})


def test_q17_under_albic_migrations():
    """q17 under the controller with ALBIC moving key groups off a loaded
    node: the jit tier's rows and tables equal the per-run oracle's, bit
    for bit, through every migration."""
    from repro.core import AdaptationFramework, AlbicParams
    from repro.data.nexmark import bid_stream, nexmark_q17
    from repro.data.synthetic import StreamSpec
    from repro.engine import Controller, ControllerConfig, Engine, ExecutionConfig

    results = []
    for config in (ExecutionConfig.oracle(), ExecutionConfig.jit()):
        topo = nexmark_q17(keygroups_per_op=12, expected_rows=0)
        alloc = np.zeros(topo.num_keygroups, dtype=np.int64)  # all on node 0
        eng = Engine(topo, 4, config=config, initial_alloc=alloc,
                     service_rate=1e9, seed=0, collect_sinks=True)
        stream = bid_stream(StreamSpec(rate=200.0, seed=8), midnight_bid=900)
        framework = AdaptationFramework(
            mode="albic", max_migrations=4,
            albic_params=AlbicParams(max_ld=10.0, time_limit=0.5),
        )
        ctrl = Controller(
            eng, framework, ControllerConfig(ticks_per_period=3, warmup_periods=0),
            feeder=lambda engine, tick: engine.push_source("bid", *next(stream)),
        )
        migrations = sum(ctrl.period(adapt=True).num_migrations for _ in range(4))
        for _ in range(4):
            eng.tick()
        eng.end_period()
        results.append((migrations, eng.metrics.sink_outputs,
                        [list(s.items()) for _, s in eng.store.items()],
                        eng.router.table.tolist(), eng.metrics.jit_calls))
    (m0, out0, st0, al0, _), (m1, out1, st1, al1, calls) = results
    assert m0 > 0 and calls > 0
    assert (m1, al1) == (m0, al0)
    assert out1 == out0
    assert st1 == st0

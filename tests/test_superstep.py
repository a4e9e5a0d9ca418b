"""The device-resident superstep (engine/superstep.py): fused ticks and the
K-tick scan must be observationally identical to the classic engine.

The conformance matrix (tests/test_real_jobs_conformance.py,
tests/test_conformance_fuzz.py) already pins the ``+superstep``
configuration against every oracle; this module pins the *mechanics*:

* static eligibility (``plan_chain``) accepts exactly the documented shape;
* a fused run really crosses the host boundary once per tick
  (``metrics.jit_host_syncs``), and ``run_supersteps(K)`` once per K ticks;
* migration at a superstep boundary produces byte-identical serialize
  envelopes (``flush_to_host`` materializes device pendings first);
* binding budgets / dead nodes force the classic fallback without any
  divergence;
* ``Engine(use_fn_jit=True, superstep=True)`` over a topology with zero
  ``fn_jit`` operators never imports jax (no x64 flip) — the flag degrades
  to the plain engine.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conformance import (
    METRIC_FIELDS,
    Scenario,
    _int_batches,
    assert_equivalent,
    fuzz_feeders,
    make_fuzz_topology,
    make_pipeline_topo,
    normalize,
    run_configs,
)
from repro.engine import Engine, ExecutionConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _engine(superstep, *, service_rate=1e9, num_nodes=4):
    config = ExecutionConfig.superstep() if superstep else ExecutionConfig.jit()
    return Engine(
        make_pipeline_topo(),
        num_nodes,
        service_rate=service_rate,
        seed=0,
        config=config,
    )


def _result(eng):
    snap = eng.end_period()
    return {
        "metrics": {m: getattr(eng.metrics, m) for m in METRIC_FIELDS},
        "sink_outputs": normalize(eng.metrics.sink_outputs),
        "states": [normalize(s) for _, s in eng.store.items()],
        "pair_src": snap.out_pairs.src.tolist(),
        "pair_dst": snap.out_pairs.dst.tolist(),
        "pair_rate": snap.out_pairs.rate.tolist(),
        "arrivals": eng._arrivals.tolist(),
        "usage": eng._cpu_usage.tolist(),
        "queue_costs": [q.cost for q in eng._queues],
        "alloc": eng.router.table.tolist(),
    }


def _drive(eng, *, ticks=12, migrate_at=(), fail_at=None, collect_blobs=False):
    feed = _int_batches()
    rng = np.random.default_rng(1)
    in_flight = []
    blobs = []
    for t in range(ticks):
        if t in migrate_at:
            kg = int(rng.integers(0, eng.topology.num_keygroups))
            dst = int(rng.integers(0, eng.num_nodes))
            if not eng.router.is_in_flight(kg):
                eng.redirect(kg, dst)
                in_flight.append((t, kg, dst))
        if fail_at is not None and t == fail_at:
            eng.fail_node(2)
        keys, values, ts = next(feed)
        eng.push_source("src", keys, values, ts)
        eng.tick()
        for item in list(in_flight):
            t0, kg, dst = item
            if t >= t0 + 1:
                blob = eng.serialize(kg)
                if collect_blobs:
                    blobs.append(blob)
                eng.install(kg, dst, blob)
                in_flight.remove(item)
    for _ in range(8):
        eng.tick()
    return blobs


# ---------------------------------------------------------------------------
# static eligibility
# ---------------------------------------------------------------------------


def test_plan_accepts_the_pipeline_chain():
    from repro.engine.superstep import plan_chain

    eng = _engine(True)
    plan = plan_chain(eng)
    assert plan is not None
    assert [eng.topology.operators[o].name for o in plan.fops] == [
        "mid",
        "sink",
    ]


def test_plan_rejects_non_fusible_shapes():
    from repro.engine.superstep import plan_chain

    # Not marked jit_fusible → never fuses (the contract is an opt-in).
    topo = make_pipeline_topo()
    topo.operators[1].jit_fusible = False
    eng = Engine(topo, 4, service_rate=1e9, seed=0,
                 config=ExecutionConfig.superstep())
    assert plan_chain(eng) is None
    # Non-identity partition key breaks the device-routing replay.
    topo = make_pipeline_topo()
    topo.operators[2].key_fn = lambda k: k % 3
    eng = Engine(topo, 4, service_rate=1e9, seed=0,
                 config=ExecutionConfig.superstep())
    assert plan_chain(eng) is None
    # The interpreted tiers must not build a plan at all.
    eng = Engine(make_pipeline_topo(), 4, service_rate=1e9, seed=0)
    assert plan_chain(eng) is None


# ---------------------------------------------------------------------------
# fused tick: equivalence + O(1) crossings per tick
# ---------------------------------------------------------------------------


def test_fused_tick_is_bit_identical_and_syncs_once_per_tick():
    ea = _engine(False)
    _drive(ea)
    eb = _engine(True)
    _drive(eb)
    assert _result(ea) == _result(eb)
    # Classic: one crossing per fn_jit operator per non-empty tick.  Fused:
    # one per non-empty tick, regardless of chain depth.
    assert 0 < eb.metrics.jit_host_syncs < ea.metrics.jit_host_syncs
    assert eb.metrics.jit_host_syncs <= eb.metrics.ticks


def test_fused_tick_with_kernel_stats_feeds_the_histogram():
    """With the partition kernel on (the TPU default) the engine still
    fuses: fused ticks record arrivals from their own per-edge counts, and
    every statistic matches the kernel-free classic run."""
    ea = _engine(False)
    _drive(ea)
    eb = Engine(make_pipeline_topo(), 4, service_rate=1e9, seed=0,
                config=ExecutionConfig.superstep().replace(kernel_stats=True))
    _drive(eb)
    assert eb.kernel_stats and eb.metrics.fused_ticks > 0
    assert ea.metrics.fused_ticks == 0
    assert _result(ea) == _result(eb)


def test_migration_blobs_byte_identical_at_superstep_boundary():
    ea = _engine(False)
    blobs_a = _drive(ea, migrate_at=(3, 7), collect_blobs=True)
    eb = _engine(True)
    blobs_b = _drive(eb, migrate_at=(3, 7), collect_blobs=True)
    assert _result(ea) == _result(eb)
    assert blobs_a and blobs_a == blobs_b  # byte-identical envelopes


def test_binding_budget_forces_classic_fallback():
    # service_rate 60 → partial drains every tick: _collect must bail and
    # flush_to_host must leave the classic drain bit-exact.
    ea = _engine(False, service_rate=60.0)
    _drive(ea)
    eb = _engine(True, service_rate=60.0)
    _drive(eb)
    assert _result(ea) == _result(eb)


def test_dead_node_forces_classic_fallback():
    ea = _engine(False)
    _drive(ea, fail_at=5)
    eb = _engine(True)
    _drive(eb, fail_at=5)
    assert _result(ea) == _result(eb)


# ---------------------------------------------------------------------------
# fixed fuzz specs (the hypothesis suite generalizes; these always run)
# ---------------------------------------------------------------------------

_FUZZ_SPECS = {
    "scalar-chain": {
        "family": "scalar",
        "key_dtype": "i8",
        "source_schema": True,
        "ops": [
            {"kind": "rekey", "kgs": 8, "schema": True, "out_schema": True,
             "key": "id"},
            {"kind": "vshift", "kgs": 8, "schema": True, "out_schema": True,
             "key": "id"},
        ],
        "edges": [[-1], [0]],
    },
    "record-window-filter": {
        "family": "record",
        "key_dtype": "i4",
        "source_schema": True,
        "ops": [
            {"kind": "project", "kgs": 6, "schema": True, "out_schema": True,
             "key": "id"},
            {"kind": "window", "kgs": 5, "schema": True, "out_schema": True,
             "key": "mod"},
            {"kind": "filter", "kgs": 7, "schema": True, "out_schema": False,
             "key": "id"},
        ],
        "edges": [[-1], [0], [1]],
    },
    "fanout-mixed-tiers": {
        "family": "scalar",
        "key_dtype": "i8",
        "source_schema": True,
        "ops": [
            {"kind": "window", "kgs": 8, "schema": True, "out_schema": True,
             "key": "id"},
            {"kind": "filter", "kgs": 6, "schema": True, "out_schema": True,
             "key": "mod"},
            {"kind": "accum", "kgs": 5, "schema": False, "out_schema": False,
             "key": "id"},
        ],
        "edges": [[-1], [-1, 0], [1, 0]],
    },
}


@pytest.mark.parametrize("name", list(_FUZZ_SPECS), ids=str)
def test_fuzz_jit_ports_conform(name):
    spec = _FUZZ_SPECS[name]
    scenario = Scenario("fuzz", ticks=10, drain_ticks=6, migrate_at=(4,))
    results = run_configs(
        lambda: make_fuzz_topology(spec), fuzz_feeders(spec), scenario
    )
    assert_equivalent(results)
    # The ported operators really ran on the compiled tier.
    assert results["soa+seg+schema+jit"]["jit_calls"] > 0


# ---------------------------------------------------------------------------
# run_supersteps: the K-tick scan
# ---------------------------------------------------------------------------


def _batches(K, seed=5):
    feed = _int_batches(seed=seed)
    return [next(feed) for _ in range(K)]


def test_run_supersteps_matches_classic_full_drain():
    K = 14
    batches = _batches(K)
    ea = _engine(False)
    for k, v, t in batches:
        ea.push_source("src", k, v, t)
        ea.tick()
    while any(bool(q) for q in ea._queues):
        ea.tick()

    eb = _engine(True)
    syncs0 = eb.metrics.jit_host_syncs
    assert eb.run_supersteps(batches) == K
    # One host crossing for all K supersteps — the tentpole invariant.
    assert eb.metrics.jit_host_syncs - syncs0 == 1
    while any(bool(q) for q in eb._queues):
        eb.tick()

    ra, rb = _result(ea), _result(eb)
    # The scan records no per-admission latency and needs fewer drain
    # ticks, but every pinned aggregate must match exactly.
    assert ra == rb
    assert ea.metrics.sink_outputs == eb.metrics.sink_outputs


def test_run_supersteps_static_route_matches_classic():
    """With jit_key_map declared on every non-terminal fused operator the
    scan routes from a host-precomputed schedule (no device sorts); the
    result must stay bit-identical to the classic engine and to one host
    crossing per scan."""
    from repro.engine.superstep import plan_chain

    def static_engine():
        topo = make_pipeline_topo()
        topo.operators[1].jit_key_map = lambda k: k + 17  # mid re-keys by +17
        return Engine(topo, 4, service_rate=1e9, seed=0,
                      config=ExecutionConfig.superstep())

    # The undeclared chain must keep using the on-device routing path.
    assert not plan_chain(_engine(True)).static_route

    K = 14
    batches = _batches(K)
    ea = _engine(False)
    for k, v, t in batches:
        ea.push_source("src", k, v, t)
        ea.tick()
    while any(bool(q) for q in ea._queues):
        ea.tick()

    eb = static_engine()
    assert plan_chain(eb).static_route
    syncs0 = eb.metrics.jit_host_syncs
    assert eb.run_supersteps(batches) == K
    assert eb.metrics.jit_host_syncs - syncs0 == 1
    while any(bool(q) for q in eb._queues):
        eb.tick()
    assert _result(ea) == _result(eb)
    assert ea.metrics.sink_outputs == eb.metrics.sink_outputs

    # A migration right after the scan still extracts/replays the
    # materialized pendings byte-exactly.
    batches = _batches(8)
    ea = _engine(False)
    for k, v, t in batches:
        ea.push_source("src", k, v, t)
        ea.tick()
    eb = static_engine()
    eb.run_supersteps(batches)
    for eng in (ea, eb):
        eng.redirect(5, 2)
        eng.tick()
        eng.install(5, 2, eng.serialize(5))
        while any(bool(q) for q in eng._queues):
            eng.tick()
    assert _result(ea) == _result(eb)


def test_run_supersteps_guards():
    eng = _engine(False)
    with pytest.raises(RuntimeError, match="superstep=True"):
        eng.run_supersteps(_batches(2))
    eng = _engine(True)
    k, v, t = _batches(1)[0]
    eng.push_source("src", k, v, t)
    with pytest.raises(RuntimeError, match="empty queues"):
        eng.run_supersteps(_batches(2))
    eng = _engine(True, service_rate=100.0)  # a superstep cannot fit
    with pytest.raises(RuntimeError, match="backpressure"):
        eng.run_supersteps(_batches(2))


def test_run_supersteps_then_migration_round_trip():
    """The scan's leftover pendings are real segments: a migration right
    after run_supersteps extracts/replays them like any queued work."""
    batches = _batches(8)
    ea = _engine(False)
    for k, v, t in batches:
        ea.push_source("src", k, v, t)
        ea.tick()
    eb = _engine(True)
    eb.run_supersteps(batches)
    for eng in (ea, eb):
        eng.redirect(5, 2)
        eng.tick()
        eng.install(5, 2, eng.serialize(5))
        while any(bool(q) for q in eng._queues):
            eng.tick()
    assert _result(ea) == _result(eb)


# ---------------------------------------------------------------------------
# zero-fn_jit regression: superstep must not drag jax in
# ---------------------------------------------------------------------------

ZERO_FN_JIT = textwrap.dedent(
    """
    import sys
    import numpy as np
    from repro.engine import Engine, ExecutionConfig
    from repro.engine.topology import OperatorSpec, Schema, Topology

    t = Topology()
    scalar = Schema(np.dtype(np.float64))
    t.add_operator(OperatorSpec("src", None, num_keygroups=4,
                                is_source=True, schema=scalar))

    def fn(state, keys, values, ts):
        state["n"] = state.get("n", 0) + len(keys)
        return state, (keys, values, ts)

    t.add_operator(OperatorSpec("snk", fn, num_keygroups=4, is_sink=True,
                                schema=scalar))
    t.connect("src", "snk")
    eng = Engine(t, 2, service_rate=1e9, seed=0,
                 config=ExecutionConfig.superstep())
    assert eng.superstep is False  # degraded: nothing to fuse
    eng.push_source("src", np.arange(8, dtype=np.int64), np.ones(8),
                    np.zeros(8))
    eng.tick()
    eng.tick()
    assert eng.metrics.sink_tuples == 8
    assert "repro.engine.jitexec" not in sys.modules
    assert "repro.engine.superstep" not in sys.modules
    assert "jax" not in sys.modules
    assert np.asarray([1.5]).dtype == np.float64  # x64 never flipped
    print("ZERO-FN-JIT-OK")
    """
)


def test_superstep_with_zero_fn_jit_ops_skips_jit_setup():
    """use_fn_jit=True + superstep=True over a topology with no fn_jit
    operators must not import jitexec/superstep/jax (the x64 flip is the
    observable side effect guarded here).  Subprocess: module-import state
    is process-global."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", ZERO_FN_JIT],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ZERO-FN-JIT-OK" in proc.stdout

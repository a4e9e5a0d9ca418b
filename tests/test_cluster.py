"""Multi-worker host runtime: determinism, seeding, pipelined ingestion,
envelope export/import and elastic growth across live worker processes.

The conformance matrix (tests/conformance.py) already pins the 2-worker
configuration against the single-process oracle on every real job; this
suite covers what the matrix can't — uneven 3-worker splits, seed
reproducibility, the pipelined ``run_stream`` mode, the public envelope
API, and the coordinator's elastic/lifecycle surface.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conformance import (
    Scenario,
    _pipeline_feeders,
    assert_equivalent,
    make_pipeline_topo,
    run_scenario,
)
from repro.engine import Engine, ExecutionConfig, make_engine
from repro.engine.cluster import (
    ClusterEngine,
    contiguous_node_worker,
    worker_rng,
)

KGS = 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cluster(num_workers=2, num_nodes=4, service_rate=1e9, seed=0, **kw):
    return make_engine(
        make_pipeline_topo(KGS),
        num_nodes,
        config=ExecutionConfig.workers(num_workers),
        service_rate=service_rate,
        seed=seed,
        **kw,
    )


def _push(eng, n, seed, key_space=5_000):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, key_space, size=n).astype(np.int64)
    return eng.push_source("src", keys, rng.random(n), np.zeros(n))


def _drain(eng, max_ticks=60):
    for _ in range(max_ticks):
        if eng.worst_queue_cost() == 0.0:
            return
        eng.tick()
    raise AssertionError("cluster failed to quiesce")


def test_contiguous_node_worker_is_monotone_and_balanced():
    for n, w in [(4, 2), (5, 2), (4, 3), (7, 3), (2, 2)]:
        owners = contiguous_node_worker(n, w)
        assert (np.diff(owners) >= 0).all()  # the determinism contract
        counts = np.bincount(owners, minlength=w)
        assert counts.min() >= 1 and counts.max() - counts.min() <= 1


def test_three_workers_uneven_split_matches_oracle():
    # 4 nodes over 3 workers → blocks of size 2/1/1: the uneven-split case
    # the 2-worker conformance matrix never exercises.
    scenario = Scenario("uneven", ticks=10, drain_ticks=8, migrate_at=(3, 6))
    results = {
        config.name: run_scenario(
            make_pipeline_topo, _pipeline_feeders, scenario, config
        )
        for config in (ExecutionConfig.typed(), ExecutionConfig.workers(3))
    }
    assert_equivalent(results)
    assert results["soa+seg+schema+workers"]["migration_blobs"]


def test_same_seed_reproduces_run_exactly():
    def drive(seed):
        with _cluster(seed=seed) as eng:
            alloc = eng.router.table.copy()
            for t in range(5):
                _push(eng, 200, seed=100 + t)
                eng.tick()
            _drain(eng)
            eng.finalize()
            return alloc, eng.metrics.sink_outputs, eng.metrics.sink_tuples

    a0, s0, n0 = drive(seed=7)
    a1, s1, n1 = drive(seed=7)
    assert np.array_equal(a0, a1) and s0 == s1 and n0 == n1
    a2, _, _ = drive(seed=8)
    assert not np.array_equal(a0, a2)  # seed reaches the alloc draw


def test_worker_rng_streams_are_deterministic_and_distinct():
    assert np.array_equal(
        worker_rng(3, 0).random(4), worker_rng(3, 0).random(4)
    )
    assert not np.array_equal(
        worker_rng(3, 0).random(4), worker_rng(3, 1).random(4)
    )
    assert not np.array_equal(
        worker_rng(3, 0).random(4), worker_rng(4, 0).random(4)
    )


def _batches(n_batches, size=150, seed=11, key_space=5_000):
    rng = np.random.default_rng(seed)
    return [
        (
            rng.integers(0, key_space, size=size).astype(np.int64),
            rng.random(size),
            np.full(size, float(t)),
        )
        for t in range(n_batches)
    ]


def test_run_stream_matches_lockstep_ticks():
    batches = _batches(10)
    with _cluster() as piped:
        accepted_p = piped.run_stream("src", batches, window=4)
        _drain(piped)
        piped.finalize()
    with _cluster() as lock:
        accepted_l = 0
        for keys, values, ts in batches:
            accepted_l += lock.push_source("src", keys, values, ts)
            lock.tick()
        _drain(lock)
        lock.finalize()
    assert accepted_p == accepted_l == sum(len(b[0]) for b in batches)
    assert piped.metrics.sink_outputs == lock.metrics.sink_outputs
    assert piped.metrics.sink_tuples == lock.metrics.sink_tuples
    assert [s for _, s in piped.store.items()] == [
        s for _, s in lock.store.items()
    ]


def test_run_stream_backpressure_conserves_tuples():
    # A tight service budget forces the asynchronous credit loop to drop
    # tuples at the source; whatever was accepted must reach the sink.
    batches = _batches(12, size=1000)
    with _cluster(service_rate=50.0) as eng:
        accepted = eng.run_stream("src", batches, window=3)
        _drain(eng, max_ticks=400)
        eng.finalize()
    assert 0 < accepted < sum(len(b[0]) for b in batches)
    assert eng.metrics.dropped_credits == sum(len(b[0]) for b in batches) - accepted
    assert eng.metrics.sink_tuples == accepted


def test_run_stream_shuffle_is_seed_reproducible():
    batches = _batches(8)

    def drive(seed):
        with _cluster(seed=seed) as eng:
            accepted = eng.run_stream("src", batches, shuffle=True)
            _drain(eng)
            eng.finalize()
            return accepted, eng.metrics.sink_outputs

    acc0, sinks0 = drive(seed=5)
    acc1, sinks1 = drive(seed=5)
    assert acc0 == acc1 == sum(len(b[0]) for b in batches)
    assert sinks0 == sinks1


def test_export_envelope_identical_to_single_process():
    single = Engine(
        make_pipeline_topo(KGS),
        4,
        config=ExecutionConfig.typed(),
        service_rate=1e9,
        seed=0,
    )
    with _cluster() as cluster:
        assert np.array_equal(single.router.table, cluster.router.table)
        for t in range(4):
            _push(single, 200, seed=40 + t)
            _push(cluster, 200, seed=40 + t)
            single.tick()
            cluster.tick()
        base = single.topology.kg_base(1)
        for kg in range(base, base + KGS):
            env_s = single.export_keygroup(kg)
            env_c = cluster.export_keygroup(kg)
            assert env_c.version == env_s.version == 1
            assert env_c.keygroup == kg
            assert env_c.blob == env_s.blob  # byte-identical envelope


def test_import_keygroup_installs_across_workers():
    with _cluster() as eng:
        for t in range(4):
            _push(eng, 200, seed=60 + t)
            eng.tick()
        _drain(eng)
        base = eng.topology.kg_base(1)
        # Pick a key group and move it to a node on the *other* worker.
        kg = next(
            k for k in range(base, base + KGS)
            if eng.worker_of_node(eng.router.node_of(k)) == 0
        )
        dst = int(np.flatnonzero(eng.node_worker == 1)[0])
        env = eng.export_keygroup(kg)
        eng.import_keygroup(env, dst)
        assert eng.router.node_of(kg) == dst
        accepted2 = _push(eng, 200, seed=99)
        _drain(eng)
        eng.finalize()
    expected = 4 * 200 + accepted2
    assert eng.metrics.sink_tuples == expected
    assert sum(
        eng.store.get(k).get("n", 0) for k in range(base, base + KGS)
    ) == expected


def test_add_nodes_stays_monotone_and_carries_traffic():
    with _cluster() as eng:
        accepted = _push(eng, 200, seed=1)
        _drain(eng)
        eng.add_nodes(2)
        assert eng.num_nodes == 6
        assert (np.diff(eng.node_worker) >= 0).all()
        assert (eng.node_worker[-2:] == eng.num_workers - 1).all()
        # Migrate a key group onto a fresh node and keep the job flowing.
        base = eng.topology.kg_base(1)
        eng.redirect(base, 5)
        eng.install(base, 5, eng.serialize(base))
        accepted2 = _push(eng, 200, seed=2)
        _drain(eng)
        eng.finalize()
    assert eng.metrics.sink_tuples == accepted + accepted2


def test_close_terminates_worker_processes():
    eng = _cluster()
    procs = list(eng.pool.processes)
    assert all(p.is_alive() for p in procs)
    _push(eng, 100, seed=3)
    eng.tick()
    eng.close()
    for p in procs:
        p.join(timeout=10)
    assert not any(p.is_alive() for p in procs)
    eng.close()  # idempotent


def test_worker_config_pins_numpy_partitioning():
    with _cluster() as eng:
        assert eng._worker_config.kernel_stats is False
    with pytest.raises(ValueError, match="numpy tiers only"):
        ExecutionConfig.workers(2).replace(kernel_stats=True)


SHARDS_NEVER_TOUCH_JAX = textwrap.dedent(
    """
    import sys, types

    class _Poisoned(types.ModuleType):
        def __getattr__(self, name):
            raise RuntimeError(f"jax.{name} reached from " + str(os.getpid()))

    import os
    # Any jax use by the coordinator or a forked shard raises.
    sys.modules["jax"] = _Poisoned("jax")
    import numpy as np
    from repro.data import airline_stream, real_job_2
    from repro.data.synthetic import StreamSpec
    from repro.engine import ExecutionConfig, make_engine

    # real_job_2 declares fn_jit bodies and integer keys: the shape that
    # would take the partition kernel on a device tier.
    with make_engine(real_job_2(keygroups_per_op=8), 4,
                     config=ExecutionConfig.workers(2),
                     service_rate=1e9, seed=0, timeout=60.0) as eng:
        stream = airline_stream(StreamSpec(rate=200.0, seed=3))
        for _ in range(4):
            eng.push_source("airline", *next(stream))
            eng.tick()
        for _ in range(6):
            eng.tick()
        eng.finalize()
        assert eng.metrics.sink_tuples > 0
    print("SHARDS-NO-JAX-OK")
    """
)


def test_forked_shards_never_touch_jax():
    """Worker shards fork from a parent that may hold the accelerator: with
    jax poisoned in the parent, a shard (or the coordinator) that reaches
    for it would raise and the run would fail."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-c", SHARDS_NEVER_TOUCH_JAX],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARDS-NO-JAX-OK" in proc.stdout


# ---------------------------------------------------------------------------
# Shared-memory exchange lanes: transport selection and overflow fallback
# ---------------------------------------------------------------------------


def _run_matched(shm, ticks=6, n=300):
    """Drive identical traffic through a 3-worker cluster with the given
    ring size and the single-process oracle; return both engines."""
    cluster = make_engine(
        make_pipeline_topo(KGS),
        4,
        config=ExecutionConfig.workers(3, shm=shm),
        service_rate=1e9,
        seed=0,
    )
    oracle = make_engine(
        make_pipeline_topo(KGS),
        4,
        config=ExecutionConfig.typed(),
        service_rate=1e9,
        seed=0,
    )
    try:
        for t in range(ticks):
            rng = np.random.default_rng(70 + t)
            keys = rng.integers(0, 5_000, size=n).astype(np.int64)
            values, ts = rng.random(n), np.zeros(n)
            cluster.push_source("src", keys, values, ts)
            oracle.push_source("src", keys, values, ts)
            cluster.tick()
            oracle.tick()
        for _ in range(60):
            if cluster.worst_queue_cost() == 0.0 and not any(
                q.cost for q in oracle._queues
            ):
                break
            cluster.tick()
            oracle.tick()
        cluster.finalize()
    finally:
        cluster.close()
    return cluster, oracle


def _assert_matches_oracle(cluster, oracle):
    assert cluster.metrics.sink_outputs == oracle.metrics.sink_outputs
    c_states = {kg: s for kg, s in cluster.store.items() if s}
    o_states = {kg: s for kg, s in oracle.store.items() if s}
    assert c_states == o_states


def test_shm_lanes_carry_the_exchange_bit_exact():
    cluster, oracle = _run_matched(shm=1 << 20)
    _assert_matches_oracle(cluster, oracle)
    xs = cluster.exchange_stats
    assert xs["shm_msgs"] > 0 and xs["queue_msgs"] == 0
    assert xs["shm_bytes_out"] > 0 and xs["shm_bytes_in"] > 0


def test_ring_full_overflow_falls_back_bit_exact():
    # A 128-byte ring holds an empty record but no real batch: every
    # payload-carrying message must overflow to the queue path, mixing
    # transports per (tick, lane) — the merge must not notice.
    cluster, oracle = _run_matched(shm=128)
    _assert_matches_oracle(cluster, oracle)
    xs = cluster.exchange_stats
    assert xs["shm_msgs"] > 0 and xs["queue_msgs"] > 0


def test_queue_only_transport_stays_bit_exact():
    cluster, oracle = _run_matched(shm=0)
    _assert_matches_oracle(cluster, oracle)
    xs = cluster.exchange_stats
    assert xs["shm_msgs"] == 0 and xs["queue_msgs"] > 0
    assert xs["shm_bytes_out"] == 0

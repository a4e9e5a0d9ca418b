"""The compiled operator tier (engine/jitexec.py): kernels, recompilation
discipline, state coherence, and shard_map parity.

The differential conformance suite (tests/test_real_jobs_conformance.py)
already pins the jit configuration against the four oracles end to end;
this module pins the runtime's *mechanics*: padding-bucket compile counts
stay O(#buckets) across a long varied-batch run, keyed tables look up /
insert / grow correctly, interpreted↔compiled state stays coherent through
migrations, and the run-sharded shard_map execution matches the plain call.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conformance import make_pipeline_topo
from repro.data.jobs import real_job_2
from repro.data.synthetic import StreamSpec, airline_stream
from repro.engine import Engine, ExecutionConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _feed_pipeline(eng, sizes, *, seed=0):
    rng = np.random.default_rng(seed)
    for t, n in enumerate(sizes):
        keys = rng.integers(0, 10_000, size=n).astype(np.int64)
        eng.push_source("src", keys, rng.random(n), np.full(n, float(t)))
        eng.tick()
    for _ in range(6):
        eng.tick()


# ---------------------------------------------------------------------------
# recompilation discipline
# ---------------------------------------------------------------------------


def test_compiles_bounded_by_buckets_not_ticks():
    """A long run with wildly varied batch sizes compiles O(#buckets)
    programs: jit_calls grows with ticks, jit_compiles does not."""
    eng = Engine(
        make_pipeline_topo(8), 4, service_rate=1e9, seed=0, config=ExecutionConfig.jit()
    )
    sizes = [7, 40, 900, 13, 260, 55, 1, 470, 33, 128] * 6  # 60 varied ticks
    _feed_pipeline(eng, sizes)
    m = eng.metrics
    assert m.jit_calls > 100  # 2 ops × 4 nodes × 60 ticks, minus empty drains
    # Buckets: tuple counts in {16..1024} (7 sizes) × run counts {4, 8} × 2
    # operators — far below the call count, and independent of tick count.
    assert m.jit_compiles < 40
    assert m.jit_compiles < m.jit_calls / 4
    assert m.jit_tuples > 0
    assert eng._jit.compile_seconds > 0.0


def test_second_engine_recompiles_nothing_globally():
    """The compile cache is keyed by the fn_jit object (module-level bodies):
    a second engine re-counts its own bucket set but hits jax's cache —
    runtime-level counts stay equal, not doubled, across engines."""
    sizes = [64, 64, 64, 64]
    eng1 = Engine(
        make_pipeline_topo(8), 2, service_rate=1e9, seed=0, config=ExecutionConfig.jit()
    )
    _feed_pipeline(eng1, sizes)
    eng2 = Engine(
        make_pipeline_topo(8), 2, service_rate=1e9, seed=0, config=ExecutionConfig.jit()
    )
    _feed_pipeline(eng2, sizes)
    assert eng2.metrics.jit_compiles == eng1.metrics.jit_compiles


def test_jit_requires_soa_and_schema():
    with pytest.raises(ValueError):
        ExecutionConfig(queue_impl="deque", use_fn_jit=True, use_schema=True)
    with pytest.raises(ValueError):
        ExecutionConfig(use_schema=False, use_fn_jit=True)


# ---------------------------------------------------------------------------
# keyed tables
# ---------------------------------------------------------------------------


def test_keyed_running_sum_matches_reference():
    """Direct kernel check against a python left-fold reference: lookups,
    first-occurrence insertion order, padding masks, duplicate codes."""
    jx = pytest.importorskip("repro.engine.jitexec")
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    n, nb, num_kg, cap = 50, 64, 3, 64
    codes = rng.integers(0, 6, size=nb).astype(np.int64) * 3 + np.arange(nb) % 3
    kg = (codes % 3).astype(np.int64)  # same code → same key group
    addends = rng.normal(size=nb)
    valid = np.arange(nb) < n
    table = jx.TableState(
        codes=jnp.full(cap, jx.EMPTY_CODE, dtype=jnp.int64),
        vals=(jnp.zeros(cap),),
        seq=jnp.zeros(cap, dtype=jnp.int64),
        owner=jnp.zeros(cap, dtype=jnp.int32),
        perm=jnp.arange(cap, dtype=jnp.int32),
        cnt=jnp.zeros((), dtype=jnp.int32),
        epoch=jnp.ones((), dtype=jnp.int64),
    )
    table2, (running,) = jx.keyed_running_agg(
        table, jnp.asarray(codes), jnp.asarray(kg), (jnp.asarray(addends),),
        jnp.asarray(valid), ("sum",),
    )
    # A second call must continue from the first (sorted view incrementally
    # merged, sequence numbers monotone across epochs).
    table3, (running2,) = jx.keyed_running_agg(
        table2, jnp.asarray(codes), jnp.asarray(kg), (jnp.asarray(addends),),
        jnp.asarray(valid), ("sum",),
    )
    # Reference: sequential dicts per key group.
    dicts = [dict() for _ in range(num_kg)]
    ref = np.zeros(n)
    ref2 = np.zeros(n)
    for pass_out in (ref, ref2):
        for i in range(n):
            d = dicts[kg[i]]
            d[codes[i]] = d.get(codes[i], 0.0) + addends[i]
            pass_out[i] = d[codes[i]]
    np.testing.assert_allclose(np.asarray(running)[:n], ref, rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(running2)[:n], ref2, rtol=1e-9, atol=1e-12
    )
    got = np.asarray(running)[:n]
    # Exactness of the first occurrence of every code.
    seen = set()
    for i in range(n):
        if codes[i] not in seen:
            seen.add(codes[i])
            assert got[i] == addends[i]
    # Table contents: per key group, codes in first-occurrence order by seq;
    # the sorted view is a valid permutation with codes ascending.
    for t in (table2, table3):
        t_codes = np.asarray(t.codes)
        t_seq = np.asarray(t.seq)
        t_owner = np.asarray(t.owner)
        cnt = int(t.cnt)
        assert cnt == sum(len(d) for d in dicts)
        for k in range(num_kg):
            mine = np.flatnonzero(t_owner[:cnt] == k)
            order = mine[np.argsort(t_seq[mine], kind="stable")]
            assert t_codes[order].tolist() == list(dicts[k])
        perm = np.asarray(t.perm)
        assert sorted(perm.tolist()) == list(range(len(perm)))
        assert np.all(np.diff(t_codes[perm]) >= 0)


def test_table_growth_past_initial_capacity():
    """More distinct keys than the initial 64-slot capacity: the runtime
    grows the tables (a new compile bucket) and the state stays equal to the
    interpreted oracle."""
    topo = real_job_2(keygroups_per_op=2)
    kw = dict(service_rate=1e9, seed=0, collect_sinks=False)
    jit_eng = Engine(real_job_2(keygroups_per_op=2), 2,
                     config=ExecutionConfig.jit(), **kw)
    seg_eng = Engine(topo, 2, **kw)
    stream = airline_stream(StreamSpec(rate=500.0, seed=3))
    batches = [next(stream) for _ in range(6)]
    for eng in (jit_eng, seg_eng):
        for k, v, ts in batches:
            eng.push_source("airline", k, v, ts)
            eng.tick()
        for _ in range(4):
            eng.tick()
        eng.end_period()
    caps = jit_eng._jit._by_op[2].caps  # sumdelay
    assert caps["sums"] > 64  # ~1000 (plane, year) pairs over 2 key groups
    for kg in range(topo.num_keygroups):
        a = jit_eng.store.get(kg)
        b = seg_eng.store.get(kg)
        assert list(a) == list(b)
        for name in a:
            if isinstance(a[name], dict):
                assert list(a[name]) == list(b[name])  # keys + order
                np.testing.assert_allclose(
                    list(a[name].values()),
                    list(b[name].values()),
                    rtol=1e-9,
                    atol=1e-9,
                )
            else:
                assert a[name] == b[name]


def _sumdelay_runtime():
    from repro.engine.executor import EngineMetrics
    from repro.engine.jitexec import JitRuntime
    from repro.engine.state import KeyedStore

    topo = real_job_2(keygroups_per_op=4)
    store = KeyedStore(topo.num_keygroups)
    rt = JitRuntime(topo, store, EngineMetrics(), topo.kg_operator())
    op = topo._resolve("sumdelay")
    return rt, store, op, topo.kg_base(op)


def _sumdelay_call(rt, op, base, planes, years, delays):
    """One SumDelay call over runs of key groups plane % 4; returns the
    tuples in run order and the running sums the call emitted."""
    from repro.data.jobs import EXTRACT_SCHEMA

    kg = planes % 4
    order = np.argsort(kg, kind="stable")
    values = np.zeros(len(planes), dtype=EXTRACT_SCHEMA.value)
    values["plane"], values["year"], values["delay"] = (
        planes[order], years[order], delays[order]
    )
    ends = np.cumsum(np.bincount(kg, minlength=4))
    starts = ends - np.bincount(kg, minlength=4)
    (_, ov, _), _ = rt.execute(
        op, [base + k for k in range(4)], starts.tolist(), ends.tolist(),
        values["plane"].copy(), values, np.zeros(len(planes)),
    )
    return values, ov["sum"]


def test_overflowing_call_runs_again_on_a_grown_table():
    """A first call with more new keys than the table's 64 slots leaves the
    table as it was, grows it to twice its keys and runs again once: its
    outputs, values and insertion order equal a left fold, and its integer
    state equals a call on a table large enough from the start, bit for
    bit.  A second call on the same keys neither grows nor runs again."""
    rng = np.random.default_rng(11)
    n = 600
    planes = rng.integers(0, 150, size=n).astype(np.int64)
    years = 2000 + rng.integers(0, 3, size=n).astype(np.int64)
    keys = len(set(zip(planes.tolist(), years.tolist())))
    batches = [
        (planes, years, rng.normal(size=n)),
        (planes[::-1].copy(), years[::-1].copy(), rng.normal(size=n)),
    ]
    rt, store, op, base = _sumdelay_runtime()
    ref_rt, _, _, _ = _sumdelay_runtime()
    ost = ref_rt._by_op[op]
    ref_rt._grow(ost, ost.fields[0], 1000)  # 2,048 slots: no overflow
    dicts = [dict() for _ in range(4)]
    for step, batch in enumerate(batches):
        values, sums = _sumdelay_call(rt, op, base, *batch)
        _, ref_sums = _sumdelay_call(ref_rt, op, base, *batch)
        expect = []
        for p, y, dl in values[["plane", "year", "delay"]].tolist():
            d = dicts[p % 4]
            d[(p, y)] = d.get((p, y), 0.0) + dl
            expect.append(d[(p, y)])
        np.testing.assert_allclose(sums, expect, rtol=1e-9, atol=1e-12)
        np.testing.assert_array_equal(sums, ref_sums)
        m = rt._metrics
        assert (m.table_retries, m.table_grows) == (1, 1)
        assert rt._by_op[op].caps["sums"] == 1 << (2 * keys - 1).bit_length()
        got, ref = rt._by_op[op].cols["sums"], ost.cols["sums"]
        cnt = rt._by_op[op].cnt_host["sums"]
        assert cnt == int(got.cnt) == sum(len(d) for d in dicts) == keys
        for a, b in zip(got[:4], ref[:4]):  # codes, vals, seq, owner
            np.testing.assert_array_equal(
                np.asarray(a)[..., :cnt], np.asarray(b)[..., :cnt]
            )
        assert int(got.epoch) == int(ref.epoch) == 2 + step
        rt.sync_store()
        for k in range(4):
            state = store.get(base + k)["sums"]
            assert list(state) == list(dicts[k])  # insertion order
            np.testing.assert_allclose(
                list(state.values()), list(dicts[k].values()), rtol=1e-9
            )


def _acc_fn(state, keys, values, ts):
    state["n"] = state.get("n", 0) + len(keys)
    sums = state.setdefault("sums", {})
    out = np.empty(len(keys))
    for i, (k, v) in enumerate(zip(np.asarray(keys).tolist(),
                                   np.asarray(values).tolist())):
        sums[k] = sums.get(k, 0.0) + v
        out[i] = sums[k]
    return state, (keys, out, ts)


def _acc_jit(state, kgs, starts, ends, keys, values, ts):
    from repro.engine import jitexec as jx

    nb = keys.shape[0]
    kg = kgs[jx.run_of_tuples(ends, nb)]
    valid = jx.tuple_valid(starts, ends, nb)
    table, (running,) = jx.keyed_running_agg(
        state["sums"], keys, kg, (values,), valid, ("sum",)
    )
    n = jx.count_runs(state["n"], kgs, starts, ends)
    return {"n": n, "sums": table}, (keys, running, ts), None


def _acc_topo():
    """source → a sink holding a counter and a keyed table per key group."""
    from repro.engine.topology import (
        OperatorSpec, Schema, StateField, StateSchema, Topology,
    )

    scalar = Schema(np.dtype(np.float64))
    state = StateSchema((
        StateField("n", "scalar", dtype=np.int64, py=int),
        StateField("sums", "table", dtype=np.float64, py=float,
                   key_encode=int, key_decode=int),
    ))
    t = Topology()
    t.add_operator(OperatorSpec(
        "src", None, num_keygroups=4, is_source=True, schema=scalar))
    t.add_operator(OperatorSpec(
        "acc", _acc_fn, num_keygroups=4, is_sink=True, fn_jit=_acc_jit,
        state_schema=state, schema=scalar, out_schema=scalar))
    t.connect("src", "acc")
    return t


@pytest.mark.parametrize("mesh", [False, True], ids=["plain", "mesh1"])
def test_overflow_holds_every_state_field(mesh):
    """An operator with a counter beside its keyed table: the call whose new
    keys overflow the table must leave the counter as it was too, so that
    the re-run counts each tuple once — plain and key-group-sharded."""
    import jax

    m = jax.make_mesh((1,), ("nodes",), devices=jax.devices()[:1]) if mesh else None
    rng = np.random.default_rng(5)
    batches = [
        (rng.integers(0, 300, size=n).astype(np.int64), rng.normal(size=n))
        for n in (400, 400, 100)
    ]
    engines = [
        Engine(_acc_topo(), 2, service_rate=1e9, seed=0, config=cfg)
        for cfg in (ExecutionConfig.jit(mesh=m), ExecutionConfig.typed())
    ]
    for eng in engines:
        for t, (keys, vals) in enumerate(batches):
            eng.push_source("src", keys, vals, np.full(len(keys), float(t)))
            eng.tick()
        for _ in range(3):
            eng.tick()
    jit_eng, ref = engines
    assert jit_eng.metrics.table_retries == 1
    jit_eng._jit.sync_store()
    total = 0
    for kg in range(4, 8):
        a, b = jit_eng.store.get(kg), ref.store.get(kg)
        assert a["n"] == b["n"]
        total += a["n"]
        assert list(a["sums"]) == list(b["sums"])
        np.testing.assert_allclose(
            list(a["sums"].values()), list(b["sums"].values()), rtol=1e-9
        )
    assert total == 900


# ---------------------------------------------------------------------------
# interpreted ↔ compiled state coherence
# ---------------------------------------------------------------------------


def test_migration_blob_bytes_identical_on_integer_state():
    """serialize() of a jit-tier key group materializes the device columns
    into the oracle dict — on integer state the blob bytes are identical to
    the interpreted engine's."""
    sizes = [100, 80, 120]
    jit_eng = Engine(
        make_pipeline_topo(8), 2, service_rate=1e9, seed=0, config=ExecutionConfig.jit()
    )
    seg_eng = Engine(make_pipeline_topo(8), 2, service_rate=1e9, seed=0)
    _feed_pipeline(jit_eng, sizes)
    _feed_pipeline(seg_eng, sizes)
    assert jit_eng.metrics.jit_calls > 0
    for kg in range(8, 24):  # mid + sink key groups
        assert jit_eng.serialize(kg) == seg_eng.serialize(kg)


def test_install_then_jit_resumes_from_installed_state():
    """install() marks the dict authoritative; the next jit call pushes it
    back into columns and continues from it."""
    eng = Engine(
        make_pipeline_topo(8), 2, service_rate=1e9, seed=0, config=ExecutionConfig.jit()
    )
    _feed_pipeline(eng, [50, 50])
    kg = 8  # a mid-operator key group
    blob = eng.serialize(kg)
    before = dict(eng.store.get(kg))
    dst = (eng.router.node_of(kg) + 1) % eng.num_nodes
    eng.redirect(kg, dst)
    eng.install(kg, dst, blob)
    assert eng.store.get(kg) == before
    _feed_pipeline(eng, [50])
    eng._jit.sync_store()
    after = eng.store.get(kg)
    assert after.get("n", 0) >= before.get("n", 0)


# ---------------------------------------------------------------------------
# shard_map execution
# ---------------------------------------------------------------------------


def test_shard_map_single_device_parity():
    """With a 1-device mesh the run-sharded execution must be bit-identical
    to the plain jitted call (integer pipeline state and outputs)."""
    jax = pytest.importorskip("jax")
    mesh = jax.make_mesh((1,), ("nodes",), devices=jax.devices()[:1])
    sizes = [60, 130, 90]
    plain = Engine(
        make_pipeline_topo(8), 2, service_rate=1e9, seed=0, config=ExecutionConfig.jit()
    )
    sharded = Engine(
        make_pipeline_topo(8),
        2,
        service_rate=1e9,
        seed=0,
        config=ExecutionConfig.jit(mesh=mesh),
    )
    _feed_pipeline(plain, sizes)
    _feed_pipeline(sharded, sizes)
    assert sharded.metrics.jit_calls > 0
    assert plain.metrics.sink_outputs == sharded.metrics.sink_outputs
    plain._jit.sync_store()
    sharded._jit.sync_store()
    for kg in range(24):
        assert plain.store.get(kg) == sharded.store.get(kg)
    # Keyed tables take the key-group-sharded path: a first batch whose new
    # keys overflow the 64-slot tables runs again on grown ones, alike.
    kw = dict(service_rate=1e9, seed=0, collect_sinks=True)
    engines = [
        Engine(real_job_2(keygroups_per_op=4), 2,
               config=ExecutionConfig.jit(mesh=m), **kw)
        for m in (None, mesh)
    ]
    stream = airline_stream(StreamSpec(rate=400.0, seed=9))
    batches = [next(stream) for _ in range(3)]
    for eng in engines:
        for k, v, ts in batches:
            eng.push_source("airline", k, v, ts)
            eng.tick()
        for _ in range(4):
            eng.tick()
        eng._jit.sync_store()
    a, b = engines
    assert a.metrics.table_retries >= 1
    assert b.metrics.table_retries == a.metrics.table_retries
    assert a.metrics.sink_outputs == b.metrics.sink_outputs
    for kg in range(a.topology.num_keygroups):
        assert a.store.get(kg) == b.store.get(kg)


SHARDED_PARITY = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np
    import jax
    from repro.data.jobs import real_job_2
    from repro.data.synthetic import StreamSpec, airline_stream
    from repro.engine import Engine, ExecutionConfig

    mesh = jax.make_mesh((2,), ("nodes",), devices=jax.devices()[:2])
    kw = dict(service_rate=1e9, seed=0, collect_sinks=True)
    engines = [
        Engine(real_job_2(keygroups_per_op=4), 2,
               config=ExecutionConfig.jit(), **kw),
        Engine(real_job_2(keygroups_per_op=4), 2,
               config=ExecutionConfig.jit(mesh=mesh), **kw),
    ]
    stream = airline_stream(StreamSpec(rate=120.0, seed=5))
    batches = [next(stream) for _ in range(5)]
    for eng in engines:
        for k, v, ts in batches:
            eng.push_source("airline", k, v, ts)
            eng.tick()
        for _ in range(4):
            eng.tick()
        eng.end_period()
    a, b = engines
    assert b.metrics.jit_calls > 0
    assert a.metrics.processed_tuples == b.metrics.processed_tuples
    assert len(a.metrics.sink_outputs) == len(b.metrics.sink_outputs)
    for (k1, v1, t1), (k2, v2, t2) in zip(
        a.metrics.sink_outputs, b.metrics.sink_outputs
    ):
        assert k1 == k2 and t1 == t2
        np.testing.assert_allclose(v1[1], v2[1], rtol=1e-9, atol=1e-9)
    for kg in range(a.topology.num_keygroups):
        sa, sb = a.store.get(kg), b.store.get(kg)
        assert list(sa) == list(sb)
        for name in sa:
            assert list(sa[name]) == list(sb[name])
            np.testing.assert_allclose(
                list(sa[name].values()),
                list(sb[name].values()),
                rtol=1e-9,
                atol=1e-9,
            )

    # Duplicate key groups in one call (budget-leftover + fresh segments of
    # the same operator) must not shard-split: two shards updating the same
    # key group from the same base would double-count on merge.  The runtime
    # falls back to the plain call there — scalar state stays bit-exact.
    import jax.numpy as jnp
    from repro.engine.topology import (
        OperatorSpec, Schema, StateField, StateSchema, Topology
    )

    def mid_fn(state, keys, values, ts):
        state["n"] = state.get("n", 0) + len(keys)
        return state, (keys, values, ts)

    def mid_jit(state, kgs, starts, ends, keys, values, ts):
        from repro.engine import jitexec as jx
        return (
            {"n": jx.count_runs(state["n"], kgs, starts, ends)},
            (keys, values, ts),
            None,
        )

    def scalar_topo():
        scalar = Schema(np.dtype(np.float64))
        t = Topology()
        t.add_operator(OperatorSpec(
            "src", None, num_keygroups=4, is_source=True, schema=scalar))
        t.add_operator(OperatorSpec(
            "mid", mid_fn, num_keygroups=4, is_sink=True, fn_jit=mid_jit,
            state_schema=StateSchema(
                (StateField("n", "scalar", dtype=np.int64, py=int),)
            ),
            schema=scalar, out_schema=scalar))
        t.connect("src", "mid")
        return t

    keys4 = np.arange(4, dtype=np.int64)
    vals4 = np.ones(4)
    ts4 = np.zeros(4)
    results = []
    for m in (None, mesh):
        e = Engine(scalar_topo(), 2, service_rate=1e9, seed=0,
                   config=ExecutionConfig.jit(mesh=m))
        g = e.topology.kg_base(1)
        out, lens = e._jit_exec(
            1, [g + 1, g + 1], [0, 2], [2, 4], keys4, vals4, ts4
        )
        e._jit.sync_store()
        results.append((e.store.get(g + 1), np.asarray(out[0]).tolist()))
    assert results[0] == results[1] == ({"n": 4}, [0, 1, 2, 3]), results

    # A first batch whose new keys overflow a shard's 64-slot table: the
    # whole call runs again on grown tables, and the result is unchanged.
    engines = [
        Engine(real_job_2(keygroups_per_op=4), 2,
               config=ExecutionConfig.jit(mesh=m), **kw)
        for m in (None, mesh)
    ]
    stream = airline_stream(StreamSpec(rate=600.0, seed=6))
    batches = [next(stream) for _ in range(3)]
    for eng in engines:
        for k, v, ts in batches:
            eng.push_source("airline", k, v, ts)
            eng.tick()
        for _ in range(4):
            eng.tick()
        eng._jit.sync_store()
    a, b = engines
    assert a.metrics.table_retries >= 1 and b.metrics.table_retries >= 1
    assert len(a.metrics.sink_outputs) == len(b.metrics.sink_outputs)
    for (k1, v1, t1), (k2, v2, t2) in zip(
        a.metrics.sink_outputs, b.metrics.sink_outputs
    ):
        assert k1 == k2 and t1 == t2
        np.testing.assert_allclose(v1[1], v2[1], rtol=1e-9, atol=1e-9)
    for kg in range(a.topology.num_keygroups):
        sa, sb = a.store.get(kg), b.store.get(kg)
        for name in sa:
            assert list(sa[name]) == list(sb[name])
    print("SHARDED-PARITY-OK")
    """
)


def test_shard_map_two_device_parity():
    """Two forced host devices: run-sharded keyed-table execution merges
    per-shard state/output deltas into the same result as the plain call.
    Subprocess: the device count must be forced before any jax import."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", SHARDED_PARITY],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARDED-PARITY-OK" in proc.stdout


# ---------------------------------------------------------------------------
# keyed tables with several aggregates
# ---------------------------------------------------------------------------

AGG = ("sum", "sum", "min", "max")  # count-if, sum, min, max


def _agg_terms(prices):
    return (
        (prices < 100).astype(np.int64),
        prices.astype(np.float64) / 7.0,
        prices,
        prices,
    )


def _agg_loop(dicts, codes, kg, prices):
    """The python reference: each key's row folded tuple by tuple."""
    out = []
    for c, k, p in zip(codes.tolist(), kg.tolist(), prices.tolist()):
        terms = (int(p < 100), p / 7.0, p, p)
        row = dicts[k].get(c)
        if row is None:
            row = (0 + terms[0], 0.0 + terms[1], terms[2], terms[3])
        else:
            row = (row[0] + terms[0], row[1] + terms[1], min(row[2], p), max(row[3], p))
        dicts[k][c] = row
        out.append(row)
    return out


def _agg_table(cap):
    from repro.engine import jitexec as jx
    from repro.engine.topology import StateField

    f = StateField("t", "table", dtype=np.int64, key_encode=int,
                   key_decode=int, combine=AGG)
    table = jx._empty_table(cap, f)
    # The sum of floats keeps its own dtype beside the integer columns.
    vals = list(table.vals)
    vals[1] = vals[1].astype(np.float64)
    return table._replace(vals=tuple(vals))


def _hot_and_new(rng, call, nb):
    codes = rng.integers(0, 40 + 60 * call, size=nb)
    codes[rng.random(nb) < 0.5] = 7  # the hot key: long segments
    return codes, rng.integers(1, 200, size=nb), nb - 12


#: Edge cases of the table update, one scenario each: (capacity, tuples a
#: call, one function per call giving codes, prices and the valid count).
AGG_CASES = {
    # A hot key holding half of each segment, new keys in each call.
    "hot_key_and_new_keys": (1024, 512, [_hot_and_new] * 3),
    # 40 keys, then 24 new ones beside hits: the table ends exactly full.
    "fills_to_capacity": (64, 192, [
        lambda rng, c, nb: (np.tile(rng.permutation(np.arange(100, 140)), 5)[:nb],
                            rng.integers(1, 200, size=nb), 160),
        lambda rng, c, nb: (np.concatenate([np.arange(140, 164), np.tile(
            np.arange(100, 140), 5)])[:nb], rng.integers(1, 200, size=nb), 192),
    ]),
    # 40 keys, then 30 new ones: the call overflows the 64 slots.
    "overflows": (64, 128, [
        lambda rng, c, nb: (np.tile(np.arange(100, 140), 4)[:nb],
                            rng.integers(1, 200, size=nb), 120),
        lambda rng, c, nb: (np.concatenate([np.arange(200, 230), np.tile(
            np.arange(100, 140), 3)])[:nb], rng.integers(1, 200, size=nb), 128),
    ]),
    # The second call hits existing keys only.
    "only_hits": (128, 256, [
        lambda rng, c, nb: (rng.integers(0, 50, size=nb),
                            rng.integers(1, 200, size=nb), 250),
        lambda rng, c, nb: (rng.integers(0, 50, size=nb),
                            rng.integers(1, 200, size=nb), 256),
    ]),
    # Every call brings keys the table has never held.
    "only_new_keys": (256, 128, [
        lambda rng, c, nb: (rng.integers(50 * c, 50 * c + 50, size=nb),
                            rng.integers(1, 200, size=nb), 120),
    ] * 3),
    # A call of padding alone changes nothing but the epoch.
    "padding_only": (128, 128, [
        lambda rng, c, nb: (rng.integers(0, 60, size=nb),
                            rng.integers(1, 200, size=nb), 100),
        lambda rng, c, nb: (rng.integers(0, 60, size=nb),
                            rng.integers(1, 200, size=nb), 0),
    ]),
    # New codes below and above every code in the table, beside hits.
    "new_codes_below_and_above": (256, 256, [
        lambda rng, c, nb: (rng.integers(1000, 1050, size=nb),
                            rng.integers(1, 200, size=nb), 256),
        lambda rng, c, nb: (rng.choice(np.concatenate([
            np.arange(0, 10), np.arange(1000, 1050), np.arange(5000, 5010)]),
            size=nb), rng.integers(1, 200, size=nb), 240),
    ]),
    # Negative prices: min and max below zero, negative sums.
    "negative_under_min_max": (256, 256, [
        lambda rng, c, nb: (rng.integers(0, 30 + 30 * c, size=nb),
                            rng.integers(-500, 0, size=nb), 250),
    ] * 3),
}


def _check_view(table):
    """The code-sorted view: a permutation of the slots, real codes in
    ascending order, then the EMPTY slots ascending by slab index."""
    from repro.engine import jitexec as jx

    perm = np.asarray(table.perm)
    codes = np.asarray(table.codes)
    cnt = int(table.cnt)
    assert sorted(perm.tolist()) == list(range(len(perm)))
    assert np.all(np.diff(codes[perm[:cnt]]) > 0)
    assert np.all(codes[perm[cnt:]] == jx.EMPTY_CODE)
    assert perm[cnt:].tolist() == list(range(cnt, len(perm)))


@pytest.mark.parametrize("case", list(AGG_CASES))
def test_keyed_running_agg_matches_a_numpy_loop(case):
    """Four columns under sum (a count-if and a float sum), min and max
    against a python fold, call by call, in each edge case of
    ``AGG_CASES``: the running values, the table's entries in insertion
    order, and its sorted view.  A call whose new keys overflow the table
    leaves it held as it came in, its count reading the keys it needed."""
    import copy

    import jax
    import jax.numpy as jnp

    from repro.engine import jitexec as jx

    cap, nb, calls = AGG_CASES[case]
    rng = np.random.default_rng(3)
    num_kg = 4
    dicts = [dict() for _ in range(num_kg)]
    table = _agg_table(cap)
    agg = jax.jit(jx.keyed_running_agg, static_argnames="combines")
    for call, make in enumerate(calls):
        codes, prices, n = make(rng, call, nb)
        codes = np.asarray(codes, np.int64)
        prices = np.asarray(prices, np.int64)
        kg = codes % num_kg
        valid = np.arange(nb) < n
        new, running = agg(
            table, jnp.asarray(codes), jnp.asarray(kg),
            tuple(jnp.asarray(t) for t in _agg_terms(prices)),
            jnp.asarray(valid), combines=AGG,
        )
        after = copy.deepcopy(dicts)
        expect = _agg_loop(after, codes[:n], kg[:n], prices[:n])
        entries = sum(len(d) for d in after)
        assert int(new.cnt) == entries
        if entries > cap:
            held = jx._hold_on_overflow({"t": table}, {"t": new})["t"]
            assert int(held.cnt) == entries
            for a, b in zip(jax.tree_util.tree_leaves(held._replace(cnt=table.cnt)),
                            jax.tree_util.tree_leaves(table)):
                assert np.array_equal(np.asarray(a), np.asarray(b))
            continue
        if n == 0:  # padding alone: every leaf but the epoch as it was
            for a, b in zip(new[:-1], table[:-1]):
                for x, y in zip(jax.tree_util.tree_leaves(a),
                                jax.tree_util.tree_leaves(b)):
                    assert np.array_equal(np.asarray(x), np.asarray(y))
        dicts, table = after, new
        got = [np.asarray(r)[:n] for r in running]
        for j in (0, 2, 3):  # integer columns: exact
            assert got[j].tolist() == [row[j] for row in expect]
        np.testing.assert_allclose(got[1], [row[1] for row in expect], rtol=1e-12)
        assert int(table.epoch) == call + 2
        cnt = int(table.cnt)
        t_codes = np.asarray(table.codes)[:cnt]
        t_seq = np.asarray(table.seq)[:cnt]
        t_owner = np.asarray(table.owner)[:cnt]
        for k in range(num_kg):
            mine = np.flatnonzero(t_owner == k)
            order = mine[np.argsort(t_seq[mine], kind="stable")]
            assert t_codes[order].tolist() == list(dicts[k])
            for j in (0, 2, 3):
                col = np.asarray(table.vals[j])[order]
                assert col.tolist() == [row[j] for row in dicts[k].values()]
        _check_view(table)
    assert case != "overflows" or int(table.cnt) == 40
    assert case != "fills_to_capacity" or int(table.cnt) == cap


@pytest.mark.parametrize("combine", ["sum", "max"])
@pytest.mark.parametrize("n", [1, 6, 1024, 3072, 8192])
def test_prefix_in_two_levels_matches_numpy(n, combine):
    """The table update's int32 prefix sums and maxima, in blocks of up to
    1,024 and a carry between blocks, against numpy's one pass."""
    import jax.numpy as jnp

    from repro.engine import jitexec as jx

    x = np.random.default_rng(n).integers(0, 1000, size=n).astype(np.int32)
    got = np.asarray(jx._prefix(jnp.asarray(x), combine))
    want = np.cumsum(x) if combine == "sum" else np.maximum.accumulate(x)
    assert got.dtype == np.int32 and got.tolist() == want.tolist()


@pytest.mark.parametrize("columns", [1, 7])
def test_one_sort_per_call_whatever_the_columns(columns):
    """The lowered program of a call holds one sort, for one column as for
    q17's seven."""
    import jax
    from repro.engine import jitexec as jx
    from repro.engine.topology import StateField

    combine = "sum" if columns == 1 else ("sum",) * 4 + ("min", "max", "sum")
    f = StateField("t", "table", dtype=np.int64, key_encode=int,
                   key_decode=int, combine=combine)
    table = jx._empty_table(256, f)
    nb = 128
    x = np.arange(nb, dtype=np.int64)

    def call(table, codes, kg, valid):
        return jx.keyed_running_agg(
            table, codes, kg, (codes,) * columns, valid, f.combines
        )

    hlo = jax.jit(call).lower(table, x, x % 4, x < 100).as_text()
    assert hlo.count("stablehlo.sort") == 1, hlo.count("stablehlo.sort")


@pytest.mark.parametrize("columns,dtype", [
    (1, np.int64), (7, np.int64), (1, np.float64), (3, np.int16),
])
def test_no_64_bit_scatter_and_three_scatters_per_call(columns, dtype):
    """The lowered program of a call scatters no 64-bit operand, indices
    or updates, and holds at most three scatters (the radix sort's own
    lie on the TPU's path alone), for one column as for q17's seven, and
    for float64 and 16-bit columns: the values move through int32 index
    maps and rows of 32-bit words."""
    import re

    import jax
    from repro.engine import jitexec as jx
    from repro.engine.topology import StateField

    combine = "sum" if columns == 1 else ("sum",) * (columns - 3) + (
        "min", "max", "sum")
    f = StateField("t", "table", dtype=dtype, key_encode=int,
                   key_decode=int, combine=combine)
    table = jx._empty_table(256, f)
    nb = 128
    x = np.arange(nb, dtype=np.int64)
    terms = (x.astype(dtype),) * columns

    def call(table, codes, kg, terms, valid):
        return jx.keyed_running_agg(table, codes, kg, terms, valid, f.combines)

    hlo = jax.jit(call).lower(table, x, x % 4, terms, x < 100).as_text()
    types = re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \(([^)]*)\) ->', hlo, re.DOTALL
    )
    assert hlo.count('"stablehlo.scatter"(') == len(types)
    assert 1 <= len(types) <= 3, len(types)
    for sig in types:
        assert not re.search(r"[x<](?:ui|i|f)64>", sig), sig


@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.float16, "bfloat16"])
def test_narrow_columns_match_a_numpy_fold(dtype):
    """Columns of 8 and 16 bits under sum, min and max, whose slab writes
    ride widened to 32-bit words, against a fold in the column's dtype:
    small values, so every float sum is exact whatever its order."""
    import jax
    import jax.numpy as jnp

    from repro.engine import jitexec as jx
    from repro.engine.topology import StateField

    dtype = jnp.dtype(dtype)
    combines = ("sum", "min", "max")
    f = StateField("t", "table", dtype=dtype, key_encode=int,
                   key_decode=int, combine=combines)
    table = jx._empty_table(256, f)
    agg = jax.jit(jx.keyed_running_agg, static_argnames="combines")
    rng = np.random.default_rng(5)
    rows, nb = {}, 128
    for call in range(3):
        codes = rng.integers(0, 20 + 20 * call, size=nb).astype(np.int64)
        terms = rng.integers(-8, 9, size=nb)
        table, running = agg(
            table, jnp.asarray(codes), jnp.asarray(codes % 4),
            (jnp.asarray(terms, dtype),) * 3, jnp.asarray(np.ones(nb, bool)),
            combines=combines,
        )
        assert all(r.dtype == dtype for r in running + table.vals)
        got = np.stack([np.asarray(r, np.float64) for r in running], 1)
        for i, (c, t) in enumerate(zip(codes.tolist(), terms.tolist())):
            s, lo, hi = rows.get(c, (0, t, t))
            rows[c] = (s + t, min(lo, t), max(hi, t))
            assert got[i].tolist() == list(rows[c])
        cnt = int(table.cnt)
        vals = np.stack([np.asarray(v, np.float64)[:cnt] for v in table.vals], 1)
        stored = dict(zip(np.asarray(table.codes)[:cnt].tolist(), vals.tolist()))
        assert stored == {c: list(r) for c, r in rows.items()}
        _check_view(table)


#: sha256 of a one-column table's running sums and leaves after two Job 2
#: batches, as the one-column running sum gave them before tables took several
#: columns (the pinned bytes of the float64 trajectory on the CPU).
JOB2_PIN = "554fff1ba14929a2e0ab136b815736e6a0c50ea8df0f58a197b9360da2c3551d"


def test_one_column_case_is_bit_identical_to_the_running_sum():
    """Two Job 2 batches through a one-column table: the running sums, the
    codes, values, sequence numbers, owners, sorted view, count and epoch
    are the bytes the running sum gave before the table took several
    columns."""
    import hashlib

    from repro.engine import jitexec as jx
    import jax.numpy as jnp

    stream = airline_stream(StreamSpec(rate=3000.0, seed=21))
    cap = 8192
    table = jx.TableState(
        codes=jnp.full(cap, jx.EMPTY_CODE, dtype=jnp.int64), vals=(jnp.zeros(cap),),
        seq=jnp.zeros(cap, dtype=jnp.int64), owner=jnp.zeros(cap, dtype=jnp.int32),
        perm=jnp.arange(cap, dtype=jnp.int32), cnt=jnp.zeros((), dtype=jnp.int32),
        epoch=jnp.ones((), dtype=jnp.int64),
    )
    h = hashlib.sha256()
    for _ in range(2):
        _, v, _ = next(stream)
        n = len(v)
        nb = 1 << (n - 1).bit_length()
        codes = np.zeros(nb, np.int64)
        codes[:n] = (v["plane"] << 32) | v["year"]
        add = np.zeros(nb)
        add[:n] = v["dep_delay"] + v["arr_delay"]
        table, (running,) = jx.keyed_running_agg(
            table, jnp.asarray(codes), jnp.asarray(codes % 7), (jnp.asarray(add),),
            jnp.asarray(np.arange(nb) < n), ("sum",),
        )
        h.update(np.asarray(running).tobytes())
        for leaf in table:
            h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == JOB2_PIN


def _q17_runtime(kgs=4, expected_rows=0):
    from repro.data.nexmark import nexmark_q17
    from repro.engine.executor import EngineMetrics
    from repro.engine.jitexec import JitRuntime
    from repro.engine.state import KeyedStore

    topo = nexmark_q17(keygroups_per_op=kgs, expected_rows=expected_rows)
    store = KeyedStore(topo.num_keygroups)
    rt = JitRuntime(topo, store, EngineMetrics(), topo.kg_operator())
    op = topo._resolve("q17")
    return rt, store, op, topo.kg_base(op)


def _q17_call(rt, op, base, bids, kgs=4):
    """One q17 call over runs of key groups auction % kgs; the bids in run
    order and the rows the call emitted."""
    kg = bids["auction"] % kgs
    order = np.argsort(kg, kind="stable")
    bids = bids[order]
    counts = np.bincount(kg, minlength=kgs)
    ends = np.cumsum(counts)
    (_, ov, _), _ = rt.execute(
        op, [base + k for k in range(kgs)], (ends - counts).tolist(),
        ends.tolist(), bids["auction"].copy(), bids, np.zeros(len(bids)),
    )
    return bids, ov


def _q17_loop(dicts, bids, kgs=4):
    from repro.data.nexmark import DAY_MS

    out = []
    for a, p, dt in bids[["auction", "price", "date_time"]].tolist():
        key = (a, dt // DAY_MS)
        r1, r3 = int(p < 10_000), int(p >= 1_000_000)
        row = dicts[a % kgs].get(key)
        if row is None:
            row = (1, r1, 1 - r1 - r3, r3, p, p, p)
        else:
            n, c1, c2, c3, lo, hi, s = row
            row = (n + 1, c1 + r1, c2 + 1 - r1 - r3, c3 + r3,
                   min(lo, p), max(hi, p), s + p)
        dicts[a % kgs][key] = row
        out.append(key + row[:6] + (row[6] // row[0], row[6]))
    return out


def _bids(seed, n, auctions):
    from repro.data.nexmark import BID_SCHEMA, DAY_MS

    rng = np.random.default_rng(seed)
    bids = np.zeros(n, dtype=BID_SCHEMA.value)
    bids["auction"] = 1000 + rng.integers(0, auctions, size=n)
    bids["auction"][rng.random(n) < 0.5] = 1000  # the hot auction
    bids["price"] = np.round(10.0 ** (rng.random(n) * 6) * 100).astype(np.int64)
    bids["date_time"] = 20_000 * DAY_MS + rng.integers(-1, 2, size=n) * 3_600_000
    return bids


def test_q17_table_grows_retries_and_pushes_after_a_migration():
    """q17's seven-column table through the runtime: a first call whose
    new keys overflow the 64-slot table runs again on a grown one, every
    row equals the python fold, the materialized dicts hold ``{key: row}``
    in insertion order, and a key group whose dict was replaced (a
    migration's install) is pushed back and continued from."""
    from repro.data.nexmark import Q17_FIELDS

    rt, store, op, base = _q17_runtime()
    dicts = [dict() for _ in range(4)]
    m = rt._metrics
    for step, bids in enumerate([_bids(1, 600, 80), _bids(2, 400, 120)]):
        bids, ov = _q17_call(rt, op, base, bids)
        expect = _q17_loop(dicts, bids)
        got = list(zip(*(ov[name].tolist() for name in Q17_FIELDS)))
        assert got == expect
        if step == 0:
            assert (m.table_retries, m.table_grows) == (1, 1)
            assert m.table_grow_s > 0
        rt.sync_store()
        for k in range(4):
            assert list(store.get(base + k)["q17"].items()) == list(dicts[k].items())
    # A migration installs a changed dict: the next call starts from it.
    kg = base + 1
    rt.ensure_dict(kg)
    moved = dict(store.get(kg)["q17"])
    key = next(iter(moved))
    moved[key] = (5, 1, 2, 2, 1, 9_999_999, 12_345_678)
    store.put(kg, {"q17": moved})
    rt.invalidate(kg)
    dicts[1] = dict(moved)
    before = m.device_fetches
    bids, ov = _q17_call(rt, op, base, _bids(3, 300, 150))
    expect = _q17_loop(dicts, bids)
    got = list(zip(*(ov[name].tolist() for name in Q17_FIELDS)))
    assert got == expect
    assert m.device_fetches - before >= 4 + 7  # the push read the table
    rt.sync_store()
    for k in range(4):
        assert list(store.get(base + k)["q17"].items()) == list(dicts[k].items())


def test_q17_floor_division_is_exact():
    """q17's average divides without an int64 division: exact against
    numpy's ``//`` wherever the quotient fits 31 bits, large sums and
    counts near 2**31 included."""
    import jax.numpy as jnp

    from repro.data.nexmark import floor_div
    from repro.engine import jitexec  # noqa: F401  (int64 on the device)

    rng = np.random.default_rng(4)
    n = 200_000
    b = rng.integers(1, 2**31, n)
    a = rng.integers(-(2**31) + 1, 2**31 - 1, n) * b + rng.integers(0, 2**31, n) % b
    counts = rng.integers(1, 5_000, n)
    sums = rng.integers(100, 10**8, n) * counts + rng.integers(0, 5_000, n) % counts
    edges = np.array([0, 1, 10**12, -1, -(10**12), 2**31 - 1])
    divisors = np.array([1, 1, 465, 7, 2**31 - 1, 1])
    for x, y in ((a, b), (sums, counts), (edges, divisors)):
        got = np.asarray(floor_div(jnp.asarray(x), jnp.asarray(y)))
        np.testing.assert_array_equal(got, x // y)


def test_a_grow_runs_no_device_program():
    """A capacity bump pads every slab leaf on the host: no program is
    compiled at the new shape, the entries keep their slots, and the
    sorted view's EMPTY tail extends with the fresh slab indices."""
    import jax
    from jax import monitoring

    from repro.engine.jitexec import EMPTY_CODE

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None
    )
    rt, store, op, base = _q17_runtime()
    ost = rt._by_op[op]
    _q17_call(rt, op, base, _bids(1, 60, 30))
    before = ost.cols["q17"]
    old = [np.asarray(x) for x in jax.tree_util.tree_leaves(before)]
    n = len(compiles)
    rt._grow(ost, ost.fields[0], 300)
    assert len(compiles) == n and ost.caps["q17"] == 1024
    after = ost.cols["q17"]
    for was, now in zip(old, jax.tree_util.tree_leaves(after)):
        now = np.asarray(now)
        np.testing.assert_array_equal(now[:64] if now.ndim else now, was)
    assert (np.asarray(after.codes)[64:] == EMPTY_CODE).all()
    assert np.asarray(after.perm)[64:].tolist() == list(range(64, 1024))


def test_a_table_compiles_ahead_for_its_expected_entries():
    """q17's table, expected to reach 1,024 rows: its first grow compiles
    the call at every capacity up to 2,048 slots, so the calls that later
    grow into them compile nothing, and every row still equals the python
    fold."""
    from jax import monitoring

    from repro.data.nexmark import Q17_FIELDS

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None
    )
    rt, store, op, base = _q17_runtime(expected_rows=1024)
    ost = rt._by_op[op]
    dicts = [dict() for _ in range(4)]
    caps, after_first = [], None
    for seed, auctions in ((1, 80), (2, 300), (3, 500), (4, 900), (5, 900)):
        bids, ov = _q17_call(rt, op, base, _bids(seed, 600, auctions))
        got = list(zip(*(ov[name].tolist() for name in Q17_FIELDS)))
        assert got == _q17_loop(dicts, bids)
        caps.append(ost.caps["q17"])
        if after_first is None:
            after_first = len(compiles)
    # A relaunch after an overflow (second call) and a grow before a call
    # (last call), both into capacities compiled ahead.
    assert (caps[0], caps[1], caps[-1]) == (256, 1024, 2048)
    assert sorted(c for (_, ((_, c),)) in ost.compiled) == [256, 512, 1024, 2048]
    assert len(compiles) == after_first  # the later grows compiled nothing
    assert rt._metrics.table_retries == 2


Q17_SHARDED = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    from repro.data.nexmark import bid_stream, nexmark_q17
    from repro.data.synthetic import StreamSpec
    from repro.engine import Engine, ExecutionConfig

    mesh = jax.make_mesh((2,), ("nodes",), devices=jax.devices()[:2])
    engines = [
        Engine(nexmark_q17(keygroups_per_op=4, expected_rows=1024), 2,
               service_rate=1e9, seed=0,
               collect_sinks=True, config=ExecutionConfig.jit(mesh=m))
        for m in (None, mesh)
    ]
    for eng in engines:
        stream = bid_stream(StreamSpec(rate=1500.0, seed=4), midnight_bid=3000)
        for _ in range(5):
            eng.push_source("bid", *next(stream))
            eng.tick()
        for _ in range(3):
            eng.tick()
        eng._jit.sync_store()
    a, b = engines
    assert b.metrics.jit_calls > 0
    assert a.metrics.table_retries >= 1 and b.metrics.table_retries >= 1
    for eng in engines:  # grown into capacities compiled ahead
        ost = eng._jit._by_op[eng.topology._resolve("q17")]
        assert ost.compiled and set(ost.compiled) & ost.seen_keys
    assert b.metrics.table_grow_s > 0
    assert a.metrics.sink_outputs == b.metrics.sink_outputs
    for kg in range(a.topology.num_keygroups):
        assert list(a.store.get(kg).items()) == list(b.store.get(kg).items())
    print("Q17-SHARDED-OK")
    """
)


def test_q17_sharded_tables_match_the_plain_call():
    """Two forced host devices: q17's key-group-sharded seven-column
    tables, through overflows and grows, give the plain call's rows and
    dicts exactly."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-c", Q17_SHARDED],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "Q17-SHARDED-OK" in proc.stdout

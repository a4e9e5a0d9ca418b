"""Differential conformance harness for engine data-plane equivalence.

One scenario — a topology, randomized sources, optional migrations and
backpressure — is driven through every execution configuration:

* ``soa+seg+schema``     — SoA work queues, segment-vectorized ``fn_seg``,
  declared schemas honored (columnar structured-array edges);
* ``soa+seg+schema+jit`` — same plus the compiled tier: operators declaring
  ``fn_jit`` execute contiguous segments as jitted programs over device
  state columns (``repro.engine.jitexec``); operators without ``fn_jit``
  fall back bit-identically to the numpy ``fn_seg``;
* ``soa+seg+schema+jit+superstep`` — same plus ``Engine(superstep=True)``:
  eligible whole ticks fuse route → drain → ``fn_jit`` into one device
  program (``repro.engine.superstep``), falling back to the classic tick —
  after materializing device-pending columns — whenever a tick is not
  fusible, so every pinned field (including migration blobs) must still
  match;
* ``soa+seg``   — schemas stripped (``use_schema=False``): every edge
  carries the object-array representation;
* ``soa+fn``    — SoA queues with ``fn_seg`` also stripped (every run takes
  the per-run ``fn``);
* ``deque+fn``  — the legacy per-entry deque queue (always per-run ``fn``),
  the original oracle;
* ``soa+seg+schema+workers`` — the multi-worker host runtime
  (``ExecutionConfig.workers(2)``): the same topology sharded over two real
  OS worker processes (:class:`repro.engine.cluster.ClusterEngine`), nodes
  assigned in contiguous ascending blocks.  Because the exchange merges
  each operator's cross-worker contributions in ascending worker order —
  which equals the single-process node-ascending flush order under
  contiguous blocks — this configuration is pinned **bit-identical** in
  every tuple-carrying field: queues, states, sink values *and order*,
  credits, routing and migration envelope bytes (no sink order
  normalization is needed while the node → worker map stays monotone).
  The one relaxation is float *statistics summation*: the coordinator
  folds per-worker partial sums of the SPL usage windows, so key groups
  with non-dyadic per-tuple costs may differ from the oracle's single
  running sum by a few ulp — ``kg_load`` and ``pair_rate`` are compared
  with :data:`WORKERS_FLOAT_RTOL` (everything integer-derived stays
  exact).  See docs/execution_tiers.md for the full contract.

The run results must be *bit-identical*: every tuple-flow metric, the sink
outputs (values and order), every key group's operator state (including dict
insertion order — it decides TopK tie-breaks and pickle bytes), the folded
SPL statistics (loads, arrival rates, sparse pair rates, state sizes), the
routing table, the per-node queue costs, and the migration envelope bytes
(hashed per install — the proof that a cross-worker serialize → install
round trip ships exactly the single-process blob).  Envelope bytes encode
backlog batches in the configuration's own edge encoding, so they are
pinned only across configurations sharing the base's schema encoding — the
``+workers`` comparison that matters; schema-stripped configs pickle
object-array backlogs and are exempt from that one field.

One documented escape hatch: the jit configuration's *multi-term float
reductions* (running sums via ``jnp.cumsum``) may diverge from the oracle's
strict left-to-right association in the last bits, because XLA's scan uses
a different reduction order.  ``assert_equivalent`` therefore compares the
``+jit`` configuration's ``sink_outputs`` and ``states`` with
:data:`JIT_FLOAT_RTOL`/:data:`JIT_FLOAT_ATOL` on floats — structure, ints,
strings, ordering and every other pinned field stay exact (integer tuple
flow must never inherit the tolerance: jit operators' float outputs must
not feed partition keys, see docs/operator_authoring.md).

This is the required check for new operators, new ``fn_seg`` ports and new
schema declarations: add a topology + feeder entry to ``JOBS`` (NEXmark q17
is one: a multi-column keyed table under sum, min and max) (or call
:func:`run_configs` directly) and assert with :func:`assert_equivalent`.
See ``tests/test_real_jobs_conformance.py`` for the real-job instantiation
and ``docs/operator_authoring.md`` for the authoring contract.

:func:`make_fuzz_topology` extends the harness with *randomized* topologies
— random fan-out DAGs, key dtypes, schema/no-schema mixes over a library of
generic operators — driven by hypothesis in
``tests/test_conformance_fuzz.py``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import math

import numpy as np

from repro.data.jobs import make_real_job_1, real_job_2, real_job_3, real_job_4
from repro.data.nexmark import bid_stream, nexmark_q17
from repro.data.synthetic import (
    StreamSpec,
    airline_stream,
    weather_stream,
    wiki_edit_stream,
)
from repro.engine import ExecutionConfig, make_engine
from repro.engine.topology import (
    OperatorSpec,
    Schema,
    StateField,
    StateSchema,
    Topology,
)

# The full configuration matrix, keyed by ExecutionConfig.name.  The workers
# configuration sits before the jit ones so its processes fork before any
# jax state exists in this process.
CONFIGS = tuple(
    (c.name, c)
    for c in (
        ExecutionConfig.typed(),
        ExecutionConfig.seg(),
        ExecutionConfig(use_fn_seg=False, use_schema=False),
        ExecutionConfig.oracle(),
        ExecutionConfig.workers(2),
        ExecutionConfig.jit(),
        ExecutionConfig.superstep(),
    )
)

# The hypothesis fuzz suites draw dozens of examples; they skip the workers
# configuration (process pool per example) to stay fast — the fixed jobs
# and the cluster suite pin it.
FUZZ_CONFIGS = tuple(
    (name, c) for name, c in CONFIGS if c.num_workers == 1
)

# The documented XLA reduction-order tolerance (see module docstring): only
# the ``+jit`` configuration's floats are compared with it, and only in the
# ``sink_outputs``/``states`` fields — everything else stays bit-exact.
JIT_FLOAT_RTOL = 1e-9
JIT_FLOAT_ATOL = 1e-9
_TOLERANT_FIELDS = ("sink_outputs", "states")

# The workers configuration's documented statistics relaxation (see module
# docstring): per-worker partial sums vs the oracle's single running sum —
# a few ulp on non-dyadic cost charges, nothing more.
WORKERS_FLOAT_RTOL = 1e-12
WORKERS_FLOAT_ATOL = 1e-18
_WORKERS_TOLERANT_FIELDS = ("kg_load", "pair_rate")

METRIC_FIELDS = (
    "processed_tuples",
    "emitted_tuples",
    "sink_tuples",
    "cross_node_tuples",
    "intra_node_tuples",
    "dropped_credits",
)


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One randomized drive of a topology, identical across configurations."""

    name: str
    ticks: int = 14
    drain_ticks: int = 8
    service_rate: float = 1e9
    num_nodes: int = 4
    seed: int = 0
    # Ticks at which a random key group is redirected; its state is installed
    # at the destination one tick later (traffic in between exercises the
    # router's in-flight buffering and the non-contiguous fn fallback).
    migrate_at: tuple[int, ...] = ()


def normalize(obj):
    """Recursively convert to comparable plain structures.

    Dicts become ordered item lists — insertion order is part of the
    conformance contract (it decides stable-sort tie-breaks and pickle
    bytes, hence migration blobs and ``kg_state_bytes``).
    """
    if isinstance(obj, dict):
        return ("dict", [(normalize(k), normalize(v)) for k, v in obj.items()])
    if isinstance(obj, (list, tuple)):
        return ("seq", [normalize(x) for x in obj])
    if isinstance(obj, np.ndarray):
        return ("array", obj.tolist())
    if isinstance(obj, np.generic):
        return obj.item()
    return obj


def run_scenario(topo_factory, feeder_factory, scenario, config):
    """Drive one :class:`ExecutionConfig` through the scenario; return a
    result dict of everything the equivalence contract pins."""
    topo = topo_factory()
    eng = make_engine(
        topo,
        scenario.num_nodes,
        config=config,
        service_rate=scenario.service_rate,
        seed=scenario.seed,
    )
    feeds = feeder_factory()
    rng = np.random.default_rng(scenario.seed + 1)
    in_flight: list[tuple[int, int, int]] = []
    migration_blobs: list[str] = []
    for t in range(scenario.ticks):
        if t in scenario.migrate_at:
            # Drawn unconditionally so the rng stream (and therefore every
            # subsequent choice) is identical across configurations.
            kg = int(rng.integers(0, topo.num_keygroups))
            dst = int(rng.integers(0, eng.num_nodes))
            if not eng.router.is_in_flight(kg):
                eng.redirect(kg, dst)
                in_flight.append((t, kg, dst))
        for op, it in feeds.items():
            keys, values, ts = next(it)
            eng.push_source(op, keys, values, ts)
        eng.tick()
        for item in list(in_flight):
            t0, kg, dst = item
            if t >= t0 + 1:
                blob = eng.serialize(kg)
                migration_blobs.append(hashlib.sha256(blob).hexdigest())
                eng.install(kg, dst, blob)
                in_flight.remove(item)
    for _ in range(scenario.drain_ticks):
        eng.tick()
    snap = eng.end_period()
    eng.finalize()  # multi-worker: gather states/metrics, stop the pool
    return {
        "metrics": {m: getattr(eng.metrics, m) for m in METRIC_FIELDS},
        "sink_outputs": normalize(eng.metrics.sink_outputs),
        "states": [normalize(s) for _, s in eng.store.items()],
        "kg_load": snap.kg_load.tolist(),
        "kg_tuple_rate": snap.kg_tuple_rate.tolist(),
        "kg_state_bytes": snap.kg_state_bytes.tolist(),
        "pair_src": snap.out_pairs.src.tolist(),
        "pair_dst": snap.out_pairs.dst.tolist(),
        "pair_rate": snap.out_pairs.rate.tolist(),
        "alloc": eng.router.table.tolist(),
        "queue_costs": eng.queue_costs(),
        "migration_blobs": migration_blobs,
        "seg_calls": eng.metrics.seg_calls,
        "seg_tuples": eng.metrics.seg_tuples,
        "typed_batches": eng.metrics.typed_batches,
        "jit_calls": eng.metrics.jit_calls,
        "jit_compiles": eng.metrics.jit_compiles,
        "jit_host_syncs": eng.metrics.jit_host_syncs,
    }


def run_configs(topo_factory, feeder_factory, scenario, configs=CONFIGS):
    """Run every execution configuration; returns {config name: result}."""
    return {
        name: run_scenario(topo_factory, feeder_factory, scenario, config)
        for name, config in configs
    }


def approx_equal(a, b, rtol: float, atol: float) -> bool:
    """Structural equality over normalized results with float tolerance.

    Structure, ints, bools and strings must match exactly (bool/int/float
    type flips count as differences); only float *values* may differ within
    the tolerance — the shape of the documented XLA reduction-order escape
    hatch.
    """
    if type(a) is not type(b):
        return False
    if isinstance(a, float):
        return a == b or math.isclose(a, b, rel_tol=rtol, abs_tol=atol)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(
            approx_equal(x, y, rtol, atol) for x, y in zip(a, b)
        )
    return a == b


def assert_equivalent(results: dict[str, dict]) -> None:
    """All configurations must agree on every pinned field, bit for bit —
    except the ``+jit`` configuration's float values in the tolerant fields
    (see module docstring)."""
    names = list(results)
    base_name, base = names[0], results[names[0]]
    for name in names[1:]:
        other = results[name]
        tol = "+jit" in name
        for field, expect in base.items():
            if field in (
                "seg_calls",
                "seg_tuples",
                "typed_batches",
                "jit_calls",
                "jit_compiles",
                "jit_host_syncs",
            ):
                continue  # differs by construction across configurations
            if field == "migration_blobs" and (tol or "schema" not in name):
                # Envelope byte equality is pinned between same-encoding
                # configurations only: schema-stripped configs legitimately
                # pickle object-array backlogs where typed configs ship raw
                # buffer slices, and the jit configurations' documented
                # float tolerance makes byte equality too strong.  The
                # claim that matters — a cross-worker migration envelope is
                # byte-identical to the single-process one — is exactly the
                # base vs ``+workers`` comparison, which stays exact.
                continue
            got = other[field]
            if "+workers" in name and field in _WORKERS_TOLERANT_FIELDS:
                assert approx_equal(
                    got, expect, WORKERS_FLOAT_RTOL, WORKERS_FLOAT_ATOL
                ), (
                    f"{base_name} vs {name}: {field} differs beyond the "
                    f"workers statistics-summation tolerance:"
                    f"\n  {str(expect)[:400]}\n  {str(got)[:400]}"
                )
                continue
            if field == "states":
                for kg, (a, b) in enumerate(zip(expect, got)):
                    same = (
                        approx_equal(a, b, JIT_FLOAT_RTOL, JIT_FLOAT_ATOL)
                        if tol
                        else a == b
                    )
                    assert same, (
                        f"{base_name} vs {name}: state of key group {kg} differs:"
                        f"\n  {a!r}\n  {b!r}"
                    )
                continue
            if tol and field in _TOLERANT_FIELDS:
                assert approx_equal(
                    got, expect, JIT_FLOAT_RTOL, JIT_FLOAT_ATOL
                ), (
                    f"{base_name} vs {name}: {field} differs beyond the "
                    f"jit float tolerance:"
                    f"\n  {str(expect)[:400]}\n  {str(got)[:400]}"
                )
                continue
            assert got == expect, (
                f"{base_name} vs {name}: {field} differs:"
                f"\n  {str(expect)[:400]}\n  {str(got)[:400]}"
            )


# ---------------------------------------------------------------------------
# Job registry: the four real jobs plus the synthetic pipeline.
# ---------------------------------------------------------------------------

_KGS = 12  # small key-group counts keep the suite fast but multi-run


def _wiki_feeders():
    return {"wiki": wiki_edit_stream(StreamSpec(rate=90.0, seed=5))}


def _airline_feeders():
    return {"airline": airline_stream(StreamSpec(rate=90.0, seed=5))}


def _job4_feeders():
    return {
        "airline": airline_stream(StreamSpec(rate=90.0, seed=5)),
        "weather": weather_stream(StreamSpec(rate=40.0, seed=5)),
    }


def _bid_feeders():
    return {"bid": bid_stream(StreamSpec(rate=90.0, seed=5))}


def _int_batches(rate=120, key_space=10_000, seed=5):
    rng = np.random.default_rng(seed)
    tick = 0
    while True:
        n = int(rng.poisson(rate))
        keys = rng.integers(0, key_space, size=n).astype(np.int64)
        yield keys, rng.random(n), np.full(n, float(tick))
        tick += 1


def _pipe_mid_jit(state, kgs, starts, ends, keys, values, ts):
    from repro.engine import jitexec as jx

    return (
        {"n": jx.count_runs(state["n"], kgs, starts, ends)},
        (keys + 17, values, ts),
        None,
    )


def _pipe_sink_jit(state, kgs, starts, ends, keys, values, ts):
    from repro.engine import jitexec as jx

    return (
        {"n": jx.count_runs(state["n"], kgs, starts, ends)},
        (keys * 2, values, ts),
        None,
    )


_PIPE_STATE = StateSchema((StateField("n", "scalar", dtype=np.int64, py=int),))


def make_pipeline_topo(kgs: int = 16) -> Topology:
    """The synthetic source → re-key → recording-sink pipeline, with all
    three operator protocols (shared with the migration property tests).
    Every edge declares the scalar float64 payload schema, so the same
    topology runs typed (native key/value dtypes end to end, raw-buffer
    migration blobs), untyped via ``Engine(use_schema=...)``, or compiled
    via ``Engine(use_fn_jit=True)`` (per-key-group counters in jit-tier
    scalar state columns)."""

    scalar = Schema(np.dtype(np.float64))

    def mid_fn(state, keys, values, ts):
        state["n"] = state.get("n", 0) + len(keys)
        return state, (keys + 17, values, ts)

    def mid_seg(store, run_kgs, starts, ends, keys, values, ts):
        for kg, a, z in zip(run_kgs, starts, ends):
            st = store[kg]
            st["n"] = st.get("n", 0) + (z - a)
        return (keys + 17, values, ts), None

    def sink_fn(state, keys, values, ts):
        state["n"] = state.get("n", 0) + len(keys)
        return state, (keys * 2, values, ts)

    def sink_seg(store, run_kgs, starts, ends, keys, values, ts):
        for kg, a, z in zip(run_kgs, starts, ends):
            st = store[kg]
            st["n"] = st.get("n", 0) + (z - a)
        return (keys * 2, values, ts), None

    t = Topology()
    t.add_operator(
        OperatorSpec("src", None, num_keygroups=kgs, is_source=True, schema=scalar)
    )
    t.add_operator(
        OperatorSpec(
            "mid",
            mid_fn,
            num_keygroups=kgs,
            fn_seg=mid_seg,
            fn_jit=_pipe_mid_jit,
            jit_fusible=True,
            state_schema=_PIPE_STATE,
            schema=scalar,
            out_schema=scalar,
        )
    )
    t.add_operator(
        OperatorSpec(
            "sink",
            sink_fn,
            num_keygroups=kgs,
            is_sink=True,
            fn_seg=sink_seg,
            fn_jit=_pipe_sink_jit,
            jit_fusible=True,
            state_schema=_PIPE_STATE,
            schema=scalar,
            out_schema=scalar,
        )
    )
    t.connect("src", "mid")
    t.connect("mid", "sink")
    return t


def _pipeline_feeders():
    return {"src": _int_batches()}


JOBS = {
    "job1": (
        lambda: make_real_job_1(keygroups_per_op=_KGS, topk=3, window_ticks=4.0),
        _wiki_feeders,
    ),
    "job2": (lambda: real_job_2(keygroups_per_op=_KGS), _airline_feeders),
    "job3": (lambda: real_job_3(keygroups_per_op=_KGS), _airline_feeders),
    "job4": (lambda: real_job_4(keygroups_per_op=_KGS), _job4_feeders),
    "pipeline": (lambda: make_pipeline_topo(_KGS), _pipeline_feeders),
    "q17": (
        lambda: nexmark_q17(keygroups_per_op=_KGS, expected_rows=0),
        _bid_feeders,
    ),
}


# ---------------------------------------------------------------------------
# Fuzzing mode: randomized topologies over a library of generic operators.
#
# A *fuzz spec* is a plain dict (hypothesis draws it in
# tests/test_conformance_fuzz.py) describing a random fan-out DAG:
#
#   {"family": "scalar" | "record",       # value payload family
#    "key_dtype": "i8" | "i4",            # declared key dtype
#    "source_schema": bool,               # source edge declared?
#    "ops": [{"kind": ..., "kgs": int,    # per middle operator
#             "schema": bool,             # input edge declared?
#             "out_schema": bool,         # output edge declared?
#             "key": "id" | "mod" | "byval"},
#            ...],
#    "edges": [[upstream indices], ...]}  # -1 = source, else earlier op
#
# Every operator implements fn + fn_seg, and each fn_seg handles both value
# representations, so any schema/no-schema mix along any DAG must stay
# bit-identical across the full CONFIGS matrix.  All kinds except the
# keyed-table ``accum`` also carry an fn_jit port (attached whenever the
# declared schemas allow the jit tier to run them — see
# :func:`_fuzz_jit_bodies`), so the same DAGs exercise the compiled tier
# and, on eligible linear chains, the fused superstep.
# ---------------------------------------------------------------------------

FUZZ_RECORD_DTYPE = np.dtype([("a", "i8"), ("b", "f8")])
FUZZ_KINDS = {
    "scalar": ("rekey", "vshift", "filter", "window", "accum"),
    "record": ("rekey", "project", "filter", "window", "accum"),
}

# Sliding-count window length of the "window" fuzz operator.
_FUZZ_WINDOW = 5


def _count_runs(store, run_kgs, starts, ends):
    for kg, a, z in zip(run_kgs, starts, ends):
        st = store[kg]
        st["n"] = st.get("n", 0) + (z - a)


def _fuzz_stateful_bodies(kind: str, family: str):
    """Windowed / keyed-accumulator generic operators — the ROADMAP's
    "extend the fuzz pool toward windowed/stateful operators".

    ``window`` keeps a sliding count window (last :data:`_FUZZ_WINDOW`
    payloads) per key group and emits each tuple with its window sum;
    ``accum`` keeps a keyed accumulator (payloads summed by ``key % 7``)
    and emits the running totals.  Both walk tuples in order inside
    ``fn_seg`` — what these operators fuzz is *stateful* equivalence
    across representations, schema mixes and migrations, not
    vectorization — and the python ``sum``/left-fold keeps every float
    trajectory bit-identical to the per-run oracle.
    """
    rec = family == "record"

    def _payload(v):
        return v[1] if rec else v

    def _emit(v, s):
        return (v[0], s) if rec else s

    if kind == "window":

        def run(state, out, keys, values, ts):
            buf = state.setdefault("buf", [])
            vals = values.tolist() if isinstance(values, np.ndarray) else values
            for k, v, t in zip(keys.tolist(), vals, np.asarray(ts).tolist()):
                buf.append(_payload(v))
                if len(buf) > _FUZZ_WINDOW:
                    del buf[0]
                out.append((k, _emit(v, sum(buf)), t))

    else:  # accum

        def run(state, out, keys, values, ts):
            acc = state.setdefault("acc", {})
            vals = values.tolist() if isinstance(values, np.ndarray) else values
            for k, v, t in zip(keys.tolist(), vals, np.asarray(ts).tolist()):
                kk = k % 7
                s = acc.get(kk, 0.0) + _payload(v)
                acc[kk] = s
                out.append((k, _emit(v, s), t))

    def fn(state, keys, values, ts):
        out = []
        run(state, out, keys, values, ts)
        return state, out

    def seg(store, run_kgs, starts, ends, keys, values, ts):
        out = []
        lens = []
        for kg, a, z in zip(run_kgs, starts, ends):
            before = len(out)
            run(store[kg], out, keys[a:z], values[a:z], ts[a:z])
            lens.append(len(out) - before)
        if not out:
            return None, None
        ok, ov, ot = zip(*out)
        if rec:
            ov_arr = np.empty(len(ov), dtype=object)
            ov_arr[:] = list(ov)
        else:
            ov_arr = np.asarray(ov)
        return (np.asarray(ok), ov_arr, np.asarray(ot)), lens

    return fn, seg


def _fuzz_bodies(kind: str, family: str):
    """(fn, fn_seg) for one generic operator, bit-identical across
    representations (structured column views vs object tuples)."""
    if kind in ("window", "accum"):
        return _fuzz_stateful_bodies(kind, family)
    if family == "scalar":
        if kind == "rekey":

            def fn(state, keys, values, ts):
                state["n"] = state.get("n", 0) + len(keys)
                return state, (keys + 7, values, ts)

            def seg(store, run_kgs, starts, ends, keys, values, ts):
                _count_runs(store, run_kgs, starts, ends)
                return (keys + 7, values, ts), None

        elif kind == "vshift":

            def fn(state, keys, values, ts):
                state["n"] = state.get("n", 0) + len(keys)
                return state, (keys, values + 0.5, ts)

            def seg(store, run_kgs, starts, ends, keys, values, ts):
                _count_runs(store, run_kgs, starts, ends)
                return (keys, values + 0.5, ts), None

        else:  # filter

            def fn(state, keys, values, ts):
                state["n"] = state.get("n", 0) + len(keys)
                keep = keys % 3 != 0
                return state, (keys[keep], values[keep], ts[keep])

            def seg(store, run_kgs, starts, ends, keys, values, ts):
                _count_runs(store, run_kgs, starts, ends)
                keep = keys % 3 != 0
                lens = [int(keep[a:z].sum()) for a, z in zip(starts, ends)]
                return (keys[keep], values[keep], ts[keep]), lens

        return fn, seg

    # record family: values are (a: i8, b: f8) records
    def _project_cols(values):
        """(a column, b column) as native arrays, either representation."""
        if values.dtype.names is not None:
            return values["a"], values["b"]
        a_l, b_l = zip(*values.tolist())
        return np.asarray(a_l, dtype=np.int64), np.asarray(b_l)

    def _record_out(values, a, b):
        if values.dtype.names is not None:
            out = np.empty(len(a), dtype=FUZZ_RECORD_DTYPE)
            out["a"] = a
            out["b"] = b
            return out
        out = np.empty(len(a), dtype=object)
        out[:] = list(zip(a.tolist(), b.tolist()))
        return out

    if kind == "rekey":

        def fn(state, keys, values, ts):
            state["n"] = state.get("n", 0) + len(keys)
            return state, (keys + 7, values, ts)

        def seg(store, run_kgs, starts, ends, keys, values, ts):
            _count_runs(store, run_kgs, starts, ends)
            return (keys + 7, values, ts), None

    elif kind == "project":

        def fn(state, keys, values, ts):
            state["n"] = state.get("n", 0) + len(keys)
            out = [
                (k, (v[0], v[1] + v[0]), t)
                for k, v, t in zip(keys.tolist(), values.tolist(), ts.tolist())
            ]
            return state, out

        def seg(store, run_kgs, starts, ends, keys, values, ts):
            _count_runs(store, run_kgs, starts, ends)
            a, b = _project_cols(values)
            return (keys, _record_out(values, a, b + a), ts), None

    else:  # filter on the record's a field

        def fn(state, keys, values, ts):
            state["n"] = state.get("n", 0) + len(keys)
            a, _ = _project_cols(values)
            keep = a % 3 != 0
            return state, (keys[keep], values[keep], ts[keep])

        def seg(store, run_kgs, starts, ends, keys, values, ts):
            _count_runs(store, run_kgs, starts, ends)
            a, _ = _project_cols(values)
            keep = a % 3 != 0
            lens = [int(keep[a_:z].sum()) for a_, z in zip(starts, ends)]
            return (keys[keep], values[keep], ts[keep]), lens

    return fn, seg


_FUZZ_JIT_STATE = StateSchema(
    (StateField("n", "scalar", dtype=np.int64, py=int),)
)
_FUZZ_WINDOW_STATE = StateSchema(
    (
        StateField(
            "buf", "vector", dtype=np.float64, py=float, length=_FUZZ_WINDOW
        ),
    )
)


def _fuzz_jit_bodies(kind: str, family: str):
    """(fn_jit, state_schema) port of one generic fuzz operator.

    ``accum`` (keyed-table state) stays on the numpy tiers → ``(None,
    None)``.  The ports follow the fn_jit contract end to end: run bounds
    may be padded (``kgs`` with the key-group count, ``starts``/``ends``
    with the tuple count), scatters use ``mode="drop"``, and the 1:1 ops'
    state updates are run-order-insensitive scatter-adds.  ``filter``
    compacts with a stable partition (kept tuples keep the oracle's global
    order) and returns per-run ``out_counts``; ``window`` mirrors the
    oracle's left-fold window sum over a :class:`repro.engine.jitexec.
    VectorState` ring, so its floats stay bit-identical, not merely within
    the jit tolerance.
    """
    rec = family == "record"
    if kind == "accum":
        return None, None

    if kind == "rekey":

        def fn_jit(state, kgs, starts, ends, keys, values, ts):
            from repro.engine import jitexec as jx

            return (
                {"n": jx.count_runs(state["n"], kgs, starts, ends)},
                (keys + 7, values, ts),
                None,
            )

        return fn_jit, _FUZZ_JIT_STATE

    if kind == "vshift":

        def fn_jit(state, kgs, starts, ends, keys, values, ts):
            from repro.engine import jitexec as jx

            return (
                {"n": jx.count_runs(state["n"], kgs, starts, ends)},
                (keys, values + 0.5, ts),
                None,
            )

        return fn_jit, _FUZZ_JIT_STATE

    if kind == "project":

        def fn_jit(state, kgs, starts, ends, keys, values, ts):
            from repro.engine import jitexec as jx

            return (
                {"n": jx.count_runs(state["n"], kgs, starts, ends)},
                (keys, {"a": values["a"], "b": values["b"] + values["a"]}, ts),
                None,
            )

        return fn_jit, _FUZZ_JIT_STATE

    if kind == "filter":

        def fn_jit(state, kgs, starts, ends, keys, values, ts):
            import jax.numpy as jnp

            from repro.engine import jitexec as jx

            n = keys.shape[0]
            new = {"n": jx.count_runs(state["n"], kgs, starts, ends)}
            keep = (values["a"] % 3 != 0) if rec else (keys % 3 != 0)
            keepv = jx.tuple_valid(starts, ends, n) & keep
            # Stable partition: kept tuples first, in run-major order — the
            # compacted layout the engine splits back by out_counts.
            order = jnp.argsort(jnp.where(keepv, 0, 1), stable=True)
            if rec:
                ov = {nm: col[order] for nm, col in values.items()}
            else:
                ov = values[order]
            oc = (
                jnp.zeros(kgs.shape[0], jnp.int64)
                .at[jx.run_of_tuples(ends, n)]
                .add(keepv.astype(jnp.int64))
            )
            return new, (keys[order], ov, ts[order]), oc

        return fn_jit, _FUZZ_JIT_STATE

    # window: sliding count window over a fixed-length VectorState ring.
    def fn_jit(state, kgs, starts, ends, keys, values, ts):
        import jax.numpy as jnp

        from repro.engine import jitexec as jx

        W = _FUZZ_WINDOW
        data, cnt = state["buf"].data, state["buf"].cnt
        nkg = data.shape[0]
        n = keys.shape[0]
        payload = values["b"] if rec else values
        # Per-tuple window sum: tuple at position p (its run's m-th payload,
        # ring count c before the run) sums the last min(W, c+m) of
        # ring ++ payload[start..p], oldest first — the oracle's left fold.
        ridx = jx.run_of_tuples(ends, n)
        kg_t = jnp.clip(kgs[ridx], 0, nkg - 1)
        c_t = cnt[kg_t].astype(jnp.int64)
        pos = jnp.arange(n)
        m = pos - starts[ridx] + 1
        s = jnp.zeros(n, jnp.float64)
        for d in range(W - 1, -1, -1):  # back-offset from the newest element
            pay = payload[jnp.clip(pos - d, 0, n - 1)]
            ring = data[kg_t, jnp.clip(c_t + m - 1 - d, 0, W - 1)]
            s = jnp.where(d < c_t + m, s + jnp.where(d < m, pay, ring), s)
        # New ring per run: the last min(W, c+L) elements of ring ++ payload,
        # re-packed oldest-first into slots [0, new_cnt).
        L = ends - starts
        kg_r = jnp.clip(kgs, 0, nkg - 1)
        c_r = cnt[kg_r].astype(jnp.int64)
        new_cnt = jnp.minimum(c_r + L, W)
        j = jnp.arange(W)[None, :]
        s_idx = (c_r + L - new_cnt)[:, None] + j
        from_pay = s_idx >= c_r[:, None]
        pay_idx = starts[:, None] + (s_idx - c_r[:, None])
        row = jnp.where(
            j < new_cnt[:, None],
            jnp.where(
                from_pay,
                payload[jnp.clip(pay_idx, 0, n - 1)],
                data[kg_r[:, None], jnp.clip(s_idx, 0, W - 1)],
            ),
            0.0,
        )
        new_vst = jx.VectorState(
            data.at[kgs].set(row, mode="drop"),
            cnt.at[kgs].set(new_cnt.astype(cnt.dtype), mode="drop"),
        )
        out_v = {"a": values["a"], "b": s} if rec else s
        return {"buf": new_vst}, (keys, out_v, ts), None

    return fn_jit, _FUZZ_WINDOW_STATE


def make_fuzz_topology(spec: dict) -> Topology:
    """Build the randomized DAG a fuzz spec describes (deterministic)."""
    family = spec["family"]
    key_dtype = np.dtype(spec["key_dtype"])
    value_dtype = (
        FUZZ_RECORD_DTYPE if family == "record" else np.dtype(np.float64)
    )
    schema = Schema(value_dtype, key=key_dtype)
    t = Topology()
    t.add_operator(
        OperatorSpec(
            "src",
            None,
            num_keygroups=spec.get("source_kgs", 8),
            is_source=True,
            schema=schema if spec["source_schema"] else None,
        )
    )
    for i, op in enumerate(spec["ops"]):
        fn, seg = _fuzz_bodies(op["kind"], family)
        kw = {}
        if op["key"] == "mod":
            kw["key_fn"] = lambda k: k % 13
        elif op["key"] == "byval" and family == "record":
            kw["key_by_value"] = lambda v: v[0] % 11
            kw["key_by_value_col"] = lambda v: v["a"] % np.int64(11)
        fj, st = _fuzz_jit_bodies(op["kind"], family)
        # The jit tier needs native input columns (declared input schema)
        # and, for record-family dict outputs, a declared out_schema to
        # assemble the structured output array.
        if fj is not None and op["schema"] and (
            family == "scalar" or op["out_schema"]
        ):
            kw["fn_jit"] = fj
            kw["state_schema"] = st
            # Fusible = strictly 1:1 with run-order-insensitive scalar
            # state and an unmapped partition key (superstep contract).
            kw["jit_fusible"] = (
                op["kind"] in ("rekey", "vshift", "project")
                and op["key"] == "id"
            )
        t.add_operator(
            OperatorSpec(
                f"op{i}",
                fn,
                num_keygroups=op["kgs"],
                fn_seg=seg,
                schema=schema if op["schema"] else None,
                out_schema=schema if op["out_schema"] else None,
                **kw,
            )
        )
    for i, ups in enumerate(spec["edges"]):
        for u in ups:
            t.connect("src" if u < 0 else f"op{u}", f"op{i}")
    return t


def fuzz_feeders(spec: dict, *, rate: float = 90.0, seed: int = 5):
    """Deterministic source feeders matching a fuzz spec's value family."""
    family = spec["family"]

    def factory():
        def gen():
            rng = np.random.default_rng(seed)
            tick = 0
            while True:
                n = int(rng.poisson(rate))
                keys = rng.integers(0, 100_000, size=n).astype(np.int64)
                if family == "record":
                    a = rng.integers(0, 1_000, size=n)
                    b = rng.random(n)
                    values = list(zip(a.tolist(), b.tolist()))
                else:
                    values = rng.random(n)
                yield keys, values, np.full(n, float(tick))
                tick += 1

        return {"src": gen()}

    return factory

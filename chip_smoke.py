"""Bring-up smoke test: the streaming engine's device tiers on a TPU.

Drives the paper's jobs once through the normal entry points
(``Controller(Engine(...), AdaptationFramework(mode="albic"))``) on the
first TPU chip, in this one process:

1. **device** — platform, device kind and count; anything but a TPU fails
   at once, before any result is printed.
2. **kernels** — ``keygroup_partition`` and ``bucket_argsort`` compiled
   (the program must hold a Mosaic kernel) at the sizes the job phases
   use, bit for bit against their ``ref.py``; plus the int64 radix argsort
   the keyed running sums sort with.
3. **job2_jit**, **job3_jit** — Real Jobs 2 and 3 at 20 nodes × 400 key
   groups per operator on ``ExecutionConfig.jit()``: 4 SPL periods × 8
   ticks of 65,536 airline events (2.1 M events), ALBIC migrating key
   groups between periods.
4. **superstep** — the fused record pipeline (depth 3, 400 key groups per
   operator, 20 nodes) on ``ExecutionConfig.superstep()`` under the same
   controller, then one 8-tick ``Engine.run_supersteps`` scan.

Every engine phase is replayed on the numpy ``.typed()`` engine with the
same batches and the migration plans the device run chose.  Sink outputs
(values and order), per-key-group state, tuple metrics and the final
allocation must match exactly, except float values, which may differ by
``rtol = atol = 1e-9`` (the jit tier's documented float policy).

``--chips 4`` runs only Job 3 on ``ExecutionConfig.jit(mesh=<4-device
"nodes" mesh>)`` against the same job on one chip, and checks that every
device holds part of the keyed state.

Earlier lines are bring-up facts (compile seconds, tuples, migrations,
wall time); the last line is ``{"ok": true, "device": {...}}``, printed
only when every phase passed.

    python chip_smoke.py [--seed N] [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from repro.compile_cache import enable_compile_cache  # noqa: E402

NODES = 20
KGS = 400  # key groups per operator (solver_perf/fig2_20n_400kg)
EVENTS = 65_536  # events per tick
PERIODS = 4
TICKS = 8  # ticks per SPL period
SCAN_TICKS = 8
RTOL = ATOL = 1e-9
METRICS = (
    "processed_tuples",
    "emitted_tuples",
    "sink_tuples",
    "cross_node_tuples",
    "intra_node_tuples",
    "dropped_credits",
)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _CompileClock:
    """Sums XLA backend compile seconds (JAX's own monitoring events)."""

    total = 0.0

    def __call__(self, event, duration, **kw):
        if event == _COMPILE_EVENT:
            _CompileClock.total += duration


# ---------------------------------------------------------------------------
# data, controller plumbing, comparison
# ---------------------------------------------------------------------------


def airline_batches(seed: int, ticks: int, events: int = EVENTS) -> list:
    """``ticks`` airline batches of exactly ``events`` events each."""
    from repro.data import airline_stream
    from repro.data.synthetic import StreamSpec

    # Poisson batch sizes: eight standard deviations above ``events``.
    stream = airline_stream(
        StreamSpec(rate=events + 8 * math.sqrt(events), fluctuation=0.0,
                   seed=seed)
    )
    out = []
    for _ in range(ticks):
        keys, values, ts = next(stream)
        if len(keys) < events:
            raise RuntimeError(f"airline batch of {len(keys)} < {events}")
        out.append((keys[:events], values[:events], ts[:events]))
    return out


def record_batches(seed: int, ticks: int, events: int = EVENTS) -> list:
    """Batches for the record pipeline: int keys, ``(a: i8, b: f8)`` values."""
    from benchmarks.engine_throughput import _REC_SCHEMA

    rng = np.random.default_rng([seed, 7])
    out = []
    for t in range(ticks):
        values = np.empty(events, dtype=_REC_SCHEMA.value)
        values["a"] = rng.integers(0, 1_000, size=events)
        values["b"] = rng.random(events)
        keys = rng.integers(0, 1_000_000, size=events).astype(np.int64)
        out.append((keys, values, np.full(events, float(t))))
    return out


class _Recorder:
    """The device run's adaptation framework, recording every result."""

    splitter = None

    def __init__(self, framework):
        self.framework = framework
        self.results = []

    def adapt(self, state, **kw):
        result = self.framework.adapt(state, **kw)
        self.results.append(result)
        return result


class _Replay:
    """Hands the reference engine the device run's recorded results."""

    splitter = None

    def __init__(self, results):
        self._results = iter(results)

    def adapt(self, state, **kw):
        return next(self._results)


def albic():
    from repro.core import AdaptationFramework, AlbicParams

    return AdaptationFramework(
        mode="albic",
        max_migrations=20,
        albic_params=AlbicParams(max_ld=10.0, time_limit=2.0),
    )


def make_engine(topology, config, seed: int, nodes: int, events: int = EVENTS):
    """An engine whose key groups all start on the first half of the nodes
    (a cluster that just doubled), so ALBIC has balancing to do, and whose
    source admits every ``events``-event batch whole (the default credit
    of 10,000 tuples per tick would drop most of it)."""
    from repro.engine import Engine

    rng = np.random.default_rng([seed, 11])
    alloc = rng.integers(0, max(nodes // 2, 1), size=topology.num_keygroups)
    engine = Engine(
        topology, nodes, config=config, initial_alloc=alloc,
        service_rate=1e9, seed=seed,
    )
    # Twice the batch: credits shrink with queued work, and a tick must
    # never drop part of a batch.
    engine.backpressure.full_credit = 2 * events
    return engine


def drain(engine) -> None:
    while any(bool(q) for q in engine._queues):
        engine.tick()


def drive(engine, framework, source: str, batches, periods: int, ticks: int):
    """``periods`` controller periods of ``ticks`` ticks, then drain.

    Returns the per-period metrics rows."""
    from repro.engine import Controller, ControllerConfig

    feed = iter(batches)
    ctrl = Controller(
        engine,
        framework,
        ControllerConfig(ticks_per_period=ticks),
        feeder=lambda eng, tick: eng.push_source(source, *next(feed)),
    )
    rows = [ctrl.period() for _ in range(periods)]
    drain(engine)
    return rows


def _close(a, b) -> bool:
    if type(a) is not type(b):
        return False
    if isinstance(a, float):
        return a == b or math.isclose(a, b, rel_tol=RTOL, abs_tol=ATOL)
    if isinstance(a, dict):
        return _close(list(a.items()), list(b.items()))
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(_close(x, y) for x, y in zip(a, b))
    return a == b


def compare(dev, ref) -> dict:
    """Device engine vs reference under the float policy; raises on any
    difference, returns counts of what was compared."""
    dev.end_period()  # materializes device state columns into the store
    ref.end_period()
    for m in METRICS:
        a, b = getattr(dev.metrics, m), getattr(ref.metrics, m)
        if a != b:
            raise AssertionError(f"metric {m}: device {a} != reference {b}")
    if not np.array_equal(dev.router.table, ref.router.table):
        raise AssertionError("final allocations differ")
    sa, sb = dev.metrics.sink_outputs, ref.metrics.sink_outputs
    if len(sa) != len(sb):
        raise AssertionError(f"sink outputs: {len(sa)} != {len(sb)}")
    inexact = 0
    for i, (x, y) in enumerate(zip(sa, sb)):
        if x != y:
            if not _close(x, y):
                raise AssertionError(f"sink output {i}: {x!r} != {y!r}")
            inexact += 1
    states = 0
    for (kg, x), (_, y) in zip(dev.store.items(), ref.store.items()):
        if x != y and not _close(x, y):
            raise AssertionError(f"state of key group {kg}: {x!r} != {y!r}")
        states += bool(y)
    return {
        "sink_outputs": len(sa),
        "sink_within_tolerance": inexact,
        "keygroup_states": states,
    }


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_kernels(seed: int, events: int = EVENTS, nodes: int = NODES,
                  kgs: int = KGS) -> dict:
    import jax
    import jax.numpy as jnp

    # The jit tier turns on 64-bit types at import; the kernels run under
    # the same configuration the engine phases do (and the int64 radix
    # sort's codes would otherwise truncate to int32).
    import repro.engine.jitexec  # noqa: F401
    from repro.kernels.keygroup_partition import fold_keys64, keygroup_partition
    from repro.kernels.keygroup_partition.keygroup_partition import (
        keygroup_partition_pallas,
    )
    from repro.kernels.keygroup_partition.ref import keygroup_partition_ref
    from repro.kernels.radix_sort import bucket_argsort, radix_argsort_jax
    from repro.kernels.radix_sort.radix_sort import bucket_argsort_pallas
    from repro.kernels.radix_sort.ref import bucket_argsort_ref

    rng = np.random.default_rng([seed, 3])
    info = {}
    keys = rng.integers(-(2**62), 2**62, size=events)
    t0 = time.perf_counter()
    kg, hist = keygroup_partition(keys, kgs)
    info["partition_first_s"] = time.perf_counter() - t0
    rkg, rhist = keygroup_partition_ref(jnp.asarray(fold_keys64(keys)), kgs)
    if not (
        np.array_equal(kg, np.asarray(rkg)) and np.array_equal(hist, np.asarray(rhist))
    ):
        raise AssertionError("keygroup_partition differs from ref.py")
    x32 = jax.ShapeDtypeStruct((events,), jnp.int32)
    hlo = keygroup_partition_pallas.lower(x32, x32, num_keygroups=kgs)
    if "tpu_custom_call" not in hlo.compile().as_text():
        raise AssertionError("keygroup_partition program holds no TPU kernel")
    # The routing sorts: node × key-group composites (host routing) and
    # the fused superstep's device sort with its sentinel bucket.
    for nb in (nodes * kgs, nodes * kgs + 1):
        codes = rng.integers(0, nb, size=events)
        t0 = time.perf_counter()
        order = bucket_argsort(codes, nb)
        info[f"argsort_{nb}_first_s"] = time.perf_counter() - t0
        if not np.array_equal(order, bucket_argsort_ref(codes)):
            raise AssertionError(f"bucket_argsort({nb} buckets) differs")
        hlo = bucket_argsort_pallas.lower(x32, num_buckets=nb)
        if "tpu_custom_call" not in hlo.compile().as_text():
            raise AssertionError("bucket_argsort program holds no TPU kernel")
    codes64 = rng.integers(0, 2**62, size=events)
    codes64[::7] = np.iinfo(np.int64).max  # the running sums' padding code
    t0 = time.perf_counter()
    order = np.asarray(jax.jit(radix_argsort_jax)(jnp.asarray(codes64)))
    info["radix64_first_s"] = time.perf_counter() - t0
    if not np.array_equal(order, bucket_argsort_ref(codes64)):
        raise AssertionError("radix_argsort_jax differs from numpy")
    return info


def phase_job(job: int, seed: int, *, nodes: int = NODES, kgs: int = KGS,
              events: int = EVENTS, periods: int = PERIODS,
              ticks: int = TICKS) -> dict:
    from repro.data import real_job_2, real_job_3
    from repro.engine import ExecutionConfig

    factory = {2: real_job_2, 3: real_job_3}[job]
    batches = airline_batches(seed, periods * ticks, events)
    dev = make_engine(factory(keygroups_per_op=kgs), ExecutionConfig.jit(),
                      seed, nodes, events)
    rec = _Recorder(albic())
    t0 = time.perf_counter()
    rows = drive(dev, rec, "airline", batches, periods, ticks)
    wall = time.perf_counter() - t0
    ref = make_engine(factory(keygroups_per_op=kgs), ExecutionConfig.typed(),
                      seed, nodes, events)
    drive(ref, _Replay(rec.results), "airline", batches, periods, ticks)
    info = _engine_info(dev, rows, wall)
    info.update(compare(dev, ref))
    return info


def phase_superstep(seed: int, *, nodes: int = NODES, kgs: int = KGS,
                    events: int = EVENTS, periods: int = PERIODS,
                    ticks: int = TICKS, scan_ticks: int = SCAN_TICKS) -> dict:
    from benchmarks.engine_throughput import make_record_pipeline_job
    from repro.engine import ExecutionConfig

    batches = record_batches(seed, periods * ticks + scan_ticks, events)
    ctrl_batches, scan = batches[: periods * ticks], batches[periods * ticks:]

    def topology():
        return make_record_pipeline_job(num_keygroups=kgs, depth=3)

    dev = make_engine(topology(), ExecutionConfig.superstep(), seed, nodes,
                      events)
    rec = _Recorder(albic())
    t0 = time.perf_counter()
    rows = drive(dev, rec, "src", ctrl_batches, periods, ticks)
    fused_ctrl = dev.metrics.fused_ticks
    if fused_ctrl == 0:
        raise AssertionError("no tick fused under the controller")
    syncs0 = dev.metrics.jit_host_syncs
    if dev.run_supersteps(scan) != scan_ticks:
        raise AssertionError("run_supersteps did not run every tick")
    if dev.metrics.jit_host_syncs - syncs0 != 1:
        raise AssertionError("the K-tick scan crossed the host more than once")
    drain(dev)
    wall = time.perf_counter() - t0
    ref = make_engine(topology(), ExecutionConfig.typed(), seed, nodes, events)
    drive(ref, _Replay(rec.results), "src", ctrl_batches, periods, ticks)
    for keys, values, ts in scan:
        ref.push_source("src", keys, values, ts)
        ref.tick()
    drain(ref)
    info = _engine_info(dev, rows, wall)
    info["fused_ticks_controller"] = fused_ctrl
    info["scan_ticks"] = scan_ticks
    info.update(compare(dev, ref))
    return info


def phase_mesh(seed: int, *, nodes: int = NODES, kgs: int = KGS,
               events: int = EVENTS, periods: int = PERIODS,
               ticks: int = TICKS) -> dict:
    """Job 3 run-sharded over a 4-device "nodes" mesh vs one chip."""
    import jax
    from jax.sharding import AxisType

    from repro.data import real_job_3
    from repro.engine import ExecutionConfig

    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = jax.make_mesh((4,), ("nodes",), devices=devices[:4],
                         axis_types=(AxisType.Auto,))
    batches = airline_batches(seed, periods * ticks, events)
    dev = make_engine(
        real_job_3(keygroups_per_op=kgs),
        ExecutionConfig.jit(mesh=mesh, mesh_axis="nodes"), seed, nodes, events,
    )
    rec = _Recorder(albic())
    t0 = time.perf_counter()
    rows = drive(dev, rec, "airline", batches, periods, ticks)
    wall = time.perf_counter() - t0
    one = make_engine(real_job_3(keygroups_per_op=kgs), ExecutionConfig.jit(),
                      seed, nodes, events)
    drive(one, _Replay(rec.results), "airline", batches, periods, ticks)
    info = _engine_info(dev, rows, wall)
    info.update(compare(dev, one))
    # Each device must hold part of every keyed table: the key-group-sharded
    # slabs carry one shard per device, each with entries of its own.
    spread = {}
    for ost in dev._jit._by_op.values():
        for f in ost.fields:
            cnt = ost.cols[f.name].cnt
            per_dev = {s.device.id: int(np.asarray(s.data).sum())
                       for s in cnt.addressable_shards}
            if len(per_dev) != 4 or min(per_dev.values()) == 0:
                raise AssertionError(f"{f.name} not spread: {per_dev}")
            spread[f.name] = per_dev
    info["entries_per_device"] = spread
    return info


def _engine_info(engine, rows, wall: float) -> dict:
    m = engine.metrics
    info = {
        "wall_s": wall,
        "tuples_processed": m.processed_tuples,
        "migrations": sum(r.num_migrations for r in rows),
        "migrations_per_period": [r.num_migrations for r in rows],
        "jit_compiles": m.jit_compiles,
        "jit_host_syncs": m.jit_host_syncs,
        "fused_ticks": m.fused_ticks,
        "kernel_stats": engine.kernel_stats,
    }
    if info["migrations"] == 0:
        raise AssertionError("ALBIC migrated no key group")
    if m.dropped_credits:
        raise AssertionError(f"the source dropped {m.dropped_credits} events")
    return info


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    import jax
    import jax.monitoring

    devices = jax.devices()
    dev0 = devices[0]
    print(f"[device] platform={dev0.platform} kind={dev0.device_kind} "
          f"count={len(devices)} compile_cache={cache} "
          f"entries_at_start={cached}", flush=True)
    if dev0.platform != "tpu":
        print("[device] no TPU: refusing to run on another backend",
              file=sys.stderr)
        return 2
    jax.monitoring.register_event_duration_secs_listener(_CompileClock())

    if args.chips == 4:
        phases = [("mesh4_job3_jit", lambda: phase_mesh(args.seed))]
    else:
        phases = [
            ("kernels", lambda: phase_kernels(args.seed)),
            ("job2_jit", lambda: phase_job(2, args.seed)),
            ("job3_jit", lambda: phase_job(3, args.seed)),
            ("superstep", lambda: phase_superstep(args.seed)),
        ]
    failed = []
    for name, fn in phases:
        c0, t0 = _CompileClock.total, time.perf_counter()
        try:
            info = fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAILED", flush=True)
            continue
        info["compile_s"] = _CompileClock.total - c0
        info["phase_s"] = time.perf_counter() - t0
        print(f"[{name}] " + json.dumps(info, default=str), flush=True)
    if failed:
        print(f"[smoke] failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev0.platform,
            "kind": dev0.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

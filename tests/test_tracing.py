"""The engine's own spans and device counters (engine/tracing.py).

Spans are profiler annotations once the jit tier is up and one shared no-op
on the numpy tiers, which import no jax; a whole engine life under the
profiler records every span name; ``device_fetches`` matches a hand count
of the reads one SumDelay call and one routed batch make; the table gauges
follow the host's own counts.
"""

import glob
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.data.jobs import EXTRACT_SCHEMA, real_job_2, real_job_3
from repro.engine import Engine, ExecutionConfig
from repro.engine.executor import EngineMetrics
from repro.engine.state import KeyedStore
from repro.engine.tracing import SPANS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NUMPY_TIER = textwrap.dedent(
    """
    import gc
    import sys
    import numpy as np
    from repro.data.jobs import real_job_2
    from repro.engine import Engine, ExecutionConfig
    from repro.engine import tracing

    eng = Engine(real_job_2(keygroups_per_op=4), 2, service_rate=1e9, seed=0,
                 config=ExecutionConfig.typed())
    assert tracing.span("route") is tracing.span("tick")  # one shared no-op
    assert tracing._gc_span not in gc.callbacks
    assert eng.metrics.device_fetches == 0
    assert "jax" not in sys.modules
    print("NUMPY-TIER-OK")
    """
)


def test_spans_are_a_shared_no_op_on_the_numpy_tiers():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-c", NUMPY_TIER],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "NUMPY-TIER-OK" in proc.stdout


def _airline(n, seed):
    from repro.data.synthetic import AIRLINE_DTYPE

    rng = np.random.default_rng(seed)
    values = np.zeros(n, dtype=AIRLINE_DTYPE)
    values["plane"] = rng.integers(0, 50, n)
    values["origin"] = rng.integers(0, 300, n)
    values["dest"] = (values["origin"] + 1 + rng.integers(0, 299, n)) % 300
    values["dep_delay"] = rng.normal(8.0, 20.0, n)
    values["arr_delay"] = rng.normal(6.0, 25.0, n)
    values["year"] = 2004
    return values["plane"].astype(np.int64), values, np.arange(n, dtype=np.float64)


def _names_in_trace(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path[0])
    return {
        ev.name
        for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
    }


def test_every_span_appears_in_one_engine_life(tmp_path):
    """Compile, table growth, routing kernels, ticks, periods, an ALBIC
    decision and a migration, all under one profiler session."""
    import jax

    from repro.core import AdaptationFramework, AlbicParams
    from repro.core.migration import Migration, MigrationPlan, execute_plan
    from repro.engine import Controller, ControllerConfig

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # annotations only, as the benchmark traces
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng = Engine(
            real_job_3(keygroups_per_op=8), 4, service_rate=1e9, seed=0,
            config=ExecutionConfig.jit().replace(kernel_stats=True),
            collect_sinks=True,
        )
        framework = AdaptationFramework(
            mode="albic", max_migrations=2,
            albic_params=AlbicParams(max_ld=10.0, time_limit=0.5),
        )
        batches = iter(range(100))

        def feed(engine, tick):
            engine.push_source("airline", *_airline(60, next(batches)))

        ctrl = Controller(
            eng, framework, ControllerConfig(ticks_per_period=3, warmup_periods=0),
            feeder=feed,
        )
        ctrl.period(adapt=True)
        kg = eng.topology.kg_base(eng.topology._resolve("sumdelay"))
        src = int(eng.router.table[kg])
        execute_plan(MigrationPlan([Migration(kg, src, (src + 1) % 4, 1.0)]), eng)
        eng.tick()
    finally:
        jax.profiler.stop_trace()
    missing = set(SPANS) - _names_in_trace(str(tmp_path))
    assert not missing, missing


def test_device_fetches_of_one_sumdelay_call():
    """Hand count: the first call pushes the key groups' (empty) dict state
    (codes, vals, seq, owner and the epoch: 5 reads), then reads the table's
    entry count (1), the output keys and times (2) and the two value
    columns, plane and sum (2); a second call on the same key groups reads
    only its results (5); a call whose new keys overflow the table reads
    its count (1), the codes to grow the table on the host (1), then the
    re-run's results (5); a table holding more than half its slots is grown
    before the next call (1)."""
    from repro.engine.jitexec import JitRuntime

    topo = real_job_2(keygroups_per_op=4)
    metrics = EngineMetrics()
    rt = JitRuntime(topo, KeyedStore(topo.num_keygroups), metrics, topo.kg_operator())
    op = topo._resolve("sumdelay")
    base = topo.kg_base(op)

    def call(n, planes=24):
        values = np.zeros(n, dtype=EXTRACT_SCHEMA.value)
        values["plane"] = np.arange(n) % planes
        values["year"] = 2004
        values["delay"] = 1.0
        half = n // 2
        before = metrics.device_fetches
        rt.execute(op, [base, base + 1], [0, half], [half, n],
                   values["plane"].copy(), values, np.zeros(n))
        return metrics.device_fetches - before

    assert call(8) == 5 + 1 + 2 + 2
    assert (metrics.table_entries, metrics.table_capacity) == (8, 64)
    assert call(8) == 5
    assert call(64) == 5
    assert metrics.table_grows == 0
    assert (metrics.table_entries, metrics.table_capacity) == (24, 64)
    assert call(100, planes=100) == 1 + 1 + 5
    assert (metrics.table_grows, metrics.table_retries) == (1, 1)
    assert (metrics.table_entries, metrics.table_capacity) == (100, 256)
    assert call(150, planes=150) == 5
    assert call(8) == 1 + 5
    assert (metrics.table_grows, metrics.table_retries) == (2, 1)
    assert (metrics.table_entries, metrics.table_capacity) == (150, 512)


def test_device_fetches_of_one_routed_batch():
    """Hand count: the partition kernel's key-group ids and histogram (2);
    the bucket argsort fetches its order on a TPU only (0 here)."""
    import jax

    eng = Engine(
        real_job_2(keygroups_per_op=8), 4, service_rate=1e9, seed=0,
        config=ExecutionConfig.jit().replace(kernel_stats=True),
    )
    assert eng._argsort_fetches == int(jax.default_backend() == "tpu")
    eng.push_source("airline", *_airline(64, 1))
    assert eng.metrics.device_fetches == 2 + eng._argsort_fetches
    assert eng.metrics.jit_host_syncs == 0

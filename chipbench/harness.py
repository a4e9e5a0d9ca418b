"""One run of one cell: set-up, warm-up, the measured window, the check.

The driven entry is the path users run: ``Controller.period()`` over
``Engine(..., config=ExecutionConfig.jit())``, fed by a feeder that calls
``Engine.push_source`` before every tick.  The engine collects its sink
outputs (``collect_sinks=True``, its result interface): a tick returns
after its results are on the host, so a tick's end is when they arrived.

Each event's ``ts`` is its index in the run's stream, so every sink output
names its event.  After the window the run drains, reads the device's
memory peak, takes the engine's outputs and final tables, frees the engine
and hands everything to the configuration's plain reference.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import os
import time
from typing import Optional

import numpy as np

from chipbench import registry

#: How long the drain after the window may wait for late results, and how
#: many ticks without a new result end it (a tick moves every queued tuple
#: one operator on, so a result still to come shows within a few ticks).
DRAIN_LIMIT_S = 60.0
IDLE_TICKS = 8
#: The host spans the harness records around the calls into each layer.
SPANS = ("push_source", "tick", "end_period", "adapt", "execute_plan")


def process_age_s() -> float:
    """Seconds since this process started (0 where /proc cannot say)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


class Spans:
    """Host-clock spans from the benchmark's own wrappers around the calls
    into each layer; with ``annotate`` each is also a profiler
    ``TraceAnnotation``, so the trace can say what the host was doing."""

    def __init__(self, annotate: bool) -> None:
        self.records: dict[str, list] = collections.defaultdict(list)
        self._annotation = None
        if annotate:
            import jax

            self._annotation = jax.profiler.TraceAnnotation

    def wrap(self, fn, name: str):
        rec = self.records[name]
        clock = time.perf_counter
        ann = self._annotation
        if ann is None:
            def timed(*args, **kwargs):
                t0 = clock()
                try:
                    return fn(*args, **kwargs)
                finally:
                    rec.append((t0, clock()))
        else:
            def timed(*args, **kwargs):
                with ann(name):
                    t0 = clock()
                    try:
                        return fn(*args, **kwargs)
                    finally:
                        rec.append((t0, clock()))
        return timed

    def within(self, name: str, lo: float, hi: float) -> list:
        return [(a, b) for a, b in self.records.get(name, ()) if a >= lo and b <= hi]


class _Framework:
    """The adaptation framework behind a timing wrapper around ``adapt``."""

    def __init__(self, framework, spans: Spans) -> None:
        self._framework = framework
        self.adapt = spans.wrap(framework.adapt, "adapt")

    def __getattr__(self, name):
        return getattr(self._framework, name)


class Feeder:
    """Pushes the stream's next events before every tick.

    ``target`` decides how many: a fixed list of sizes (warm-up), a full
    batch (closed loop), or every event due by the clock (open loop), never
    more than ``cap`` in one tick."""

    def __init__(self, stream, source: str, cap: int, spans: Spans) -> None:
        self.stream = stream
        self.source = source
        self.cap = cap
        self.next = 0  # events [0, next) were offered to the engine
        self.accepted = 0
        self.sizes: list = []  # events offered per push
        self.target = lambda: self.next  # index the stream may reach by now
        self._push = spans.wrap(self._push_source, "push_source")

    def _push_source(self, engine, keys, values, ts) -> int:
        return engine.push_source(self.source, keys, values, ts)

    def __call__(self, engine, tick: int) -> None:
        n = min(self.target() - self.next, self.cap)
        if n <= 0:
            return
        keys, values, ts = self.stream.batch(self.next, self.next + n)
        self.accepted += self._push(engine, keys, values, ts)
        self.next += n
        self.sizes.append(n)


@dataclasses.dataclass
class Run:
    """What one run recorded; per-layer readers take what they need."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    spans: Spans
    t0: float = 0.0  # first timed tick
    t1: float = 0.0  # window closed
    setup_s: float = 0.0
    warmup_events: int = 0
    window_events: tuple = (0, 0)  # [a, b): events attempted in the window
    schedule: tuple = (0.0, 0)  # open loop: event ``origin`` was due at ``s0``
    window_ticks: int = 0
    counters: dict = dataclasses.field(default_factory=dict)  # window deltas
    periods: list = dataclasses.field(default_factory=list)
    compiles: dict = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0
    completion: Optional[np.ndarray] = None  # host time each event completed
    checks: dict = dataclasses.field(default_factory=dict)
    bad: Optional[np.ndarray] = None
    trace: Optional[dict] = None
    device_kind: str = ""
    info: dict = dataclasses.field(default_factory=dict)

    def window_spans(self, name: str) -> list:
        return self.spans.within(name, self.t0, self.t1)

    def due_time(self, idx: np.ndarray) -> np.ndarray:
        """Scheduled creation time of events (open loop)."""
        s0, origin = self.schedule
        return s0 + (idx - origin) / float(self.traffic["rate_per_s"])


_COUNTERS = (
    "ticks",
    "processed_tuples",
    "sink_tuples",
    "cross_node_tuples",
    "jit_calls",
    "jit_compiles",
    "jit_host_syncs",
    "dropped_credits",
)


def _counters(engine) -> dict:
    return {k: getattr(engine.metrics, k) for k in _COUNTERS}


def build_engine(config: dict, seed: int):
    """The cell's engine on the jit tier, round-robin allocated, whose
    source admits every batch whole."""
    from repro import data
    from repro.engine import Engine, ExecutionConfig

    if config["execution"] != "jit":
        raise ValueError(f"unknown execution tier {config['execution']!r}")
    topology = getattr(data, config["topology"])(
        keygroups_per_op=config["keygroups_per_op"]
    )
    nodes = config["nodes"]
    if config["initial_allocation"] != "round_robin":
        raise ValueError(f"unknown allocation {config['initial_allocation']!r}")
    alloc = np.arange(topology.num_keygroups) % nodes
    engine = Engine(
        topology,
        nodes,
        config=ExecutionConfig.jit(),
        initial_alloc=alloc,
        service_rate=1e9,  # every queue drains whole each tick
        seed=int(seed) % 2**63,
        collect_sinks=True,
    )
    # Credits shrink with queued work; twice the cap never drops a batch.
    engine.backpressure.full_credit = 2 * config["events_per_tick"]
    return engine


def _framework(config: dict):
    from repro.core import AdaptationFramework, AlbicParams

    a = config["albic"]
    return AdaptationFramework(
        mode="albic",
        max_migrations=a["max_migrations"],
        albic_params=AlbicParams(max_ld=a["max_ld"], time_limit=a["time_limit"]),
    )


def execute(
    cell,
    seed: int,
    seconds: float,
    *,
    trace_dir: Optional[str] = None,
    overrides: Optional[dict] = None,
    t_start: Optional[float] = None,
    monitor=None,
) -> Run:
    """Run one cell once: ``cell`` is a name in ``BENCHMARK.json`` or an
    entry of that form (``config``, ``traffic``); ``overrides`` (tests,
    readings) replace configuration and traffic entries, e.g. to shrink the
    sizes on the CPU."""
    from repro.engine import Controller, ControllerConfig
    import repro.engine.controller as controller_mod

    t_start = time.perf_counter() if t_start is None else t_start
    if isinstance(cell, str):
        cell = registry.cell(cell)
    config = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    for k, v in (overrides or {}).get("config", {}).items():
        config[k] = v
    for k, v in (overrides or {}).get("traffic", {}).items():
        mix[k] = v
    gen = registry.generator(mix["generator"])
    stream = gen.Stream(config, mix, seed)
    sizes = gen.warmup_sizes(mix)
    period = config["ticks_per_period"]
    if len(sizes) % period:
        raise ValueError("warm-up batches must fill whole periods")
    cap = config["events_per_tick"]
    run = Run(cell, config, mix, seed, seconds, Spans(trace_dir is not None))
    import jax

    run.device_kind = jax.devices()[0].device_kind
    spans = run.spans

    engine = build_engine(config, seed)
    marks_t: list = []  # host time at each tick's end
    marks_n: list = []  # sink outputs collected by then
    sink_outputs = engine.metrics.sink_outputs
    tick = spans.wrap(engine.tick, "tick")

    def timed_tick():
        tick()
        marks_t.append(time.perf_counter())
        marks_n.append(len(sink_outputs))

    engine.tick = timed_tick
    engine.end_period = spans.wrap(engine.end_period, "end_period")
    feeder = Feeder(stream, config["source_operator"], cap, spans)
    ctrl = Controller(
        engine,
        _Framework(_framework(config), spans),
        ControllerConfig(ticks_per_period=period),
        feeder=feeder,
    )
    adapt = bool(mix["adapt"])
    plan = controller_mod.execute_plan
    controller_mod.execute_plan = spans.wrap(plan, "execute_plan")
    try:
        # ---- warm-up: fixed batch sizes, whole periods ---------------------
        todo = iter(sizes)
        feeder.target = lambda: feeder.next + next(todo, 0)
        for _ in range(len(sizes) // period):
            ctrl.period(adapt=adapt)
        loop = mix["loop"]
        if loop == "open":
            rate = float(mix["rate_per_s"])
            # The schedule starts here and runs on into the window, so the
            # window opens in the open loop's steady state: its first tick
            # admits what fell due during the tick before, not a sliver.
            origin = feeder.next
            s0 = time.perf_counter()
            run.schedule = (s0, origin)
            feeder.target = lambda: origin + int((time.perf_counter() - s0) * rate)
            for _ in range(int(mix["open_warmup_periods"])):
                ctrl.period(adapt=adapt)
        run.warmup_events = feeder.next
        if monitor is not None:
            run.info["setup_compiles"] = dict(monitor.counts)
            monitor.mark()
        c0 = _counters(engine)
        nperiods = len(ctrl.history)
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans: the benchmark's own
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # ---- the measured window ------------------------------------------
        first = feeder.next
        run.t0 = t0 = time.perf_counter()
        if loop == "open":
            first = origin + math.ceil((t0 - s0) * rate)  # first due in the window
        elif loop == "closed":
            feeder.target = lambda: feeder.next + cap
        else:
            raise ValueError(f"unknown loop {loop!r}")
        push0 = len(feeder.sizes)
        backlog = run.info["backlog_per_period"] = []

        def window():
            while time.perf_counter() - t0 < seconds:
                ctrl.period(adapt=adapt)
                backlog.append(feeder.target() - feeder.next)

        window_ann = spans.wrap(window, "window")
        tick0 = len(marks_t)
        window_ann()
        run.t1 = t1 = time.perf_counter()
        run.window_ticks = len(marks_t) - tick0
        sizes_w = np.asarray(feeder.sizes[push0:], dtype=np.int64)
        buckets, counts = np.unique(
            1 << np.ceil(np.log2(np.maximum(sizes_w, 1))).astype(np.int64),
            return_counts=True,
        )
        run.info["window_batches_by_bucket"] = dict(
            zip(buckets.tolist(), counts.tolist())
        )
        if trace_dir is not None:
            jax.profiler.stop_trace()
        if monitor is not None:
            run.compiles = monitor.since_mark()
        c1 = _counters(engine)
        run.counters = {k: c1[k] - c0[k] for k in _COUNTERS}
        run.periods = ctrl.history[nperiods:]
        last = origin + int((t1 - s0) * rate) if loop == "open" else feeder.next
        run.window_events = (first, last)
        # ---- drain: every event offered (or due) by the close completes ----
        feeder.target = lambda: last
        nsinks = len(config["sinks"])
        deadline = time.perf_counter() + DRAIN_LIMIT_S
        still = 0  # ticks without a new result once everything was offered
        while time.perf_counter() < deadline and still < IDLE_TICKS and (
            feeder.next < last or len(sink_outputs) < nsinks * feeder.next
        ):
            seen = len(sink_outputs)
            feeder(engine, 0)
            engine.tick()
            still = still + 1 if feeder.next >= last and len(sink_outputs) == seen else 0
        run.info["drain_ticks"] = len(marks_t) - tick0 - run.window_ticks
        run.info["drain_s"] = time.perf_counter() - t1
        run.info["dropped_credits"] = engine.metrics.dropped_credits
        run.memory_peak_bytes = _memory_peak()
        # ---- hand the results to the reference -----------------------------
        engine.end_period()
        n_events = feeder.next
        ref = registry.reference(config["reference"])
        outputs = ref.parse_outputs(sink_outputs, config["sinks"])
        state = ref.parse_state(engine.store.items())
        run.info["offered"] = n_events
        run.info["accepted"] = feeder.accepted
    finally:
        controller_mod.execute_plan = plan
    del engine, ctrl, sink_outputs
    t_check = time.perf_counter()
    events = stream.columns(0, n_events)
    # Events due in the window but never offered have no result either.
    run.completion = _completion(
        outputs, config["sinks"], marks_t, marks_n, max(n_events, last)
    )
    run.checks, run.bad = ref.check(config, events, outputs, state)
    run.setup_s = run.t0 - t_start
    run.info["check_s"] = time.perf_counter() - t_check
    return run


def _memory_peak() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _completion(outputs, sinks, marks_t, marks_n, n_events) -> np.ndarray:
    """Host time at which each event's last sink result was collected
    (``inf`` where one never came)."""
    done = np.full(n_events, -np.inf)
    seen = np.zeros(n_events, dtype=np.int64)
    mt = np.asarray(marks_t, dtype=np.float64)
    mn = np.asarray(marks_n, dtype=np.int64)
    for sink in sinks:
        o = outputs.get(sink)
        if o is None or not len(o["ts"]):
            continue
        ids = o["ts"].astype(np.int64)
        ok = (ids >= 0) & (ids < n_events)
        at = mt[np.minimum(np.searchsorted(mn, o["pos"], side="right"), len(mt) - 1)]
        np.maximum.at(done, ids[ok], at[ok])
        np.add.at(seen, np.unique(ids[ok]), 1)
    done[seen < len(sinks)] = np.inf
    return done

"""The program spans' reduction (``chipbench/spans.py``) and the readers of
the program's spans and counters, on a tiny `job3.steady` run traced on the
CPU and on the committed chip traces.  The CPU run's times are host
arithmetic, not device numbers: only the reduction is checked on them."""

import gzip
import math
import os

import numpy as np
import pytest

from chipbench import harness, registry, spans
from chipbench.tests.tiny import tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: A window traced on a TPU v5e before the program had spans of its own.
WITHOUT_SPANS = os.path.join(DATA, "job3_steady_v5e.xplane.pb.gz")
#: A `job3.steady` window of 8 ticks traced on a TPU v5e with the program's
#: spans (`spans.py --seconds 1`, seed 3000000017), in which the program
#: counted 240 device fetches (its `device_fetches_in_window`).
WITH_SPANS = os.path.join(DATA, "job3_steady_spans_v5e.xplane.pb.gz")
#: Spans of the tiny run that nest in a fixed parent (span: parents).
PARENTS = {
    "route.argsort": {"route"},
    "route": {"engine.flush_outputs", "push_source"},
    "engine.flush_outputs": {"tick"},
    "engine.drain": {"tick"},
    "jit.call": {"tick", "engine.drain"},
    "jit.fetch": {"jit.call"},
    "jit.launch": {"jit.call"},
    "period.sync_store": {"end_period"},
    "period.fold": {"end_period"},
}
#: Spans on the steady path on the CPU: no routing partition kernel (it runs
#: on a TPU only), no compile or table growth after the warm-up, no ALBIC.
STEADY = set(spans.SPANS) - {
    "route.partition", "jit.grow", "jit.compile", "controller.decide",
    "migration.pause",
}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny `job3.steady` run, traced: the line ``spans.py`` prints, and
    the profile it kept."""
    keep = str(tmp_path_factory.mktemp("trace") / "job3_steady.xplane.pb.gz")
    line = spans.traced_run(
        "job3.steady", 2**31 + 11, 0.4, keep=keep, overrides=tiny([[512, 8]])
    )
    return line, _load(keep)


@pytest.fixture(scope="module")
def on_chip():
    return _load(WITH_SPANS)


@pytest.fixture(params=["cpu", "v5e"])
def profile(request):
    """The profile of a traced window and the spans its path records."""
    if request.param == "cpu":
        return request.getfixturevalue("traced")[1], STEADY
    return request.getfixturevalue("on_chip"), STEADY | {"route.partition"}


def _load(path):
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        return ProfileData.from_serialized_xspace(gzip.decompress(f.read()))


def _main_line(pd):
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "window":
                    return line, ev.start_ns, ev.start_ns + ev.duration_ns
    raise AssertionError("no window span")


def _events(line, lo, hi):
    names = set(spans.SPANS) | set(harness.SPANS)
    return sorted(
        ((max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi), ev.name)
         for ev in line.events if ev.name in names),
        key=lambda x: (x[0], -x[1]),
    )


def _brute_force(pd):
    """Counts and totals straight from the events; self and idle time by
    painting each span over a fine grid, outer spans first."""
    line, lo, hi = _main_line(pd)
    res = max(100, (hi - lo) // 5_000_000)  # ns per cell
    n = int(math.ceil((hi - lo) / res))
    names = list(spans.SPANS) + list(harness.SPANS)
    label = np.full(n, -1, dtype=np.int16)
    count, total = {}, {}
    events = [x for x in _events(line, lo, hi) if x[1] > x[0]]
    for s, e, name in events:
        count[name] = count.get(name, 0) + 1
        total[name] = total.get(name, 0) + (e - s) / 1e9
        label[round((s - lo) / res): round((e - lo) / res)] = names.index(name)
    busy = np.zeros(n, dtype=bool)
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            if ln.name != "XLA Ops":
                continue
            for ev in ln.events:
                s, e = max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi)
                if e > s:
                    busy[round((s - lo) / res): round((e - lo) / res)] = True
        if busy.any():
            break
    own = {x: (label == i).sum() * res / 1e9 for i, x in enumerate(names)}
    idle = {x: ((label == i) & ~busy).sum() * res / 1e9 for i, x in enumerate(names)}
    idle["other"] = ((label == -1) & ~busy).sum() * res / 1e9
    tol = res / 1e9 * (2 * len(events) + 2)
    return count, total, own, idle, tol


def test_span_names_are_the_engines():
    from repro.engine.tracing import SPANS

    assert spans.SPANS == SPANS


def test_spans_nest_where_the_work_happens(profile):
    pd, path = profile
    line, lo, hi = _main_line(pd)
    seen, stack = set(), []
    for s, e, name in _events(line, lo, hi):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if name in PARENTS:
            assert stack and stack[-1][1] in PARENTS[name], (name, stack[-1:])
        seen.add(name)
        stack.append((e, name))
    assert path <= seen, path - seen
    # Each tick routes its outputs in more than one batch.
    program = spans.reduce(pd)["spans"]
    assert program["route"]["count"] > program["engine.flush_outputs"]["count"]


def test_reduce_agrees_with_a_brute_force_reading(profile):
    pd, _ = profile
    r = spans.reduce(pd)
    count, total, own, idle, tol = _brute_force(pd)
    assert r["spans"], "no program span in the window"
    for name, got in r["spans"].items():
        assert got["count"] == count[name], name
        assert got["total_s"] == pytest.approx(total[name], abs=1e-9), name
        assert got["self_s"] == pytest.approx(own[name], abs=tol), name
        assert got["self_s"] <= got["total_s"] + 1e-9
    assert set(r["spans"]) == {n for n in count if n in spans.SPANS}
    for name, seconds in idle.items():
        assert r["idle_by_span"].get(name, 0.0) == pytest.approx(seconds, abs=tol), name
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        sum(idle.values()), abs=tol
    )


def test_reduce_finds_nothing_in_a_trace_without_program_spans():
    assert spans.reduce(_load(WITHOUT_SPANS)) is None


def test_the_chips_fetch_events_are_the_programs_count(on_chip):
    """jaxlib's own device→host read events in the window equal the
    program's ``device_fetches`` of the same window (30 per tick)."""
    assert spans.reduce(on_chip)["fetch_events"] == 240


def test_program_readers_on_the_chip_trace(on_chip):
    class Run:
        window_ticks = 8
        trace = {"program": spans.reduce(on_chip)}

    # The host was in some program span for nearly all the idle time.
    assert 0 < registry.metric_reader("idle_untraced_share")(Run()) < 10
    routing = registry.metric_reader("route_kernel_ms_per_tick")(Run())
    assert 0 < routing < registry.metric_reader("route_ms_per_tick")(Run())
    idle = Run.trace["program"]["idle_by_span"]
    assert set(idle) <= set(spans.SPANS) | set(harness.SPANS) | {"other"}


def test_program_readers_on_a_traced_run(traced):
    line, _ = traced
    assert line["correct"] and line["kind"] == "cpu"
    metrics = line["metrics"]
    for name in spans.PROGRAM_METRICS:
        assert metrics[name] >= 0, name
    assert metrics["route_ms_per_tick"] > 0
    assert 0 <= metrics["idle_untraced_share"] <= 100
    # Per tick: extract's keys, times and five value columns (7), SumDelay's
    # table count, keys, times, plane and sum (5), RouteDelay's table count,
    # keys, times and four value columns (7); per period the two tables
    # read back whole (4 columns each).  Routing fetches nothing on the CPU.
    ticks, periods = line["window_ticks"], line["periods_in_window"]
    assert line["device_fetches_in_window"] == 19 * ticks + 8 * periods
    assert metrics["device_fetches_per_tick"] == pytest.approx(19 + 8 * periods / ticks)
    assert 0 < metrics["table_fill"] < 100
    assert line["table_grows_in_window"] == 0


def test_readers_find_nothing_without_the_programs_spans_and_counters():
    """What a traced run of a program without spans or counters gives."""

    class Period:  # a period row without the counters
        num_migrations = 0

    class Run:
        trace = {"busy_s": 1.0, "window_s": 2.0}
        window_ticks = 8
        periods = [Period()]

    names = list(spans.PROGRAM_METRICS) + ["device_fetches_per_tick", "table_fill"]
    for name in names:
        assert registry.metric_reader(name)(Run()) is None, name

"""The q17 cell: the NEXmark bid generator keeps the rules it states, the
plain reference agrees with a dict loop and with the jit tier at a tiny
size, and the comparison fails its control (the reference in int32) and
every planted fault: a count off by one, rows not reset at the day change,
a row lost."""

import functools

import numpy as np
import pytest

from chipbench import harness, registry
from chipbench.verdict import judge, verdict
from repro.data.nexmark import FIRST_ID

CELL = "q17.steady"
CONFIG = "nexmark_q17_20n400kg"
GEN = registry.generator("nexmark")
REF = registry.reference("nexmark")
MIX = registry.traffic("nexmark_bids")
#: The cell on the CPU: only scale shrinks, and midnight falls in the window.
TINY = {
    "config": {
        "nodes": 4,
        "keygroups_per_op": 8,
        "events_per_tick": 512,
        "ticks_per_period": 4,
    },
    "traffic": {"warmup_batches": [[512, 8]], "midnight_bid": 5000},
}


def _config():
    return registry.config(CONFIG)


def _stream(seed=2**31 + 77, **mix):
    return GEN.Stream(_config(), dict(MIX, **mix), seed)


def _run(seed=2**31 + 5, seconds=0.6):
    return harness.execute(CELL, seed, seconds, overrides=TINY)


@pytest.fixture
def tiny_rows(monkeypatch):
    """The tiny cell's table expects rows at its own scale, 4,096: its
    compile ahead then stays a few programs on the CPU."""
    from repro import data

    monkeypatch.setattr(
        data, "nexmark_q17", functools.partial(data.nexmark_q17, expected_rows=4096)
    )


# ------------------------------------------------------------- generator
def test_bids_are_a_pure_function_of_seed_and_index():
    one, two, other = _stream(), _stream(), _stream(seed=2**31 + 78)
    n = (GEN.POOL + 2) * GEN.BLOCK + 9
    k, v, t = one.batch(0, n)
    k2, v2, t2 = two.batch(0, n)
    assert k.tobytes() == k2.tobytes() and v.tobytes() == v2.tobytes()
    assert v.tobytes() != other.batch(0, n)[1].tobytes()
    cut = 3 * GEN.BLOCK - 11
    parts = [one.batch(0, cut), one.batch(cut, n)]
    assert np.concatenate([p[1] for p in parts]).tobytes() == v.tobytes()
    assert (np.concatenate([p[0] for p in parts]) == k).all()
    assert (t == np.arange(n)).all() and (k == v["auction"]).all()
    # Past the pool's end the draws repeat, the ids and times do not.
    a = GEN.POOL * GEN.BLOCK
    assert (v["price"][a:a + 100] == v["price"][GEN.BLOCK:GEN.BLOCK + 100]).all()
    assert (v["auction"][a:a + 100] > v["auction"][GEN.BLOCK:GEN.BLOCK + 100]).all()


def test_proportions_hot_auction_and_price_bands():
    s = _stream()
    i = np.arange(46 * 28_000, dtype=np.int64)
    eid = s.event_ids(i)
    # 46 bids in every 50 events, after the cycle's person and 3 auctions.
    assert (np.bincount(eid // 50) == 46).all() and (eid % 50 >= 4).all()
    cols = s.columns(GEN.BLOCK, 9 * GEN.BLOCK)
    last = (s.event_ids(np.arange(GEN.BLOCK, 9 * GEN.BLOCK)) // 50) * 3 + 2
    hot = FIRST_ID + last // 100 * 100
    share = (cols["auction"] == hot).mean()
    assert 0.5 <= share < 0.52  # half hot, plus cold draws that hit it
    # Cold auctions: the last 100 and 10 ahead.
    a0 = cols["auction"] - FIRST_ID
    assert ((a0 >= last - 100) & (a0 <= last + 10)).all()
    price = cols["price"]
    assert price.min() >= 100 and price.max() <= 10**8
    bands = np.array([(price < 10_000).mean(), (price >= 1_000_000).mean()])
    assert np.abs(bands - 1 / 3).max() < 0.01
    # About 4,270 new auctions every 65,536 bids: 3 for every 46.
    assert abs(np.ptp(a0) - 8 * GEN.BLOCK * 3 / 46) < 120


def test_midnight_falls_at_its_bid():
    s = _stream()
    mb = MIX["midnight_bid"]
    dt = s.columns(mb - 2, mb + 2)["date_time"]
    day = dt // REF.DAY_MS
    assert dt[2] == GEN.MIDNIGHT_DAY * REF.DAY_MS
    assert day.tolist() == [GEN.MIDNIGHT_DAY - 1] * 2 + [GEN.MIDNIGHT_DAY] * 2


# ------------------------------------------------------------- reference
def _dict_loop(events):
    rows, out = {}, []
    cols = (events[k].tolist() for k in ("auction", "price", "date_time"))
    for a, p, dt in zip(*cols):
        key = (a, dt // REF.DAY_MS)
        r1, r3 = int(p < 10_000), int(p >= 1_000_000)
        n, c1, c2, c3, lo, hi, s = rows.get(key, (0, 0, 0, 0, p, p, 0))
        row = (n + 1, c1 + r1, c2 + 1 - r1 - r3, c3 + r3, min(lo, p), max(hi, p), s + p)
        rows[key] = row
        out.append(row[:6] + (row[6] // row[0], row[6]))
    return rows, out


def test_reference_agrees_with_a_dict_loop():
    s = _stream(midnight_bid=12_000)
    events = s.columns(0, 30_000)
    rows, out = _dict_loop(events)
    ref, _, _ = REF._rows(events)
    got = list(zip(*(ref[k].tolist() for k in REF.AGGREGATES)))
    assert got == out
    config = dict(_config(), **TINY["config"])
    outputs, state = REF.control(config, events, np.int64)
    table = state["q17"]
    assert len(table["auction"]) == len(rows)
    for j in range(len(table["auction"])):
        key = (int(table["auction"][j]), int(table["day"][j]))
        assert tuple(int(table[c][j]) for c in REF.COLUMNS) == rows[key]
    same, bad = REF.check(config, events, outputs, state)
    assert same == {"lost": 0, "misplaced": 0, "sum_gap": 0.0, "state_gap": 0.0}
    assert not bad.any()


def test_int32_control_fails():
    """At its own scale as at full size: the hot auction's sum of prices
    passes 2**31 within its 100 auctions' turn, and int32 wraps."""
    config = _config()
    events = _stream().columns(0, 300_000)
    ref, _, _ = REF._rows(events)
    assert ref["sum_price"].max() > 2**31
    low, _ = REF.check(config, events, *REF.control(config, events, np.int32))
    correct, checks = judge(config, dict(low, unfinished=0))
    assert not correct
    assert low["sum_gap"] > 0 and low["state_gap"] > 0
    assert low["lost"] == 0 and low["misplaced"] == 0


# ------------------------------------------------------------- the cell
def test_q17_steady_is_correct_through_the_harness(tiny_rows):
    run = _run()
    correct, attempted, failed, checks = verdict(run)
    assert correct, checks
    assert attempted > 0 and failed == 0
    first, last = run.window_events
    assert first < TINY["traffic"]["midnight_bid"] < last  # the day changes
    assert sum(p.table_grows for p in run.periods) >= 1
    # The window grows into capacities compiled ahead: it compiles nothing.
    assert run.counters["jit_compiles"] == 0
    for name in ("table_grow_ms_per_tick", "table_fill", "host_syncs_per_tick"):
        assert registry.metric_reader(name)(run) > 0, name


def _count_off_by_one(fn):
    def body(state, kgs, starts, ends, keys, values, ts):
        state, (k, out, t), counts = fn(state, kgs, starts, ends, keys, values, ts)
        out = dict(out, total_bids=out["total_bids"].at[0].add(1))
        return state, (k, out, t), counts

    return body


def _one_day(fn):
    """Every bid's row kept under one day: nothing resets at the day
    change, though each row still names its bid's day."""

    def body(state, kgs, starts, ends, keys, values, ts):
        day = values["date_time"] // REF.DAY_MS
        flat = dict(values, date_time=values["date_time"] * 0)
        state, (k, out, t), counts = fn(state, kgs, starts, ends, keys, flat, ts)
        return state, (k, dict(out, day=day), t), counts

    return body


def _drop_one(push):
    def push_less(self, op, keys, values, ts):
        push(self, op, keys[1:], values[1:], ts[1:])
        return len(keys)

    return push_less


@pytest.mark.parametrize("fault", ["count_off_by_one", "no_day_reset", "row_lost"])
def test_every_fault_comes_out_not_correct(monkeypatch, tiny_rows, fault):
    from repro.data import nexmark
    from repro.engine import Engine

    if fault == "count_off_by_one":
        monkeypatch.setattr(nexmark, "_q17_jit", _count_off_by_one(nexmark._q17_jit))
    elif fault == "no_day_reset":
        monkeypatch.setattr(nexmark, "_q17_jit", _one_day(nexmark._q17_jit))
    else:
        monkeypatch.setattr(Engine, "push_source", _drop_one(Engine.push_source))
    run = _run()
    correct, attempted, failed, checks = verdict(run)
    assert not correct, checks
    assert failed > 0
    if fault == "row_lost":
        assert checks["lost"]["value"] > 0


def test_new_readers_read_their_sources_and_nothing_else():
    """``keyed_agg_roofline`` takes q17's device operations and the
    window's sink tuples; both new readers find nothing in a run of a
    program without q17 or without the ``table_grow_s`` counter."""
    from chipbench import keyed_agg_costs, peaks

    class Period:
        table_grow_s = 0.25

    class Run:
        device_kind = "TPU v5 lite"
        window_ticks = 10
        counters = {"sink_tuples": 655_360}
        periods = [Period(), Period()]
        trace = {"device_ops": [["jit__q17_jit:fusion", 0.5],
                                ["jit__q17_jit:bucket_argsort_pallas", 0.25],
                                ["jit_bucket_argsort_pallas:fusion", 4.0]]}

    roofline = registry.metric_reader("keyed_agg_roofline")
    grow = registry.metric_reader("table_grow_ms_per_tick")
    bw = peaks.peak("TPU v5 lite", "hbm_bytes_per_s")
    assert roofline(Run()) == pytest.approx(
        100 * keyed_agg_costs.keyed_agg_bytes(655_360) / (0.75 * bw)
    )
    assert keyed_agg_costs.keyed_agg_bytes(1) == 80
    assert grow(Run()) == pytest.approx(50.0)

    class Bare:  # the parent: no q17 program, no counter
        device_kind = "TPU v5 lite"
        window_ticks = 10
        counters = {"sink_tuples": 655_360}
        periods = [object()]
        trace = {"device_ops": [["jit__sum_delay_jit:fusion", 1.0]]}

    assert roofline(Bare()) is None and grow(Bare()) is None

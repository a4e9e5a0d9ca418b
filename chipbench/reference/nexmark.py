"""Plain reference of NEXmark q17, and the comparison that decides
``correct``.

The reference imports nothing of the engine.  From the generated bids
(index order = admission order) it computes q17 with numpy in int64: for
every bid, the row of its (auction, day), day = ``date_time // 86,400,000``
(UTC), as it stands after the bid — the count, the counts of the three
price bands (below 10,000; 10,000 to below 1,000,000; from 1,000,000
cents), min, max, ``avg = sum // count`` (prices are positive, so floor
and Flink's truncating AVG agree) and sum of the prices — and each (auction,
day)'s final row.  Rows are grouped by one stable sort of the key codes;
counts and sums are prefix sums less the group's prefix before it, and the
running min and max of the sorted groups are one ``minimum``/``maximum``
accumulation over the prices offset by a multiple of the group's rank,
which keeps every group from seeing its neighbours'.

The comparison holds the engine to the configuration's guarantees:

* ``lost``: bids whose row did not arrive exactly once (each output's
  ``ts`` is its bid's index);
* ``misplaced``: rows whose key, auction or day is not their bid's, rows of
  one (auction, day) out of admission order, final-table rows missing,
  extra, or in a key group other than the one their auction hashes to;
* ``sum_gap``: the widest gap of an emitted aggregate from the reference,
  ``|engine - reference| / max(|reference|, 1)``;
* ``state_gap``: the same for the final table's seven columns.

Integer arithmetic is exact on both sides, so both limits are 0.

The key → key group map is the engine's published partition function
(a 32-bit murmur-style mix of the folded key, modulo the key groups),
written out here from its definition.
"""

from __future__ import annotations

import itertools

import numpy as np

SINK = "q17"
#: Output fields, in the engine's record order.
FIELDS = (
    "auction",
    "day",
    "total_bids",
    "rank1_bids",
    "rank2_bids",
    "rank3_bids",
    "min_price",
    "max_price",
    "avg_price",
    "sum_price",
)
AGGREGATES = FIELDS[2:]
#: The table's columns, in the engine's order.
COLUMNS = ("total_bids", "rank1_bids", "rank2_bids", "rank3_bids",
           "min_price", "max_price", "sum_price")
TABLE = "q17"
DAY_MS = 86_400_000
_MASK31 = 0x7FFFFFFF


def keygroup(keys: np.ndarray, base: int, nkg: int) -> np.ndarray:
    """Global key group of integer partition keys."""
    u = np.asarray(keys).astype(np.uint64)
    h = ((u ^ (u >> np.uint64(32))) & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    with np.errstate(over="ignore"):
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return base + (h & np.uint32(_MASK31)).astype(np.int64) % nkg


def _codes(auction: np.ndarray, day: np.ndarray) -> np.ndarray:
    return (auction.astype(np.int64) << 20) | day.astype(np.int64)


def _groups(codes: np.ndarray):
    """Stable grouping: (order, starts, ends) of equal codes."""
    order = np.argsort(codes, kind="stable")
    sc = codes[order]
    starts = np.flatnonzero(np.concatenate(([True], sc[1:] != sc[:-1])))
    ends = np.append(starts[1:], len(sc))
    return order, starts, ends


def _rows(events: dict, dtype=np.int64) -> tuple[dict, np.ndarray, tuple]:
    """Each bid's row after it, in index order, computed in ``dtype``, and
    the bids' codes and grouping."""
    n = len(events["ts"])
    day = events["date_time"] // DAY_MS
    codes = _codes(events["auction"], day)
    order, starts, ends = _groups(codes)
    lens = ends - starts
    gid = np.repeat(np.arange(len(starts)), lens)
    price = events["price"][order]

    def running(x):  # prefix sums within groups, in ``dtype``
        x = x.astype(dtype)
        c = np.cumsum(x, dtype=dtype)
        before = np.concatenate(([0], c[:-1])).astype(dtype)[starts]
        with np.errstate(over="ignore"):
            return c - np.repeat(before, lens)

    rank1 = price < 10_000
    rank3 = price >= 1_000_000
    sorted_rows = {
        "total_bids": running(np.ones(n, dtype=np.int64)),
        "rank1_bids": running(rank1),
        "rank2_bids": running(~rank1 & ~rank3),
        "rank3_bids": running(rank3),
        "sum_price": running(price),
    }
    if n:
        lo = int(price.min())
        width = int(price.max()) - lo + 1
        off = gid * width  # every later group lies wholly above (max) ...
        shifted = price - lo
        sorted_rows["max_price"] = np.maximum.accumulate(shifted + off) - off + lo
        # ... or, negated, wholly below (min).
        sorted_rows["min_price"] = np.minimum.accumulate(shifted - off) + off + lo
    else:
        sorted_rows["max_price"] = sorted_rows["min_price"] = price
    for k in ("max_price", "min_price"):
        sorted_rows[k] = sorted_rows[k].astype(dtype)
    with np.errstate(divide="ignore"):
        sorted_rows["avg_price"] = sorted_rows["sum_price"] // sorted_rows["total_bids"]
    rows = {}
    for k, v in sorted_rows.items():
        out = np.empty(n, dtype=v.dtype)
        out[order] = v
        rows[k] = out
    return rows, codes, (order, starts, ends)


# ---------------------------------------------------------------- parsing
def parse_outputs(sink_outputs: list, sinks: list) -> dict:
    """Engine sink tuples ``(key, record, ts)`` → column arrays of q17's
    rows (``pos``: each output's place in the engine's list; records of
    another arity count as ``unknown``)."""
    width = len(FIELDS)
    n = len(sink_outputs)
    keys, recs, ts = zip(*sink_outputs) if n else ((), (), ())
    keys = np.fromiter(keys, dtype=np.int64, count=n)
    ts = np.fromiter(ts, dtype=np.float64, count=n)
    arity = np.fromiter(map(len, recs), dtype=np.int64, count=n)
    idx = np.flatnonzero(arity == width)
    mine = recs if len(idx) == n else [recs[i] for i in idx.tolist()]
    rows = np.fromiter(
        itertools.chain.from_iterable(mine), dtype=np.int64, count=len(idx) * width
    ).reshape(len(idx), width)
    cols = {"key": keys[idx], "ts": ts[idx], "pos": idx}
    for j, name in enumerate(FIELDS):
        cols[name] = rows[:, j]
    return {SINK: cols, "unknown": int(n - len(idx))}


def parse_state(items) -> dict:
    """Engine store ``(key group, state dict)`` pairs → arrays of the q17
    table's rows: key group, auction, day and the seven columns."""
    kgs, keys, rows = [], [], []
    other = 0
    for kg, st in items:
        for name, table in st.items():
            if name != TABLE:
                other += 1
                continue
            kgs.extend([kg] * len(table))
            keys.extend(table.keys())
            rows.extend(table.values())
    keys = np.asarray(keys, dtype=np.int64).reshape(len(keys), 2)
    rows = np.asarray(rows, dtype=np.int64).reshape(len(rows), len(COLUMNS))
    out = {
        "kg": np.asarray(kgs, dtype=np.int64),
        "auction": keys[:, 0],
        "day": keys[:, 1],
    }
    for j, name in enumerate(COLUMNS):
        out[name] = rows[:, j]
    return {TABLE: out, "other": other}


# ---------------------------------------------------------------- control
def control(config: dict, events: dict, dtype=np.int32) -> tuple[dict, dict]:
    """The reference put in the engine's place, computed in ``dtype``:
    rows in admission order and the final table, in the engine's shapes
    (the control of the comparison: int32 for the configured int64)."""
    n = len(events["ts"])
    rows, _, (order, starts, ends) = _rows(events, dtype)
    day = events["date_time"] // DAY_MS
    out = {name: rows[name].astype(np.int64) for name in AGGREGATES}
    out.update(auction=events["auction"], day=day, key=events["auction"],
               ts=np.arange(n, dtype=np.float64), pos=np.arange(n))
    nkg = config["keygroups_per_op"]
    base = config["operators"].index(SINK) * nkg
    last = order[ends - 1] if n else np.empty(0, np.int64)
    table = {"kg": keygroup(events["auction"][last], base, nkg),
             "auction": events["auction"][last], "day": day[last]}
    table.update({name: rows[name][last].astype(np.int64) for name in COLUMNS})
    return {SINK: out, "unknown": 0}, {TABLE: table, "other": 0}


# ------------------------------------------------------------- comparison
def _gap(x: np.ndarray, ref: np.ndarray) -> np.ndarray:
    diff = np.abs(x.astype(np.int64) - ref.astype(np.int64)).astype(np.float64)
    return diff / np.maximum(np.abs(ref.astype(np.float64)), 1.0)


def check(
    config: dict, events: dict, outputs: dict, state: dict
) -> tuple[dict, np.ndarray]:
    """Compare the engine's rows and final table with the reference.

    Returns ``({number: value}, bad)`` where ``bad[i]`` marks bid ``i``
    when its row is lost, duplicated, misplaced or off by more than
    ``config["limits"]["sum_gap"]``."""
    n = len(events["ts"])
    nkg = config["keygroups_per_op"]
    limit = config["limits"]["sum_gap"]
    bad = np.zeros(n, dtype=bool)
    misplaced = outputs.get("unknown", 0) + state.get("other", 0)
    o = outputs.get(SINK)
    if o is None:
        return {"lost": n, "misplaced": misplaced + 1, "sum_gap": 0.0,
                "state_gap": 0.0}, np.ones(n, dtype=bool)
    ref, codes, (order, starts, ends) = _rows(events)
    day = events["date_time"] // DAY_MS
    ts = o["ts"]
    ids = ts.astype(np.int64)
    ok = (ids == ts) & (ids >= 0) & (ids < n)
    misplaced += int((~ok).sum())
    ids = np.where(ok, ids, 0)
    cnt = np.bincount(ids[ok], minlength=n)
    lost = int(np.abs(cnt - 1).sum())
    bad |= cnt != 1
    auction = events["auction"][ids]
    wrong = (o["key"] != auction) | (o["auction"] != auction) | (o["day"] != day[ids])
    # Admission order per (auction, day): bid indices rise within a key,
    # in the order the engine emitted the rows.
    got_codes = _codes(o["auction"], o["day"])
    by_key = np.argsort(got_codes, kind="stable")
    k, i = got_codes[by_key], ids[by_key]
    brk = np.zeros(len(ids), dtype=bool)
    brk[by_key[1:]] = (k[1:] == k[:-1]) & (i[1:] <= i[:-1])
    wrong |= brk
    gap = np.zeros(len(ids))
    for name in AGGREGATES:
        gap = np.maximum(gap, _gap(o[name], ref[name][ids]))
    wrong &= ok
    misplaced += int(wrong.sum())
    sum_gap = float(gap[ok].max(initial=0.0))
    np.logical_or.at(bad, ids[ok], wrong[ok] | (gap[ok] > limit))
    # Final table: every reference (auction, day) once, in its key group.
    state_gap = 0.0
    tab = state.get(TABLE)
    if tab is None:
        return {"lost": lost, "misplaced": misplaced + 1, "sum_gap": sum_gap,
                "state_gap": state_gap}, bad
    base = config["operators"].index(SINK) * nkg
    misplaced += int((tab["kg"] != keygroup(tab["auction"], base, nkg)).sum())
    last = order[ends - 1] if n else np.empty(0, np.int64)
    ref_codes = codes[last]  # ascending: the groups' sorted codes
    got = _codes(tab["auction"], tab["day"])
    found = np.zeros(len(got), dtype=bool)
    pos = np.zeros(len(got), dtype=np.int64)
    if len(ref_codes) and len(got):
        pos = np.minimum(np.searchsorted(ref_codes, got), len(ref_codes) - 1)
        found = ref_codes[pos] == got
    uniq = len(np.unique(got[found]))
    missing, extra = len(ref_codes) - uniq, int(found.sum()) - uniq
    misplaced += int((~found).sum()) + missing + extra
    if found.any():
        j = last[pos[found]]
        for name in COLUMNS:
            g = _gap(tab[name][found], ref[name][j])
            state_gap = max(state_gap, float(g.max()))
    return (
        {
            "lost": lost,
            "misplaced": misplaced,
            "sum_gap": sum_gap,
            "state_gap": state_gap,
        },
        bad,
    )

"""Plain reference of Real Jobs 2 and 3, and the comparison that decides
``correct``.

The reference imports nothing of the engine.  From the generated events
(index order = admission order) it computes, with numpy in float64 and a
strictly left-to-right ``cumsum`` per key:

* **SumDelay** (both jobs): for every event, the running sum of
  ``dep_delay + arr_delay`` over its (airplane, year) in admission order,
  and each (airplane, year)'s final total;
* **RouteDelay** (Job 3): every event's delay and its route's running sum.
  The engine interleaves one route's events from different ExtractDelay
  instances in an order of its choosing, so the reference sums each route
  in the order the engine emitted it; the final totals are summed in
  admission order.

The comparison holds the engine to the configuration's guarantees:

* ``lost``: (event, sink) pairs that did not arrive exactly once (each
  event's ``ts`` is its index, so every output names its event);
* ``misplaced``: outputs whose key or fields are not their event's, SumDelay
  outputs out of admission order for their airplane, final-table entries
  missing, extra, or in a key group other than the one their key hashes to;
* ``sum_gap``: the widest gap of a running sum or an emitted delay from the
  reference, over the sum of the absolute values of its terms;
* ``state_gap``: the same for the final table values.

The key → key group map is the engine's published partition function
(a 32-bit murmur-style mix of the folded key, modulo the key groups),
written out here from its definition.
"""

from __future__ import annotations

import itertools

import numpy as np

#: Output fields of each sink, in the engine's record order.
SINK_FIELDS = {
    "sumdelay": ("plane", "sum"),
    "routedelay": ("origin", "dest", "sum", "delay"),
}
#: Keyed table of each stateful sink.
SINK_TABLE = {"sumdelay": "sums", "routedelay": "route_sums"}
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


def _route(events: dict, airports: int) -> np.ndarray:
    return events["origin"] * airports + events["dest"]


def _plane_year(events: dict) -> np.ndarray:
    return events["plane"] * 10_000 + events["year"]


def _stable_argsort(codes: np.ndarray) -> np.ndarray:
    """``np.argsort(codes, kind="stable")``; codes in ``[0, 2**32)`` take two
    16-bit radix passes (numpy sorts 16-bit keys by radix)."""
    if len(codes) and codes.min() >= 0 and codes.max() < 2**32:
        lo = (codes & 0xFFFF).astype(np.uint16)
        hi = (codes >> 16).astype(np.uint16)
        first = np.argsort(lo, kind="stable")
        return first[np.argsort(hi[first], kind="stable")]
    return np.argsort(codes, kind="stable")


def _groups(codes: np.ndarray):
    """Stable grouping: (order, starts, ends) of equal codes."""
    order = _stable_argsort(codes)
    sc = codes[order]
    starts = np.flatnonzero(np.concatenate(([True], sc[1:] != sc[:-1])))
    ends = np.append(starts[1:], len(sc))
    return order, starts, ends


#: Groups up to this long are summed side by side in one 2-D ``cumsum``.
_SHORT = 512


def _running(codes, addends, dtype=np.float64):
    """Per-event running sums of ``addends`` by code, in index order, and
    the running sums of their absolute values (the error scale).

    Strictly left to right within each code, as ``np.cumsum`` adds: short
    groups are laid out as the rows of one zero-padded array and summed
    along the rows; long ones take ``np.cumsum`` each."""
    n = len(codes)
    run = np.empty(n, dtype=dtype)
    scale = np.empty(n, dtype=np.float64)
    if n == 0:
        return run, scale
    order, starts, ends = _groups(codes)
    lens = ends - starts
    a = addends.astype(dtype)[order]
    absa = np.abs(addends.astype(np.float64))[order]
    rs, ss = np.empty_like(a), np.empty_like(absa)
    short = lens <= _SHORT
    if short.any():
        width = int(lens[short].max())
        row = np.repeat(np.cumsum(short) - 1, lens)
        col = np.arange(n) - np.repeat(starts, lens)
        pos = np.flatnonzero(np.repeat(short, lens))
        for src, dst, dt in ((a, rs, dtype), (absa, ss, np.float64)):
            grid = np.zeros((int(short.sum()), width), dtype=dt)
            grid[row[pos], col[pos]] = src[pos]
            np.cumsum(grid, axis=1, out=grid)
            dst[pos] = grid[row[pos], col[pos]]
    for s, e in zip(starts[~short].tolist(), ends[~short].tolist()):
        rs[s:e] = np.cumsum(a[s:e], dtype=dtype)
        ss[s:e] = np.cumsum(absa[s:e])
    run[order] = rs
    scale[order] = ss
    return run, scale


def _delay(events: dict, dtype=np.float64) -> np.ndarray:
    return events["dep_delay"].astype(dtype) + events["arr_delay"].astype(dtype)


# ---------------------------------------------------------------- parsing
def parse_outputs(sink_outputs: list, sinks: list) -> dict:
    """Engine sink tuples ``(key, record, ts)`` → per-sink column arrays
    (``pos``: each output's place in the engine's list).

    The sinks' records differ in arity, which tells them apart."""
    by_arity = {len(SINK_FIELDS[s]): s for s in sinks}
    n = len(sink_outputs)
    keys, recs, ts = zip(*sink_outputs) if n else ((), (), ())
    keys = np.fromiter(keys, dtype=np.int64, count=n)
    ts = np.fromiter(ts, dtype=np.float64, count=n)
    arity = np.fromiter(map(len, recs), dtype=np.int64, count=n)
    out = {}
    for width, sink in by_arity.items():
        idx = np.flatnonzero(arity == width)
        mine = recs if len(idx) == n else [recs[i] for i in idx.tolist()]
        rows = np.fromiter(
            itertools.chain.from_iterable(mine), dtype=np.float64, count=len(idx) * width
        ).reshape(len(idx), width)
        cols = {"key": keys[idx], "ts": ts[idx], "pos": idx}
        for j, name in enumerate(SINK_FIELDS[sink]):
            cols[name] = rows[:, j]
        out[sink] = cols
    out["unknown"] = int(np.isin(arity, list(by_arity), invert=True).sum())
    return out


def parse_state(items) -> dict:
    """Engine store ``(key group, state dict)`` pairs → per-table arrays of
    (key group, key part 0, key part 1, value)."""
    acc = {t: ([], [], [], []) for t in SINK_TABLE.values()}
    other = 0
    for kg, st in items:
        for name, table in st.items():
            if name not in acc:
                other += 1
                continue
            kgs, k0, k1, vals = acc[name]
            for (a, b), v in table.items():
                kgs.append(kg)
                k0.append(a)
                k1.append(b)
                vals.append(v)
    out = {
        t: {
            "kg": np.asarray(c[0], dtype=np.int64),
            "k0": np.asarray(c[1], dtype=np.int64),
            "k1": np.asarray(c[2], dtype=np.int64),
            "value": np.asarray(c[3], dtype=np.float64),
        }
        for t, c in acc.items()
    }
    out["other"] = other
    return out


# ---------------------------------------------------------------- control
def control(config: dict, events: dict, dtype=np.float32) -> tuple[dict, dict]:
    """The reference put in the engine's place, computed in ``dtype``:
    outputs in admission order and the final tables, in the engine's shapes
    (the control of the comparison: float32 for the configured float64)."""
    n = len(events["ts"])
    delay = _delay(events, dtype)
    outputs, state = {"unknown": 0}, {"other": 0}
    for sink in config["sinks"]:
        if sink == "sumdelay":
            codes, key = _plane_year(events), events["plane"]
            rec = {"plane": events["plane"].astype(np.float64)}
            k0, k1 = events["plane"], events["year"]
        else:
            codes = key = _route(events, config["num_airports"])
            rec = {
                "origin": events["origin"].astype(np.float64),
                "dest": events["dest"].astype(np.float64),
                "delay": delay.astype(np.float64),
            }
            k0, k1 = events["origin"], events["dest"]
        run, _ = _running(codes, delay, dtype)
        rec.update(key=key, ts=np.arange(n, dtype=np.float64), sum=run.astype(np.float64))
        outputs[sink] = rec
        nkg = config["keygroups_per_op"]
        base = config["operators"].index(sink) * nkg
        order, starts, ends = _groups(codes)
        last = order[ends - 1] if n else np.empty(0, np.int64)
        state[SINK_TABLE[sink]] = {
            "kg": keygroup(key[last], base, nkg),
            "k0": k0[last],
            "k1": k1[last],
            "value": run[last].astype(np.float64),
        }
    return outputs, state


# ------------------------------------------------------------- comparison
def _gap(x: np.ndarray, ref: np.ndarray, scale: np.ndarray) -> np.ndarray:
    return np.abs(x - ref) / np.maximum(scale, np.finfo(np.float64).tiny)


def check(config: dict, events: dict, outputs: dict, state: dict) -> tuple[dict, np.ndarray]:
    """Compare the engine's outputs and final tables with the reference.

    Returns ``({number: value}, bad)`` where ``bad[i]`` marks event ``i``
    when one of its outputs is lost, duplicated, misplaced or off by more
    than ``config["limits"]["sum_gap"]``."""
    n = len(events["ts"])
    nkg = config["keygroups_per_op"]
    airports = config["num_airports"]
    limit = config["limits"]["sum_gap"]
    delay = _delay(events)
    bad = np.zeros(n, dtype=bool)
    lost = misplaced = 0
    sum_gap = state_gap = 0.0
    misplaced += outputs.get("unknown", 0) + state.get("other", 0)
    for sink in config["sinks"]:
        o = outputs.get(sink)
        if o is None:
            lost += n
            bad[:] = True
            continue
        ts = o["ts"]
        ids = ts.astype(np.int64)
        ok = (ids == ts) & (ids >= 0) & (ids < n)
        misplaced += int((~ok).sum())
        ids = np.where(ok, ids, 0)
        cnt = np.bincount(ids[ok], minlength=n)
        lost += int(np.abs(cnt - 1).sum())
        bad |= cnt != 1
        if sink == "sumdelay":
            wrong = (o["key"] != events["plane"][ids]) | (o["plane"] != events["plane"][ids])
            # Admission order per airplane: event indices rise within a key.
            order = np.argsort(o["key"], kind="stable")
            k, i = o["key"][order], ids[order]
            brk = np.zeros(len(ids), dtype=bool)
            brk[order[1:]] = (k[1:] == k[:-1]) & (i[1:] <= i[:-1])
            wrong |= brk
            ref_run, ref_scale = _running(_plane_year(events), delay)
            gap = _gap(o["sum"], ref_run[ids], ref_scale[ids])
        else:
            route = _route(events, airports)
            wrong = (
                (o["key"] != route[ids])
                | (o["origin"] != events["origin"][ids])
                | (o["dest"] != events["dest"][ids])
            )
            absd = np.abs(events["dep_delay"]) + np.abs(events["arr_delay"])
            gap = _gap(o["delay"], delay[ids], absd[ids])
            # The route's running sums in the order the engine emitted them.
            ref, scale = _running(o["key"], delay[ids])
            gap = np.maximum(gap, _gap(o["sum"], ref, scale))
        wrong &= ok
        misplaced += int(wrong.sum())
        if len(gap):
            sum_gap = max(sum_gap, float(gap[ok].max(initial=0.0)))
        np.logical_or.at(bad, ids[ok], wrong[ok] | (gap[ok] > limit))
        # Final table: every reference key once, in its key's key group.
        tab = state.get(SINK_TABLE[sink])
        if tab is None:
            misplaced += 1
            continue
        if sink == "sumdelay":
            codes, key = _plane_year(events), events["plane"]
            got_codes, got_key = tab["k0"] * 10_000 + tab["k1"], tab["k0"]
        else:
            codes = key = _route(events, airports)
            got_codes = got_key = tab["k0"] * airports + tab["k1"]
        base = config["operators"].index(sink) * nkg
        misplaced += int((tab["kg"] != keygroup(got_key, base, nkg)).sum())
        if sink != "sumdelay":  # admission-order totals of the routes
            ref_run, ref_scale = _running(codes, delay)
        order, starts, ends = _groups(codes)
        last = order[ends - 1] if n else np.empty(0, np.int64)
        ref_codes = codes[last]
        pos = np.searchsorted(ref_codes, got_codes)
        pos = np.minimum(pos, max(len(ref_codes) - 1, 0))
        found = (len(ref_codes) > 0) & (ref_codes[pos] == got_codes) if len(got_codes) else np.zeros(0, bool)
        uniq = len(np.unique(got_codes[found])) if len(got_codes) else 0
        misplaced += int((~found).sum()) + (len(ref_codes) - uniq) + (int(found.sum()) - uniq)
        if found.any():
            j = last[pos[found]]
            g = _gap(tab["value"][found], ref_run[j], ref_scale[j])
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

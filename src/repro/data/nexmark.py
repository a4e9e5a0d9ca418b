"""NEXmark q17, "Auction Statistics Report", as an engine topology.

The query, as the Flink Nexmark suite has it (github.com/nexmark/nexmark,
``queries/q17.sql``)::

    SELECT auction, DATE_FORMAT(dateTime, 'yyyy-MM-dd') AS day,
           count(*) AS total_bids,
           count(*) FILTER (WHERE price < 10000) AS rank1_bids,
           count(*) FILTER (WHERE price >= 10000 AND price < 1000000)
               AS rank2_bids,
           count(*) FILTER (WHERE price >= 1000000) AS rank3_bids,
           min(price) AS min_price, max(price) AS max_price,
           avg(price) AS avg_price, sum(price) AS sum_price
    FROM bid
    GROUP BY auction, DATE_FORMAT(dateTime, 'yyyy-MM-dd');

An unbounded GROUP BY: every bid updates its (auction, day) row and emits the
row as it now stands, so the sink sees exactly one result per bid.  Topology
``bid → q17``: the source pushes bids keyed by auction, and ``q17`` keeps one
keyed table whose entries are (auction, day) rows of seven int64 columns —
count, the three counts-if, min, max and sum — under the combines sum, sum,
sum, sum, min, max, sum (:data:`Q17_COMBINE`).  Its code is ``auction <<
DAY_BITS | day``, so all of an auction's days live in its key group.

Bids keep NEXmark's fields as int64 columns (:data:`BID_SCHEMA`): ``channel``
and ``url`` are integer codes for the strings, ``date_time`` is milliseconds
since the epoch in UTC, and ``day`` is ``date_time // DAY_MS``.  The output
record is (auction, day, total_bids, rank1_bids, rank2_bids, rank3_bids,
min_price, max_price, avg_price, sum_price), with ``avg_price = sum_price //
total_bids``: Flink's AVG over BIGINT truncates, and floor division agrees
with truncation on the positive prices bids carry.  All arithmetic is exact
int64; the per-run ``fn`` is the semantic oracle, and ``fn_seg`` and
``fn_jit`` match it exactly.

:class:`BidRules` holds the NEXmark generator's rules for making a bid
(Apache Beam's ``nexmark`` generator with its defaults), and :func:`bid_stream`
is a small seeded bid stream after them, for tests.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np

from repro.data.synthetic import StreamSpec
from repro.engine.topology import (
    OperatorSpec,
    Schema,
    StateField,
    StateSchema,
    Topology,
)

BID_SCHEMA = Schema.record(
    [
        ("auction", "i8"),
        ("bidder", "i8"),
        ("price", "i8"),
        ("channel", "i8"),
        ("url", "i8"),
        ("date_time", "i8"),
    ]
)
Q17_FIELDS = (
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
Q17_SCHEMA = Schema.record([(name, "i8") for name in Q17_FIELDS])
#: The table's columns: count, the three counts-if, min, max and sum of price.
Q17_COMBINE = ("sum", "sum", "sum", "sum", "min", "max", "sum")

DAY_MS = 86_400_000
#: Bits of the day in a table code: days below 2**20 (until the year 4840),
#: auction ids below 2**43.
DAY_BITS = 20
#: q17's price bands, in cents: rank 1 below 10,000, rank 3 from 1,000,000.
RANK1_BELOW = 10_000
RANK3_FROM = 1_000_000


def _q17_encode(key: tuple) -> int:
    return (int(key[0]) << DAY_BITS) | int(key[1])


def _q17_decode(code: int) -> tuple:
    return (code >> DAY_BITS, code & ((1 << DAY_BITS) - 1))


#: Rows q17's table is expected to reach: every (auction, day) stays (no
#: state TTL), and a 30 s run of the q17.steady cell on a TPU v5e closes
#: at about 303,000; the runtime compiles ahead for up to twice this many
#: slots.
EXPECTED_ROWS = 1 << 19


def q17_state(expected_rows: int = EXPECTED_ROWS) -> StateSchema:
    """q17's one keyed table of seven int64 columns."""
    return StateSchema(
        (
            StateField(
                "q17",
                "table",
                dtype=np.int64,
                py=int,
                key_encode=_q17_encode,
                key_decode=_q17_decode,
                combine=Q17_COMBINE,
                expected_entries=expected_rows,
            ),
        )
    )


# --------------------------------------------------------------------------
# Per-run oracle.
# --------------------------------------------------------------------------


def _fold(row, price: int) -> tuple:
    """One bid folded into its (auction, day) row."""
    r1 = int(price < RANK1_BELOW)
    r3 = int(price >= RANK3_FROM)
    r2 = 1 - r1 - r3
    if row is None:
        return (1, r1, r2, r3, price, price, price)
    n, c1, c2, c3, lo, hi, s = row
    return (n + 1, c1 + r1, c2 + r2, c3 + r3, min(lo, price), max(hi, price), s + price)


def _q17(state, keys, values, ts):
    table = state.setdefault("q17", {})
    out = []
    for (auction, _, price, _, _, date_time), t in zip(values.tolist(), ts):
        day = date_time // DAY_MS
        row = table[(auction, day)] = _fold(table.get((auction, day)), price)
        n, c1, c2, c3, lo, hi, s = row
        record = (auction, day, n, c1, c2, c3, lo, hi, s // n, s)
        out.append((auction, record, float(t)))
    return state, out


# --------------------------------------------------------------------------
# Segment-vectorized numpy tier.
# --------------------------------------------------------------------------

_NP_COMBINE = {"sum": np.add, "min": np.minimum, "max": np.maximum}


def _terms(price):
    """Each bid's term for each table column, (7, n) int64."""
    one = np.ones_like(price)
    r1 = (price < RANK1_BELOW).astype(np.int64)
    r3 = (price >= RANK3_FROM).astype(np.int64)
    return np.stack([one, r1, one - r1 - r3, r3, price, price, price])


def _combine_rows(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Column-wise combine of two (7, m) blocks of rows."""
    return np.stack([_NP_COMBINE[c](x, y) for c, x, y in zip(Q17_COMBINE, a, b)])


def _q17_seg(store, kgs, starts, ends, keys, values, ts):
    """The whole segment at once: one stable sort by table code, each
    group seeded with its table row, an exact segmented scan (doubling
    steps within groups), then the table rows written back group by group
    in first-occurrence order — the order the per-run loop inserts them."""
    typed = values.dtype.names is not None
    bids = values if typed else np.array(values.tolist(), dtype=BID_SCHEMA.value)
    auction = bids["auction"]
    day = bids["date_time"] // DAY_MS
    codes = (auction << DAY_BITS) | day
    n = len(codes)
    order = np.argsort(codes, kind="stable")
    sc = codes[order]
    head = np.concatenate(([True], sc[1:] != sc[:-1]))
    gstart = np.flatnonzero(head)
    gend = np.append(gstart[1:], n)
    first = order[gstart]
    slots = np.searchsorted(np.asarray(ends), first, side="right").tolist()
    gkeys = list(zip(auction[first].tolist(), day[first].tolist()))
    tables = [store[kgs[s]].setdefault("q17", {}) for s in slots]
    x = _terms(bids["price"])[:, order]
    rows = [tab.get(k) for tab, k in zip(tables, gkeys)]
    has = np.fromiter((r is not None for r in rows), dtype=bool, count=len(rows))
    if has.any():
        base = np.array([r for r in rows if r is not None], dtype=np.int64).T
        at = gstart[has]
        x[:, at] = _combine_rows(base, x[:, at])
    # Inclusive scan within groups: after the step of width d each position
    # holds the combine of its group's last 2d terms.
    pos = np.arange(n)
    lo = np.repeat(gstart, gend - gstart)
    d = 1
    while d < n:
        step = np.flatnonzero(pos - d >= lo)
        if not len(step):
            break
        x2 = x.copy()
        x2[:, step] = _combine_rows(x[:, step - d], x[:, step])
        x = x2
        d *= 2
    for g in np.argsort(first, kind="stable").tolist():
        tables[g][gkeys[g]] = tuple(x[:, gend[g] - 1].tolist())
    run = np.empty_like(x)
    run[:, order] = x
    cnt, c1, c2, c3, mn, mx, total = run
    cols = (auction, day, cnt, c1, c2, c3, mn, mx, total // cnt, total)
    if typed:
        out = np.empty(n, dtype=Q17_SCHEMA.value)
        for name, col in zip(Q17_FIELDS, cols):
            out[name] = col
    else:
        out = np.empty(n, dtype=object)
        out[:] = list(zip(*(c.tolist() for c in cols)))
    return (auction, out, ts), None


# --------------------------------------------------------------------------
# Compiled tier: one keyed table of seven columns, one sort per call.
# --------------------------------------------------------------------------


def floor_div(a, b):
    """``a // b`` of int64 arrays, ``b`` in ``[1, 2**31)`` and ``|a // b|``
    below ``2**31``, exactly, without an int64 division: XLA:TPU emulates
    int64 division with a long instruction sequence, which took most of
    the q17 program's compile time (45 s of a 131,072-slot program's
    compile for a v5e, 13 s without).  A float32 quotient is within a few
    hundred of the true one; one float32 step on the int64 remainder
    brings it within one, and a last comparison of the remainder settles
    it."""
    import jax.numpy as jnp

    fb = b.astype(jnp.float32)
    q = jnp.floor(a.astype(jnp.float32) / fb).astype(a.dtype)
    q = q + jnp.floor((a - q * b).astype(jnp.float32) / fb).astype(a.dtype)
    r = a - q * b
    return q - (r < 0) + (r >= b)


def _q17_jit(state, kgs, starts, ends, keys, values, ts):
    import jax.numpy as jnp

    from repro.engine import jitexec as jx

    auction, price = values["auction"], values["price"]
    day = floor_div(values["date_time"], jnp.full_like(price, DAY_MS))
    nb = auction.shape[0]
    codes = (auction << DAY_BITS) | day
    kg = kgs[jx.run_of_tuples(ends, nb)]
    valid = jx.tuple_valid(starts, ends, nb)
    one = jnp.ones_like(price)
    r1 = (price < RANK1_BELOW).astype(price.dtype)
    r3 = (price >= RANK3_FROM).astype(price.dtype)
    table, (cnt, c1, c2, c3, mn, mx, total) = jx.keyed_running_agg(
        state["q17"], codes, kg, (one, r1, one - r1 - r3, r3, price, price, price),
        valid, Q17_COMBINE,
    )
    avg = floor_div(total, jnp.maximum(cnt, 1))  # padding tuples count 0
    cols = (auction, day, cnt, c1, c2, c3, mn, mx, avg, total)
    return {"q17": table}, (auction, dict(zip(Q17_FIELDS, cols)), ts), None


def nexmark_q17(
    *, keygroups_per_op: int = 100, expected_rows: int = EXPECTED_ROWS
) -> Topology:
    """``bid → q17``: the bid source, keyed by auction, and the q17 sink,
    whose table compiles ahead for ``expected_rows`` rows (0: none)."""
    t = Topology()
    t.add_operator(
        OperatorSpec(
            "bid",
            None,
            num_keygroups=keygroups_per_op,
            is_source=True,
            schema=BID_SCHEMA,
        )
    )
    t.add_operator(
        OperatorSpec(
            "q17",
            _q17,
            num_keygroups=keygroups_per_op,
            is_sink=True,
            fn_seg=_q17_seg,
            fn_jit=_q17_jit,
            state_schema=q17_state(expected_rows),
            schema=BID_SCHEMA,
            out_schema=Q17_SCHEMA,
        )
    )
    t.connect("bid", "q17")
    return t


# --------------------------------------------------------------------------
# A seeded bid stream after the NEXmark generator's rules.
# --------------------------------------------------------------------------

FIRST_ID = 1000  # the first auction's and the first person's id
HOT_ROUND = 100  # hot auctions and bidders are multiples of 100
HOT_CHANNELS = 4
CHANNELS = 10_000


@dataclasses.dataclass(frozen=True)
class BidRules:
    """How the NEXmark generator (Apache Beam's ``NexmarkGenerator`` and its
    ``BidGenerator``, defaults) makes a bid from its index and its draws.

    * events cycle through ``persons``, ``auctions`` and ``bids`` (1, 3 and
      46 of every 50): bid ``i`` carries the event number it has in the full
      stream (:meth:`event_ids`), from which its auction and bidder ids
      follow;
    * auction: the hot auction (the last auction so far rounded down to a
      multiple of 100), or uniform over the last ``in_flight_auctions``
      auctions plus ``auction_lead`` not yet made; ids start at 1000;
    * bidder: the hot bidder (the last person rounded down to a multiple of
      100, plus 1), or uniform over the last ``active_people`` people plus
      ``person_lead``;
    * price: ``round(10 ** (decades * u) * price_lo)`` cents;
    * channel and url: integer codes, one of 4 hot channels (0-3) or one of
      10,000 others (4 + j); a url is coded as its channel.
    """

    persons: int = 1
    auctions: int = 3
    bids: int = 46
    in_flight_auctions: int = 100
    auction_lead: int = 10
    active_people: int = 1000
    person_lead: int = 10
    hot_auction_ratio: int = 2
    hot_bidder_ratio: int = 4
    hot_channel_ratio: int = 2
    price_lo: float = 100.0
    decades: float = 6.0

    def event_ids(self, i: np.ndarray) -> np.ndarray:
        """Event number of the ``i``-th bid in the person/auction/bid cycle."""
        cycle = self.persons + self.auctions + self.bids
        first_bid = self.persons + self.auctions
        return (i // self.bids) * cycle + first_bid + i % self.bids

    def draws(self, rng: np.random.Generator, n: int) -> tuple:
        """``n`` bids' random draws: hot flags and uniform fractions for the
        auction and the bidder, and the finished price and channel."""
        hot_auction = rng.integers(0, self.hot_auction_ratio, size=n) > 0
        auction_u = rng.random(n)
        hot_bidder = rng.integers(0, self.hot_bidder_ratio, size=n) > 0
        bidder_u = rng.random(n)
        price = np.round(
            10.0 ** (rng.random(n) * self.decades) * self.price_lo
        ).astype(np.int64)
        hot_channel = rng.integers(0, self.hot_channel_ratio, size=n) > 0
        channel = np.where(
            hot_channel,
            rng.integers(0, HOT_CHANNELS, size=n),
            HOT_CHANNELS + rng.integers(0, CHANNELS, size=n),
        )
        return hot_auction, auction_u, hot_bidder, bidder_u, price, channel

    def records(self, i: np.ndarray, draws: tuple, date_time: np.ndarray):
        """Bids ``i`` with their ``draws`` and times, as :data:`BID_SCHEMA`
        records."""
        hot_auction, auction_u, hot_bidder, bidder_u, price, channel = draws
        epoch = self.event_ids(i) // (self.persons + self.auctions + self.bids)
        # The last auction and person made before each bid (0-based).
        last_auction = epoch * self.auctions + self.auctions - 1
        first = np.maximum(last_auction - self.in_flight_auctions, 0)
        span = last_auction - first + 1 + self.auction_lead
        cold = first + (auction_u * span).astype(np.int64)
        hot = last_auction // HOT_ROUND * HOT_ROUND
        people = epoch * self.persons + self.persons  # made so far
        active = np.minimum(people, self.active_people)
        pick = (bidder_u * (active + self.person_lead)).astype(np.int64)
        cold_b = people - active + pick
        hot_b = (people - 1) // HOT_ROUND * HOT_ROUND + 1
        values = np.empty(len(i), dtype=BID_SCHEMA.value)
        values["auction"] = FIRST_ID + np.where(hot_auction, hot, cold)
        values["bidder"] = FIRST_ID + np.where(hot_bidder, hot_b, cold_b)
        values["price"] = price
        values["channel"] = channel
        values["url"] = channel
        values["date_time"] = date_time
        return values


def bid_stream(
    spec: StreamSpec | None = None,
    *,
    midnight_bid: int = 600,
    ms_per_bid: int = 60_000,
    rules: BidRules = BidRules(),
) -> Iterator[tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Bids ``(auctions, records, ts)`` per tick, ``spec.rate`` on average,
    after ``rules``; ``date_time`` advances ``ms_per_bid`` per bid and
    crosses a UTC midnight at bid ``midnight_bid``."""
    spec = spec or StreamSpec()
    rng = np.random.default_rng([spec.seed, 17])
    day0 = 20_000 * DAY_MS  # a UTC midnight (2024-10-04)
    nxt, tick = 0, 0
    while True:
        n = int(rng.poisson(spec.rate))
        i = np.arange(nxt, nxt + n, dtype=np.int64)
        values = rules.records(
            i, rules.draws(rng, n), day0 + (i - midnight_bid) * ms_per_bid
        )
        yield values["auction"].copy(), values, np.full(n, float(tick))
        nxt += n
        tick += 1

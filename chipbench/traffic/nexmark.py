"""Seeded NEXmark bid stream: the generator of the ``nexmark_bids`` mix.

Bids follow Apache Beam's NEXmark generator (``NexmarkGenerator`` and its
``BidGenerator``) under the mix's parameters, its defaults, by the rules of
``repro.data.nexmark.BidRules``: events cycle through ``proportions`` (1
person, 3 auctions, 46 bids of every 50), the stream holds the bids alone,
and bid ``i`` carries the event number it would have in the full stream,
from which its auction and bidder ids and its time follow; half of the bids
(``hot_auction_ratio`` 2) go to the hot auction, the rest to the last
``num_in_flight_auctions`` auctions plus ``auction_id_lead``; prices are
log-uniform over ``price_cents``.  ``date_time`` is milliseconds since the
epoch in UTC, ``1e6 / event_rate_per_s`` microseconds per event, from a base
chosen so that 2026-01-01 00:00 UTC falls exactly at bid ``midnight_bid``:
every bid before it is on the previous day.

Bid ``i`` is a pure function of ``(seed, i)`` and the mix.  A stream draws
:data:`POOL` blocks of :data:`BLOCK` bids' random draws when it is made
(set-up), each from a generator seeded with ``[seed, 1, block]``; later
blocks repeat blocks ``1 .. POOL - 1`` under their own indices.  Auction
and bidder ids and times are arithmetic on the index, so the key set keeps
growing, and no bid is drawn inside a measured window.
"""

from __future__ import annotations

import numpy as np

from repro.data.nexmark import DAY_MS, BidRules

BLOCK = 65_536
POOL = 32
#: 2026-01-01 00:00 UTC, in days since the epoch.
MIDNIGHT_DAY = 20_454


def warmup_sizes(mix: dict) -> list[int]:
    """Batch size of each warm-up tick: ``[[size, count], ...]`` expanded."""
    return [int(s) for s, c in mix["warmup_batches"] for _ in range(int(c))]


class Stream:
    """Bids of one run: ``batch(a, b)`` for the source, ``columns`` for the
    reference."""

    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.seed = int(seed) % 2**64
        p = mix["proportions"]
        lo, hi = mix["price_cents"]
        self.rules = BidRules(
            persons=p["person"],
            auctions=p["auction"],
            bids=p["bid"],
            in_flight_auctions=int(mix["num_in_flight_auctions"]),
            auction_lead=int(mix["auction_id_lead"]),
            active_people=int(mix["num_active_people"]),
            person_lead=int(mix["person_id_lead"]),
            hot_auction_ratio=int(mix["hot_auction_ratio"]),
            hot_bidder_ratio=int(mix["hot_bidder_ratio"]),
            hot_channel_ratio=int(mix["hot_channel_ratio"]),
            price_lo=float(lo),
            decades=float(np.log10(hi / lo)),
        )
        self.us_per_event = 1_000_000 // int(mix["event_rate_per_s"])
        self.base_us = MIDNIGHT_DAY * DAY_MS * 1000 - int(
            self.event_ids(np.array([mix["midnight_bid"]]))[0]
        ) * self.us_per_event
        self._pool = [
            self.rules.draws(np.random.default_rng([self.seed, 1, b]), BLOCK)
            for b in range(POOL)
        ]

    def event_ids(self, i: np.ndarray) -> np.ndarray:
        """Event number of bid ``i`` in the person/auction/bid cycle."""
        return self.rules.event_ids(i)

    def _records(self, a: int, draws: tuple) -> tuple:
        """Auction keys and bid records of bids ``a, a + 1, ...``."""
        i = np.arange(a, a + len(draws[0]), dtype=np.int64)
        date_time = (self.base_us + self.event_ids(i) * self.us_per_event) // 1000
        values = self.rules.records(i, draws, date_time)
        return values["auction"].copy(), values

    def batch(self, a: int, b: int) -> tuple:
        """``(keys, values, ts)`` of bids ``[a, b)`` for ``push_source``."""
        ts = np.arange(a, b, dtype=np.float64)
        parts = []
        while a < b:
            blk = a // BLOCK
            lo = a - blk * BLOCK
            hi = min(b - blk * BLOCK, BLOCK)
            src = blk if blk < POOL else 1 + (blk - 1) % (POOL - 1)
            parts.append(self._records(a, [d[lo:hi] for d in self._pool[src]]))
            a += hi - lo
        if len(parts) == 1:
            return (*parts[0], ts)
        return (*(np.concatenate(c) for c in zip(*parts)), ts)

    def columns(self, a: int, b: int) -> dict:
        """Every field of bids ``[a, b)`` as plain arrays."""
        _, values, ts = self.batch(a, b)
        out = {name: np.ascontiguousarray(values[name]) for name in values.dtype.names}
        out["ts"] = ts
        return out

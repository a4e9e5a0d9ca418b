"""Seeded airline event stream: the general generator of the airline mixes.

Keeps the columns of the repo's ``airline_stream`` (airplane, origin, dest,
departure and arrival delay, year); its key distributions follow the
configuration's deployment:

* airplanes are drawn uniformly from the fleet: in the On-Time data every
  tail number flies a few legs a day, so flights per airplane are nearly
  even;
* routes are drawn from the ``routes_flown`` (origin, dest) pairs the
  deployment flies, a fixed set for every seed, with shares falling as a
  Zipf law of exponent ``route_zipf_a`` over their rank; the seed decides
  which route holds which rank;
* every event carries its index ``i`` as ``ts``, from which its scheduled
  creation time follows (``i / rate`` after the warm-up, in an open loop).

Event ``i`` is a pure function of ``(seed, i)`` and the mix.  A stream
draws :data:`POOL` blocks of :data:`BLOCK` events when it is made (set-up),
each from a generator seeded with ``[seed, 1, block]``; later blocks repeat
blocks ``1 .. POOL - 1`` in turn under their own indices, so no event is
drawn inside a measured window, however many the window takes.

* The first ``routes_flown`` events enumerate every flown route once (in a
  seeded order) and the first ``num_airplanes`` events every airplane, so
  the warm-up fills both keyed tables to their full size.
* ``hot_set_move_s``: in an open loop, every that many seconds of event
  time the rank -> route mapping is redrawn from the seed, moving the hot
  routes.  Otherwise one seeded mapping holds throughout.
"""

from __future__ import annotations

import numpy as np

BLOCK = 65_536
POOL = 32
#: The year of the events' ``year`` column: a run's stream is shorter than
#: one year of the On-Time data (about 7 M flights).
YEAR = 2008

#: The airline source's record layout (the engine's ``AIRLINE_DTYPE``).
AIRLINE_DTYPE = np.dtype(
    [
        ("plane", "i8"),
        ("origin", "i8"),
        ("dest", "i8"),
        ("dep_delay", "f8"),
        ("arr_delay", "f8"),
        ("year", "i8"),
    ]
)


def _seed(seed: int) -> int:
    return int(seed) % 2**64


def warmup_sizes(mix: dict) -> list[int]:
    """Batch size of each warm-up tick: ``[[size, count], ...]`` expanded."""
    return [int(s) for s, c in mix["warmup_batches"] for _ in range(int(c))]


def flown_routes(airports: int, n: int) -> np.ndarray:
    """Route codes ``origin * airports + dest`` of the ``n`` routes flown:
    distinct ordered pairs of distinct airports, the same for every seed."""
    pick = np.random.default_rng([airports, n]).choice(
        airports * (airports - 1), size=n, replace=False
    )
    origin = pick // (airports - 1)
    dest = (origin + 1 + pick % (airports - 1)) % airports
    return np.sort(origin * airports + dest)


class Stream:
    """Events of one run: ``batch(a, b)`` for the source, ``columns`` for
    the reference."""

    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.seed = _seed(seed)
        self.planes = int(config["num_airplanes"])
        self.airports = int(config["num_airports"])
        self.flown = flown_routes(self.airports, int(config["routes_flown"]))
        self.routes = len(self.flown)
        w = np.arange(1, self.routes + 1, dtype=np.float64) ** -float(
            config["route_zipf_a"]
        )
        self._cdf = np.cumsum(w / w.sum())
        self._cdf[-1] = 1.0
        self.warmup = sum(warmup_sizes(mix))
        move = mix.get("hot_set_move_s")
        self.events_per_epoch = (
            int(round(float(mix["rate_per_s"]) * float(move)))
            if move and mix["loop"] == "open"
            else 0
        )
        self._cover = np.random.default_rng([self.seed, 2]).permutation(self.routes)
        self._perms: dict[int, np.ndarray] = {}
        self._pool = [self._draw(b) for b in range(POOL)]

    # ----------------------------------------------------------- columns
    def _perm(self, epoch: int) -> np.ndarray:
        """Rank -> route index in ``epoch``."""
        p = self._perms.get(epoch)
        if p is None:
            p = np.random.default_rng([self.seed, 3, epoch]).permutation(
                self.routes
            )
            self._perms[epoch] = p
        return p

    def _set_routes(self, values, rank: np.ndarray, epoch: int) -> None:
        code = self.flown[self._perm(epoch)[rank]]
        values["origin"] = code // self.airports
        values["dest"] = code % self.airports

    def _draw(self, b: int) -> tuple:
        """Block ``b``'s route ranks, airplanes and records (routes of the
        first epoch)."""
        rng = np.random.default_rng([self.seed, 1, b])
        idx = np.arange(b * BLOCK, (b + 1) * BLOCK, dtype=np.int64)
        rank = np.searchsorted(self._cdf, rng.random(BLOCK), side="right")
        rank = np.minimum(rank, self.routes - 1)
        plane = rng.integers(0, self.planes, size=BLOCK)
        dep = np.maximum(rng.normal(8.0, 20.0, size=BLOCK), -10.0)
        arr = np.maximum(rng.normal(6.0, 25.0, size=BLOCK), -20.0)
        cover = idx < self.routes
        rank[cover] = self._cover[idx[cover]]
        first = idx < self.planes
        plane[first] = idx[first]
        values = np.empty(BLOCK, dtype=AIRLINE_DTYPE)
        values["plane"] = plane
        self._set_routes(values, rank, 0)
        values["dep_delay"] = dep
        values["arr_delay"] = arr
        values["year"] = YEAR
        return rank, plane, values

    def _moved(self, rank, plane, values, a: int) -> tuple:
        """Records of events ``a, a + 1, ...`` with their epochs' routes."""
        idx = np.arange(a, a + len(rank), dtype=np.int64)
        epoch = np.maximum(idx - self.warmup, 0) // self.events_per_epoch
        if not epoch.any():
            return plane, values
        values = values.copy()
        for e in np.unique(epoch).tolist():
            m = epoch == e
            sub = values[m]
            self._set_routes(sub, rank[m], e)
            values[m] = sub
        return plane, values

    def batch(self, a: int, b: int) -> tuple:
        """``(keys, values, ts)`` of events ``[a, b)`` for ``push_source``."""
        ts = np.arange(a, b, dtype=np.float64)
        parts = []
        while a < b:
            blk = a // BLOCK
            lo = a - blk * BLOCK
            hi = min(b - blk * BLOCK, BLOCK)
            src = blk if blk < POOL else 1 + (blk - 1) % (POOL - 1)
            rank, plane, values = self._pool[src]
            part = plane[lo:hi], values[lo:hi]
            if self.events_per_epoch:
                part = self._moved(rank[lo:hi], *part, a)
            parts.append(part)
            a += hi - lo
        if len(parts) == 1:
            return (*parts[0], ts)
        return (*(np.concatenate(c) for c in zip(*parts)), ts)

    def columns(self, a: int, b: int) -> dict:
        """Every field of events ``[a, b)`` as plain arrays."""
        _, values, ts = self.batch(a, b)
        out = {name: np.ascontiguousarray(values[name]) for name in values.dtype.names}
        out["ts"] = ts
        return out

"""The airline generator: seeded, reproducible by range, its key
distributions those the configuration states."""

import numpy as np

from chipbench import registry

GEN = registry.generator("airline")
CONFIG = registry.config("job3_airline_20n400kg")
STEADY = registry.traffic("steady")
FLASH = dict(registry.traffic("flash"), rate_per_s=50_000)


def _bytes(stream, a, b):
    keys, values, ts = stream.batch(a, b)
    return keys.tobytes() + values.tobytes() + ts.tobytes()


def test_equal_seeds_give_identical_bytes_and_different_seeds_differ():
    big = 2**31 + 12_345
    one, two = GEN.Stream(CONFIG, STEADY, big), GEN.Stream(CONFIG, STEADY, big)
    other = GEN.Stream(CONFIG, STEADY, big + 1)
    n = (GEN.POOL + 3) * GEN.BLOCK + 17
    assert _bytes(one, 0, n) == _bytes(two, 0, n)
    assert _bytes(one, 0, n) != _bytes(other, 0, n)
    # A range is the same however it is cut into batches, and every event
    # carries its own index, past the pool's end too.
    cut = 2 * GEN.BLOCK - 5
    k1, v1, t1 = one.batch(0, cut)
    k2, v2, t2 = one.batch(cut, n)
    k, v, t = one.batch(0, n)
    assert (np.concatenate([k1, k2]) == k).all()
    assert np.concatenate([v1, v2]).tobytes() == v.tobytes()
    assert (np.concatenate([t1, t2]) == t).all() and (t == np.arange(n)).all()


def test_bounded_zipf_puts_no_excess_on_the_last_airplane():
    s = GEN.Stream(CONFIG, STEADY, 7)
    n = (GEN.POOL - 1) * GEN.BLOCK
    cols = s.columns(GEN.BLOCK, GEN.BLOCK + n)  # past the covering prefix
    # Airplanes: uniform over the fleet, the last one included.
    share = np.bincount(cols["plane"], minlength=s.planes) * s.planes / n
    assert len(share) == s.planes
    assert share.max() < 1.2 and share.min() > 0.8
    # Routes: only those flown, with the stated Zipf shares, the last
    # rank no heavier than the law gives it.
    code = cols["origin"] * s.airports + cols["dest"]
    assert np.isin(code, s.flown).all()
    counts = np.bincount(np.searchsorted(s.flown, code), minlength=s.routes)
    w = np.arange(1, s.routes + 1, dtype=float) ** -CONFIG["route_zipf_a"]
    p = w / w.sum()
    ranked = np.sort(counts)[::-1] / n
    assert abs(ranked[0] - p[0]) < 0.25 * p[0]
    last = s._perm(0)[-1]
    assert counts[last] / n < 2 * p[-1]


def test_the_route_set_is_the_deployments_not_the_seeds():
    a, b = GEN.Stream(CONFIG, STEADY, 1), GEN.Stream(CONFIG, STEADY, 2)
    assert (a.flown == b.flown).all() and len(a.flown) == CONFIG["routes_flown"]
    assert len(np.unique(a.flown)) == len(a.flown)
    assert (a.flown // a.airports != a.flown % a.airports).all()


def test_warmup_covers_every_route_and_airplane():
    s = GEN.Stream(CONFIG, STEADY, 3)
    cols = s.columns(0, max(s.routes, s.planes))
    routes = cols["origin"] * s.airports + cols["dest"]
    assert len(np.unique(routes[: s.routes])) == CONFIG["routes_flown"]
    assert (cols["origin"] != cols["dest"]).all()
    assert len(np.unique(cols["plane"][: s.planes])) == s.planes


def test_open_loop_moves_the_hot_set_every_epoch():
    s = GEN.Stream(CONFIG, FLASH, 11)
    epoch = s.events_per_epoch
    assert epoch == round(FLASH["rate_per_s"] * FLASH["hot_set_move_s"])
    a = s.warmup
    hot = []
    for k in range(3):
        c = s.columns(a + k * epoch, a + (k + 1) * epoch)
        hot.append(np.bincount(c["origin"] * s.airports + c["dest"]).argmax())
    assert len(set(hot)) == 3
    # Steady traffic keeps one hot set.
    t = GEN.Stream(CONFIG, STEADY, 11)
    assert t.events_per_epoch == 0

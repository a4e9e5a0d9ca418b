"""Sizes at which the benchmark's cells run on the CPU in the tests.

Only scale shrinks: the route code the engine computes fixes 300 airports.
"""

TINY = {
    "config": {
        "nodes": 4,
        "keygroups_per_op": 8,
        "num_airplanes": 60,
        "routes_flown": 200,
        "events_per_tick": 512,
        "ticks_per_period": 4,
    },
    "traffic": {
        "rate_per_s": 4_000,
        "hot_set_move_s": 0.25,
    },
}


def tiny(warmup):
    """TINY with the given warm-up batches."""
    out = {k: dict(v) for k, v in TINY.items()}
    out["traffic"]["warmup_batches"] = warmup
    return out

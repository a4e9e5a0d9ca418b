"""Reproduce the paper's Fig. 12 scenario end to end (Real Job 2).

Airline-delay pipeline where both operators partition on the same attribute:
starting from the worst allocation, ALBIC gradually collocates communicating
key groups, halving the system load (load index), while the MILP holds the
load distance low with ≤10 migrations per period.

    PYTHONPATH=src python examples/streaming_reconfiguration.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import AdaptationFramework, AlbicParams
from repro.data import airline_stream, real_job_2
from repro.data.synthetic import StreamSpec
from repro.engine import Controller, ControllerConfig, Engine, ExecutionConfig


def main() -> None:
    enable_compile_cache()
    nodes, kgs = 6, 30
    topo = real_job_2(keygroups_per_op=kgs)
    g = topo.num_keygroups

    # Anti-collocated initial allocation (the paper's starting point).
    alloc = np.zeros(g, dtype=np.int64)
    alloc[:kgs] = np.arange(kgs) % nodes
    alloc[kgs : 2 * kgs] = np.arange(kgs) % nodes
    alloc[2 * kgs :] = (np.arange(kgs) + nodes // 2) % nodes

    engine = Engine(
        topo,
        nodes,
        config=ExecutionConfig.typed(),
        initial_alloc=alloc,
        ser_cost=0.75,
        service_rate=2500.0,
    )
    stream = airline_stream(StreamSpec(rate=260.0, seed=1))

    def feeder(eng, tick):
        keys, values, ts = next(stream)
        eng.push_source("airline", keys, values, ts)

    controller = Controller(
        engine,
        AdaptationFramework(
            mode="albic",
            max_migrations=10,
            albic_params=AlbicParams(max_ld=10.0, time_limit=2.0),
        ),
        ControllerConfig(ticks_per_period=12),
        feeder=feeder,
    )

    print("Fig.12 reproduction — collocation ↑, load index ↓, ≤10 migrations/SPL")
    print("period | colloc% | load_idx | load_dist | migrations")
    for p in range(12):
        m = controller.period()
        bar = "#" * int(m.collocation_factor // 4)
        print(
            f"{p:6d} | {m.collocation_factor:7.1f} | {m.load_index:8.1f} |"
            f" {m.load_distance:9.2f} | {m.num_migrations:10d}  {bar}"
        )


if __name__ == "__main__":
    main()

"""Quickstart: integrative reconfiguration on a toy streaming job.

Builds a 3-operator word-count-style topology, runs it on 4 logical nodes
with a deliberately bad allocation, and lets the paper's controller (MILP +
ALBIC, Algorithm 1) rebalance and collocate it live.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import AdaptationFramework, AlbicParams
from repro.engine import Controller, ControllerConfig, Engine, ExecutionConfig
from repro.engine.topology import OperatorSpec, Topology


def tokenize(state, keys, values, ts):
    out = []
    for k, v, t in zip(keys, values, ts):
        for word in v["text"].split():
            out.append((word, {"word": word}, float(t)))
    return state, out


def count(state, keys, values, ts):
    counts = state.setdefault("counts", {})
    out = []
    for k, v, t in zip(keys, values, ts):
        counts[v["word"]] = counts.get(v["word"], 0) + 1
        out.append((v["word"], {"word": v["word"], "n": counts[v["word"]]}, float(t)))
    return state, out


def main() -> None:
    enable_compile_cache()
    topo = Topology()
    topo.add_operator(OperatorSpec("lines", None, num_keygroups=16, is_source=True))
    topo.add_operator(OperatorSpec("tokenize", tokenize, num_keygroups=16))
    topo.add_operator(
        OperatorSpec(
            "count",
            count,
            num_keygroups=16,
            key_by_value=lambda v: v["word"],
            is_sink=True,
        )
    )
    topo.connect("lines", "tokenize")
    topo.connect("tokenize", "count")

    engine = Engine(
        topo,
        num_nodes=4,
        config=ExecutionConfig.typed(),  # the default execution tier, spelled out
        ser_cost=0.5,
        service_rate=1500.0,
        seed=0,
    )

    rng = np.random.default_rng(0)
    vocab = ["stream", "engine", "balance", "migrate", "collocate", "scale"]

    def feeder(eng, tick):
        n = rng.poisson(120)
        keys = rng.integers(0, 1000, n)
        values = [
            {"text": " ".join(rng.choice(vocab, size=rng.integers(2, 6)))}
            for _ in range(n)
        ]
        eng.push_source("lines", keys, values, np.full(n, float(tick)))

    controller = Controller(
        engine,
        AdaptationFramework(
            mode="albic",
            max_migrations=8,
            albic_params=AlbicParams(max_ld=15.0, time_limit=1.0),
        ),
        ControllerConfig(ticks_per_period=10),
        feeder=feeder,
    )

    print("period | load_dist | colloc% | load_idx | migrations | p99 latency")
    for p in range(8):
        m = controller.period()
        print(
            f"{p:6d} | {m.load_distance:9.2f} | {m.collocation_factor:7.1f} |"
            f" {m.load_index:8.1f} | {m.num_migrations:10d} | {m.latency['p99']:.3f}"
        )
    top = sorted(
        (
            (w, c)
            for _, s in engine.store.items()
            for w, c in s.get("counts", {}).items()
        ),
        key=lambda x: -x[1],
    )[:3]
    print("top words:", top)


if __name__ == "__main__":
    main()

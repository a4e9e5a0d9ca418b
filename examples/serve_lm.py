"""Serve a small GLM4-family model with continuous batching: the controller
rebalances sequences (KV caches migrate between decode workers) and scales
the worker pool elastically.

    PYTHONPATH=src python examples/serve_lm.py [--ticks 120]
"""

import sys

from repro.compile_cache import enable_compile_cache
from repro.launch.serve import main as serve_main


def main() -> None:
    enable_compile_cache()
    extra = sys.argv[1:]
    sys.argv = [
        "serve",
        "--arch", "glm4_9b",
        "--ticks", "90",
        "--workers", "3",
        "--slots", "8",
        "--arrival-rate", "1.5",
        "--hetero", "0.5",
        *extra,
    ]
    serve_main()


if __name__ == "__main__":
    main()

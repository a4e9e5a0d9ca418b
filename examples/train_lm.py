"""Train a ~100M-parameter llama-family model for a few hundred steps with the
integrative controller managing the data plane (straggler mitigation via the
MILP), checkpointing every 50 steps.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

Equivalent to:
    python -m repro.launch.train --arch llama3_2_3b --d-model 640 --layers 10 \
        --vocab 32768 --steps 300 --batch 16 --seq-len 256 --hetero 0.6
"""

import sys

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main as train_main


def main() -> None:
    enable_compile_cache()
    extra = sys.argv[1:]
    sys.argv = [
        "train",
        "--arch", "llama3_2_3b",
        "--d-model", "640",
        "--layers", "10",
        "--vocab", "32768",
        "--steps", "300",
        "--batch", "16",
        "--seq-len", "256",
        "--num-shards", "16",
        "--num-workers", "4",
        "--hetero", "0.6",
        "--ckpt-dir", "checkpoints/train_100m",
        *extra,
    ]
    train_main()


if __name__ == "__main__":
    main()

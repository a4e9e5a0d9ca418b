"""Dispatching wrapper for the hash-partition/histogram kernel.

On TPU (or with ``force_pallas``) the Pallas kernel runs — compiled on the
chip, interpreted only when forced on another backend; elsewhere the jnp
oracle does.  Neither path falls back to the other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.keygroup_partition.keygroup_partition import (
    keygroup_partition_pallas,
)
from repro.kernels.keygroup_partition.ref import keygroup_partition_ref

_MIN_PAD = 1024


def fold_keys64(keys: np.ndarray) -> np.ndarray:
    """Fold raw 64-bit integer keys to the int32 lanes the TPU mix runs on.

    Identical to the first step of `repro.engine.topology.mix32`, so
    kernel(fold(keys)) == the engine's numpy key-group assignment.
    """
    u = np.asarray(keys).astype(np.uint64)
    folded = ((u ^ (u >> np.uint64(32))) & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return folded.view(np.int32)


def keygroup_partition(
    keys: np.ndarray,
    num_keygroups: int,
    *,
    base: int = 0,
    force_pallas: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Key-group id per key plus the per-key-group tuple histogram.

    ``keys`` are raw integer keys (any 64-bit range); ``base`` offsets the
    returned ids into the job's global key-group space, matching
    ``Topology.keygroups_of``.
    """
    if len(np.asarray(keys)) == 0:
        return np.empty(0, dtype=np.int64), np.zeros(num_keygroups, dtype=np.int64)
    folded = fold_keys64(keys)
    n = len(folded)
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or force_pallas:
        if not 0 < num_keygroups < 2**31:
            raise ValueError(
                f"keygroup_partition serves 1 <= num_keygroups < 2**31, "
                f"got {num_keygroups}"
            )
        # Power-of-two padding (valid=0, so the histogram ignores it): a
        # stream of varied batch sizes compiles a handful of programs.
        npad = max(_MIN_PAD, 1 << (n - 1).bit_length())
        keys32 = np.zeros(npad, dtype=np.int32)
        keys32[:n] = folded
        valid = np.zeros(npad, dtype=np.int32)
        valid[:n] = 1
        kg, hist = keygroup_partition_pallas(
            jnp.asarray(keys32),
            jnp.asarray(valid),
            num_keygroups=num_keygroups,
            interpret=not on_tpu,
        )
        kg = np.asarray(kg)[:n]
    else:
        kg, hist = keygroup_partition_ref(jnp.asarray(folded), num_keygroups)
    return np.asarray(kg, dtype=np.int64) + base, np.asarray(hist, dtype=np.int64)

"""Dispatching wrappers for the bucketed radix argsort.

Two entry points, matching the two places the engine sorts routing codes:

* :func:`bucket_argsort` — host-side (numpy in, numpy out).  On CPU this is
  numpy's radix argsort, the fastest stable sort at these ranges.  On TPU
  the compiled Pallas kernel runs instead, over inputs padded to a
  power-of-two length so a stream of varied batch sizes compiles a handful
  of programs, not one per size.

* :func:`bucket_argsort_jax` — traceable, for use **inside** a jit region
  (the fused superstep's routing step, where no host is reachable).  TPU →
  Pallas counting sort; other backends → XLA's stable argsort.

Both produce the permutation ``np.argsort(codes, kind="stable")`` would.
Neither falls back: a size the kernel cannot serve raises.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.radix_sort.radix_sort import bucket_argsort_pallas
from repro.kernels.radix_sort.ref import bucket_argsort_ref

_MIN_PAD = 512
_DIGIT_BITS = 8  # per radix pass: 256 buckets


def _check_buckets(num_buckets: int) -> None:
    # Codes, the overflow bucket and ranks all live in int32 lanes.
    if not 0 < num_buckets < 2**31 - 1:
        raise ValueError(
            f"bucket_argsort serves 1 <= num_buckets < 2**31 - 1, "
            f"got {num_buckets}"
        )


def bucket_argsort(
    codes: np.ndarray,
    num_buckets: int,
    *,
    force_pallas: bool = False,
) -> np.ndarray:
    """Stable argsort of host codes in ``[0, num_buckets)`` → int64 order."""
    codes = np.asarray(codes)
    if codes.size == 0:
        return np.empty(0, dtype=np.int64)
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or force_pallas:
        _check_buckets(num_buckets)
        n = codes.size
        npad = max(_MIN_PAD, 1 << (n - 1).bit_length())
        # Padding takes the kernel's overflow code, so it sorts after every
        # real code and the first n entries are the permutation.
        padded = np.full(npad, num_buckets, dtype=np.int32)
        padded[:n] = codes
        order = bucket_argsort_pallas(
            jnp.asarray(padded),
            num_buckets=num_buckets,
            interpret=not on_tpu,
        )
        return np.asarray(order)[:n].astype(np.int64)
    return bucket_argsort_ref(codes).astype(np.int64)


def radix_argsort_jax(codes: jax.Array) -> jax.Array:
    """Traceable stable argsort of non-negative int64 codes.

    On TPU: eight least-significant-digit passes of the bucketed counting
    kernel, 8 bits each, cover the 63 value bits — XLA:TPU's comparison
    sort of a 64K int64 vector takes about a minute to compile, the eight
    passes a few seconds.
    Elsewhere: XLA's stable sort.  Either way the permutation is
    ``np.argsort(codes, kind="stable")``.
    """
    if jax.default_backend() != "tpu":
        return jnp.argsort(codes, stable=True)
    mask = (1 << _DIGIT_BITS) - 1
    perm = jnp.arange(codes.shape[0], dtype=jnp.int32)
    for shift in range(0, 63, _DIGIT_BITS):
        digit = ((codes[perm] >> shift) & mask).astype(jnp.int32)
        perm = perm[bucket_argsort_pallas(digit, num_buckets=mask + 1)]
    return perm


def bucket_argsort_jax(codes: jax.Array, num_buckets: int) -> jax.Array:
    """Traceable stable argsort for codes in ``[0, num_buckets)``."""
    if jax.default_backend() == "tpu":
        _check_buckets(num_buckets)
        return bucket_argsort_pallas(
            codes.astype(jnp.int32), num_buckets=num_buckets
        )
    return jnp.argsort(codes, stable=True)

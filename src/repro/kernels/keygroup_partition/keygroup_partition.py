"""Hash-partition + histogram: folded int32 keys → key-group ids (Pallas TPU).

The engine's routing step (paper §3: hash-partitioning input keys into key
groups) as a kernel: for each key compute the 32-bit mix the CPU data plane
uses (``repro.engine.topology.mix32``) and its key-group id
``(mix & 0x7FFFFFFF) % num_keygroups``, and accumulate the per-key-group
tuple histogram the SPL statistics feed on (gLoad counting).

Layout: keys are reshaped to (rows, 1, block) int32 rows; the grid is
(key-group tile, row block).  Each step mixes one block on the VPU (uint32
multiply/xor/shift lanes) and adds its one-hot histogram contribution to one
int32 tile of at most :data:`KEYGROUP_TILE` key groups, which stays resident
across the row blocks — so fast memory per step is ``block × tile`` whatever
the key-group count.

The 64→32 fold of raw keys happens in the wrapper (ops.py): TPU lanes are
32-bit, and a 32-bit mix keeps the CPU and TPU paths bit-identical.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_MIX_C1 = 0x85EBCA6B
_MIX_C2 = 0xC2B2AE35
_MASK31 = 0x7FFFFFFF

# Block indices are int32 scalars: a Python 0 in an index map becomes an
# int64 constant once the jit tier enables x64, which Mosaic cannot lower.
_ZERO = np.int32(0)

#: Lane width of one histogram tile (a multiple of the 128-lane vreg).
KEYGROUP_TILE = 512


def _mix32_u32(h: jax.Array) -> jax.Array:
    """murmur3-style finisher on uint32 lanes (== topology.mix32)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_MIX_C1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_MIX_C2)
    h = h ^ (h >> 16)
    return h


def _kernel(keys_ref, valid_ref, kg_ref, hist_ref, *, nkg: int, tile: int):
    j = pl.program_id(0)
    i = pl.program_id(1)

    k = keys_ref[...]  # (1, block) int32
    h = _mix32_u32(jax.lax.bitcast_convert_type(k, jnp.uint32))
    # Non-negative after the 31-bit mask, so rem == the engine's mod.
    kg = jax.lax.rem(
        (h & jnp.uint32(_MASK31)).astype(jnp.int32), np.int32(nkg)
    )
    kg_ref[...] = kg

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    block = kg.shape[-1]
    onehot = (kg.reshape(block, 1) - j * tile) == jax.lax.broadcasted_iota(
        jnp.int32, (block, tile), 1
    )
    contrib = onehot.astype(jnp.int32) * valid_ref[...].reshape(block, 1)
    # dtype pinned: with jax x64 enabled (the jit tier flips it process-wide)
    # an int32 sum would promote its accumulator to int64 and fail the
    # store into the int32 histogram tile.
    hist_ref[...] += contrib.sum(axis=0, keepdims=True, dtype=jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("num_keygroups", "block", "interpret")
)
def keygroup_partition_pallas(
    keys32: jax.Array,  # (n,) int32 — already 64→32 folded
    valid: jax.Array,  # (n,) int32 — 1 for real keys, 0 for padding
    *,
    num_keygroups: int,
    block: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Return (key-group id per key (n,), histogram (num_keygroups,))."""
    n = keys32.shape[0]
    pad = (-n) % block
    if pad:
        keys32 = jnp.concatenate([keys32, jnp.zeros(pad, jnp.int32)])
        valid = jnp.concatenate([valid, jnp.zeros(pad, jnp.int32)])
    rows = (n + pad) // block
    tile = min(KEYGROUP_TILE, -(-num_keygroups // 128) * 128)
    nkg_pad = -(-num_keygroups // tile) * tile
    kernel = functools.partial(_kernel, nkg=num_keygroups, tile=tile)
    # Grid (key-group tile, row block): the histogram tile stays resident
    # while every row block accumulates into it; the key-group ids are
    # rewritten (identically) once per tile.
    row_spec = pl.BlockSpec((None, 1, block), lambda j, i: (i, _ZERO, _ZERO))
    kg, hist = pl.pallas_call(
        kernel,
        grid=(nkg_pad // tile, rows),
        in_specs=[row_spec, row_spec],
        out_specs=[
            row_spec,
            pl.BlockSpec((1, tile), lambda j, i: (_ZERO, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, 1, block), jnp.int32),
            jax.ShapeDtypeStruct((1, nkg_pad), jnp.int32),
        ],
        interpret=interpret,
    )(keys32.reshape(rows, 1, block), valid.reshape(rows, 1, block))
    return kg.reshape(-1)[:n], hist[0, :num_keygroups]

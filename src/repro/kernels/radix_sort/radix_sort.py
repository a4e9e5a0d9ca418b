"""Stable bucketed counting argsort for small-range int keys (Pallas TPU).

The engine's routing hot path sorts composite codes ``node*nkg + local_kg``
(bounded by ``num_nodes × num_keygroups``, 8,000 at the paper's 20-node ×
400-key-group scale) to group a routed batch into contiguous (node, key
group) runs.  This kernel restates numpy's stable radix argsort as two
Pallas passes over a ``(row block, bucket tile)`` grid:

1. **histogram** — each step counts one row block's codes into one tile of
   buckets, writing one ``(1, tile)`` slice of a ``(rows, buckets)`` table.
2. **rank** — after a cheap jnp prefix sum turns the table into per-block
   bucket base offsets, each element's destination rank is
   ``base[block, code]`` (gathered tile by tile through a one-hot) plus the
   number of *earlier equal codes in its block* — a pairwise
   ``block × block`` compare under a strictly-lower mask, so elements of
   equal code keep their input order.

Stability is structural: bases are accumulated in block order and the
within-block count runs in element order, so the permutation is
bit-identical to ``np.argsort(codes, kind="stable")`` at every shape.
Padding rides a dedicated overflow bucket (``num_buckets``) so it sinks to
the tail of the permutation without disturbing valid ranks.

Layout: codes live in ``(rows, 1, block)`` rows and the bucket axis is cut
into lane tiles of at most :data:`BUCKET_TILE`, so fast memory per step is
``block × tile`` (one-hot) plus ``block × block`` (pairwise), independent
of the bucket count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block indices are int32 scalars: a Python 0 in an index map becomes an
# int64 constant once the jit tier enables x64, which Mosaic cannot lower.
_ZERO = np.int32(0)

#: Lane width of one bucket tile (a multiple of the 128-lane vreg).
BUCKET_TILE = 512


def _tiles(nbk: int) -> tuple[int, int]:
    """(tile width, padded bucket count) for ``nbk`` buckets."""
    tile = min(BUCKET_TILE, -(-nbk // 128) * 128)
    return tile, -(-nbk // tile) * tile


def _hist_kernel(codes_ref, hist_ref, *, tile: int):
    j = pl.program_id(1)
    c = codes_ref[...]  # (1, block) int32
    block = c.shape[-1]
    onehot = (c.reshape(block, 1) - j * tile) == jax.lax.broadcasted_iota(
        jnp.int32, (block, tile), 1
    )
    # dtype pinned: the jit tier flips x64 process-wide; an un-pinned sum
    # would promote to int64 and fail the store into the int32 tile.
    hist_ref[...] = onehot.astype(jnp.int32).sum(
        axis=0, keepdims=True, dtype=jnp.int32
    )


def _rank_kernel(codes_ref, base_ref, ranks_ref, acc_ref, *, tile: int):
    j = pl.program_id(1)
    c = codes_ref[...]  # (1, block) int32
    block = c.shape[-1]

    @pl.when(j == 0)
    def _within():
        # Earlier equal codes in this block: eq[p, q] = (c[p] == c[q]) for
        # p < q, summed over p — the stability guarantee, without a scan.
        earlier = jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0
        ) < jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        eq = (c.reshape(block, 1) == c) & earlier
        acc_ref[...] = eq.astype(jnp.int32).sum(
            axis=0, keepdims=True, dtype=jnp.int32
        )

    # This tile's share of base[block, code]: exactly one tile holds each
    # code, so summing the one-hot selection over tiles gathers the base.
    hit = (
        jax.lax.broadcasted_iota(jnp.int32, (tile, block), 0) + j * tile == c
    )
    base_col = base_ref[...].reshape(tile, 1)
    acc_ref[...] += (hit.astype(jnp.int32) * base_col).sum(
        axis=0, keepdims=True, dtype=jnp.int32
    )

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        ranks_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("num_buckets", "block", "interpret")
)
def bucket_argsort_pallas(
    codes: jax.Array,  # (n,) int32 in [0, num_buckets)
    *,
    num_buckets: int,
    block: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Stable argsort of small-range codes; returns the (n,) int32 order.

    ``codes[order]`` is sorted ascending and equal codes keep input order —
    bit-identical to ``np.argsort(codes, kind="stable")``.
    """
    n = codes.shape[0]
    nbk = num_buckets + 1  # +1 overflow bucket for padding
    tile, nbk_pad = _tiles(nbk)
    pad = (-n) % block
    if pad:
        codes = jnp.concatenate(
            [codes, jnp.full(pad, num_buckets, jnp.int32)]
        )
    npad = n + pad
    rows = npad // block
    grid = (rows, nbk_pad // tile)
    codes3 = codes.reshape(rows, 1, block)
    # Under shard_map the outputs vary over the same mesh axes as the codes.
    vma = jax.typeof(codes3).vma
    row_spec = pl.BlockSpec((None, 1, block), lambda i, j: (i, _ZERO, _ZERO))
    tile_spec = pl.BlockSpec((None, 1, tile), lambda i, j: (i, _ZERO, j))

    hist = pl.pallas_call(
        functools.partial(_hist_kernel, tile=tile),
        grid=grid,
        in_specs=[row_spec],
        out_specs=tile_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 1, nbk_pad), jnp.int32, vma=vma),
        interpret=interpret,
    )(codes3)[:, 0, :]

    # Per-block bucket bases: global bucket start (exclusive cumsum over
    # buckets of the totals) + count of this bucket in earlier blocks
    # (exclusive cumsum over blocks).  (rows, buckets) ints — cheap on-device.
    totals = hist.sum(axis=0, dtype=jnp.int32)
    global_start = jnp.cumsum(totals, dtype=jnp.int32) - totals
    block_excl = jnp.cumsum(hist, axis=0, dtype=jnp.int32) - hist
    base = (global_start[None, :] + block_excl).reshape(rows, 1, nbk_pad)

    ranks = pl.pallas_call(
        functools.partial(_rank_kernel, tile=tile),
        grid=grid,
        in_specs=[row_spec, tile_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 1, block), jnp.int32, vma=vma),
        scratch_shapes=[pltpu.VMEM((1, block), jnp.int32)],
        interpret=interpret,
    )(codes3, base)

    ranks = ranks.reshape(-1)
    # Invert ranks → order.  Valid elements occupy ranks [0, n) (padding
    # sank into the overflow bucket), so the first n entries are the
    # stable argsort of the unpadded input.
    order = jnp.zeros(npad, jnp.int32).at[ranks].set(
        jnp.arange(npad, dtype=jnp.int32)
    )
    return order[:n]

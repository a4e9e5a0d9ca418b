"""The compiled operator tier: ``fn_jit`` bodies as jitted segment programs.

This module is the runtime behind ``OperatorSpec.fn_jit`` — the third
execution tier after the per-run ``fn`` and the segment-vectorized numpy
``fn_seg``.  A jit-tier operator's body is a *pure JAX function over column
arrays*; the runtime

* keeps the operator's declared :class:`~repro.engine.topology.StateSchema`
  in preallocated **device columns** — per-key-group scalar vectors and
  keyed-accumulator tables — instead of the python ``store`` dicts,
* compiles each body **once per (operator, padding bucket)**: segment tuple
  counts and run counts are padded to power-of-two buckets, so a long run
  with varied batch sizes compiles O(#buckets) programs, not O(#ticks)
  (``EngineMetrics.jit_compiles`` pins this),
* executes a node's whole drained contiguous slice in **one ``jax.jit``
  call** per (node, operator) with the state pytree donated (tables update
  in place), and
* when a mesh is configured, runs the same body as **one ``shard_map``
  shard per node-axis device**: the segment's runs are sharded across the
  axis (run → key group → disjoint state rows), per-shard state/output
  deltas are merged with ``psum``-of-masked selects, so the merged result
  is bit-identical to the unsharded call.

Coherence with the interpreted tiers: the python ``store`` dict and the
device columns hold the *same* state in two layouts.  Exactly one of them is
authoritative per key group at any time.  A jit call flips its key groups to
column-authoritative (pushing any dict-authoritative state in first); the
engine's per-run ``fn`` fallbacks (partial budgets, non-contiguous
migration rebuilds) and the migration codec call :meth:`JitRuntime.ensure_dict`
first, which materializes the columns back into the dict — including the
keyed tables' **insertion order** (each entry carries its insertion sequence
number) — so σ_k pickles, ``kg_state_bytes`` and the conformance state
comparison see exactly the dict the per-run oracle would have produced.

Float-tolerance policy: integer columns, single float operations and the
first two addends of every running sum are bit-exact on the CPU;
*multi-term float reductions* (the segmented scan inside
:func:`keyed_running_agg`) may diverge from the oracle's strict
left-to-right association in the last bits — the conformance harness
compares the jit configuration with a documented ``rtol=1e-9`` on floats
for exactly this reason (see tests/conformance.py and
docs/operator_authoring.md).  On a TPU float64 is emulated: no float
operation is bit-exact there and the exponent range is float32's, so every
float is held to the tolerance (``chip_smoke.py``); integers stay exact.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import math
import time
import weakref
from typing import NamedTuple, Optional

import numpy as np

import jax

# The jit tier carries the engine's float64/int64 payloads through XLA
# unchanged; without x64 every f8 column would silently truncate to f32 and
# no tolerance policy could be honest about it.  NOTE: this flips dtype
# semantics PROCESS-WIDE for all jax code — which is why the engine imports
# this module eagerly at ``Engine(use_fn_jit=True)`` construction (the
# explicit opt-in), never lazily mid-run, and why in-repo kernels pin their
# accumulator dtypes (see keygroup_partition's histogram sum).
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402  (after the x64 flag, deliberately)

from repro.engine.topology import StateField, Topology  # noqa: E402
from repro.engine.tracing import span, traced  # noqa: E402
from repro.kernels.radix_sort import radix_argsort_jax  # noqa: E402

# Sentinel for unused table slots and padding tuple codes.  Real codes must
# be < EMPTY_CODE (any keyed state built from finite attributes is).
EMPTY_CODE = np.iinfo(np.int64).max

_MIN_TUPLE_BUCKET = 16
_MIN_RUN_BUCKET = 4
_MIN_TABLE_CAP = 64
_NO_SPAN = contextlib.nullcontext()
# Device arrays read back to materialise one state field on the host.
_FIELD_FETCHES = {"scalar": 1, "vector": 2}


def _bucket(x: int, lo: int) -> int:
    b = lo
    while b < x:
        b <<= 1
    return b


def _table_cap(entries: int) -> int:
    """A keyed table's capacity: twice its entries, in a power of two."""
    return _bucket(2 * entries, _MIN_TABLE_CAP)


class TableState(NamedTuple):
    """One keyed-accumulator state field: a flat append-ordered table.

    Entries of *all* key groups share one capacity-``S`` slab (codes are
    globally unique — a code determines its key group — so no per-key-group
    partitioning is needed): ``codes``/``vals``/``owner`` hold the entries
    in insertion order (``cnt`` used, :data:`EMPTY_CODE` beyond; ``vals``
    is a tuple of arrays, one per value column), ``seq`` carries
    ``epoch << 32 | first_position`` — monotone in insertion order across
    calls, which is what reproduces the oracle dicts' insertion order
    without ranking new entries per key group — and ``perm`` is the code-sorted permutation of the slab, maintained
    *incrementally* by the merge in :func:`keyed_running_agg` (new codes
    arrive pre-sorted from the segment sort, so keeping the view sorted
    costs one int32 scatter of marks, a prefix count and one gather, never
    a table-sized sort).
    """

    codes: jax.Array  # (S,) int64, insertion order
    vals: tuple  # (S,) value dtype per column
    seq: jax.Array  # (S,) int64: epoch << 32 | first position
    owner: jax.Array  # (S,) int32 key group of each entry
    perm: jax.Array  # (S,) int32: slab indices in code-sorted order
    cnt: jax.Array  # () int32 used entries
    epoch: jax.Array  # () int64 call counter (seq high bits)


class VectorState(NamedTuple):
    """One bounded-window state field: a per-key-group ring of cells.

    ``data[k, :cnt[k]]`` holds key group ``k``'s window oldest-first — the
    exact list the per-run oracle keeps (``{name: [..]}``), so
    materialization is a slice, not a reconstruction.  Bodies shift the
    window with gathers (see the conformance suite's sliding-count-window
    port); under key-group-sharded ``shard_map`` both leaves carry the
    key-group leading axis and merge by the touched mask like scalars.
    """

    data: jax.Array  # (K, length) value dtype, oldest-first per key group
    cnt: jax.Array  # (K,) int32 occupancy


# --------------------------------------------------------------------------
# fn_jit authoring helpers (pure JAX; shape-polymorphic over padding and
# shard_map run-slices — validity always derives from the run bounds).
# --------------------------------------------------------------------------


def tuple_valid(starts: jax.Array, ends: jax.Array, nb: int) -> jax.Array:
    """Per-position validity of the (padded) tuple arrays.

    Runs tile a contiguous slice, and padding runs (``start == end`` at the
    real tuple count) are a suffix, so the valid positions are exactly
    ``[starts[0], ends[-1])`` — under shard_map run-sharding each shard's
    slice of the run arrays yields exactly its own tuple range.
    """
    pos = jnp.arange(nb)
    return (pos >= starts[0]) & (pos < ends[-1])


def run_of_tuples(ends: jax.Array, nb: int) -> jax.Array:
    """Run index per tuple position (meaningful where ``tuple_valid``)."""
    pos = jnp.arange(nb)
    idx = jnp.searchsorted(ends, pos, side="right")
    return jnp.minimum(idx, ends.shape[0] - 1)


def count_runs(col: jax.Array, kgs, starts, ends) -> jax.Array:
    """Scalar-counter update: add each run's length to its key group's cell.

    Padding runs carry ``kg == K`` (out of range → dropped) and zero length.
    """
    return col.at[kgs].add(
        (ends - starts).astype(col.dtype), mode="drop"
    )


#: Each table combine as an elementwise operation.
_COMBINE = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def segmented_scan(xs: tuple, head: jax.Array, combines: tuple) -> tuple:
    """Inclusive scans of the columns ``xs`` that restart at every ``head``,
    column ``j`` under its combine ``combines[j]`` (``"sum"``, ``"min"`` or
    ``"max"``).

    One log-depth parallel scan for all columns: ``jnp.cumsum`` lowers to
    a window reduction that XLA:TPU compiles for minutes on emulated
    float64, this compiles in seconds for every dtype.  Each value takes
    only its own segment's terms, so float error scales with the segment's
    magnitude, not the batch's; a sum's association differs from a
    left-to-right fold only in the last bits, and not at all for a
    segment's first two terms.  Integer columns, and min and max of any
    dtype, are exact.
    """
    ops = tuple(_COMBINE[c] for c in combines)

    def combine(a, b):
        ha, xa = a
        hb, xb = b
        return ha | hb, tuple(
            jnp.where(hb, y, op(x, y)) for op, x, y in zip(ops, xa, xb)
        )

    return jax.lax.associative_scan(combine, (head, tuple(xs)))[1]


def _prefix(x: jax.Array, combine: str) -> jax.Array:
    """Inclusive prefix sums (``"sum"``) or maxima (``"max"``, of
    non-negative values) of the 1-D int32 ``x``, in two levels of at most
    1,024: XLA:TPU compiles one window reduction over 1,048,576 elements
    for about 22 s, two levels in under one."""
    n = x.shape[0]
    b = math.gcd(n, 1024)
    scan = jax.lax.cumsum if combine == "sum" else jax.lax.cummax
    inner = scan(x.reshape(n // b, b), axis=1)
    carry = scan(inner[:, -1])
    carry = jnp.concatenate([jnp.zeros(1, x.dtype), carry[:-1]])
    op = jnp.add if combine == "sum" else jnp.maximum
    return op(inner, carry[:, None]).reshape(n)


def _wide(dtype):
    """The 32-bit dtype a narrower column rides in: exact both ways."""
    return jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else jnp.int32


def _words(x: jax.Array) -> list:
    """A column as uint32 words: one for up to 32 bits (a narrower column
    widened first), two (low, high) for 64."""
    if x.dtype.itemsize < 4:
        x = x.astype(_wide(x.dtype))
    if x.dtype.itemsize == 4:
        return [jax.lax.bitcast_convert_type(x, jnp.uint32)]
    u = jax.lax.bitcast_convert_type(x, jnp.uint64)
    return [u.astype(jnp.uint32), (u >> jnp.uint64(32)).astype(jnp.uint32)]


def _unwords(words, dtype) -> jax.Array:
    """The inverse of :func:`_words`, from one or two rows of words."""
    dtype = jnp.dtype(dtype)
    if len(words) == 1 and dtype.itemsize < 4:
        return jax.lax.bitcast_convert_type(words[0], _wide(dtype)).astype(dtype)
    if len(words) == 1:
        return jax.lax.bitcast_convert_type(words[0], dtype)
    lo, hi = (w.astype(jnp.uint64) for w in words)
    return jax.lax.bitcast_convert_type(lo | (hi << jnp.uint64(32)), dtype)


def keyed_running_agg(
    table: TableState,
    codes: jax.Array,
    kg: jax.Array,
    values: tuple,
    valid: jax.Array,
    combines: tuple,
    order: Optional[jax.Array] = None,
) -> tuple[TableState, tuple]:
    """Grouped running aggregates over one segment, against the keyed table.

    ``table.vals`` holds one column per entry of ``combines`` (a tuple of
    arrays); ``values[j]`` is each tuple's term for column ``j``.  For every
    tuple ``i``: looks up ``codes[i]`` in the flat table, combines its
    group's terms up to ``i`` into the table's values and returns, per
    column, the per-tuple running value; new codes are appended to the slab
    with ``seq = epoch << 32 | first_position`` — monotone in
    first-occurrence order, which is exactly the order the per-run oracle
    inserts them into its dicts.  Requirements: equal codes always map to
    the same key group (key the table by the operator's partition key), and
    real codes are non-negative and < 2^63 − 1.

    Cost, whatever the number of columns: ONE stable sort of the segment
    (:func:`radix_argsort_jax`), ONE lookup through the table's code-sorted
    view, ONE :func:`segmented_scan` and THREE scatters, all of int32 words
    (on a TPU a 64-bit scatter costs 13 to 19 times an int32 one, and a
    gather of one element per slot more than either at large capacities):
    ``inv``, the segment's sorted → tuple order map, which each running
    column then gathers through; the slab writes, every written column
    (codes, values, ``seq``, ``owner``) as rows of uint32 words of one
    (words, capacity) array, a 64-bit column as two rows; and the merge
    of the segment's pre-sorted new codes into the sorted view, their slab
    indices marked at their merged positions, an int32 prefix count of the
    marks and one gather of the old view (never a table-sized sort or
    search).  A new entry's first position comes from an int32 prefix
    maximum over the group heads.
    A ``"sum"`` column's group is seeded ``base + first term``
    (``0 + term`` for a new key, as the oracle's ``get(key, 0) + term``);
    a ``"min"``/``"max"`` column's only where the key is in the table.
    Float sums are the one place the jit tier may diverge from the
    oracle's left-to-right association (module docstring's tolerance
    policy); a group's first two sums associate exactly as the oracle's,
    so singleton groups (mostly-unique keys) stay bit-exact end to end.

    ``order`` is the *pre-sorted order handoff*: the caller may pass the
    stable argsort of ``where(valid, codes, EMPTY_CODE)`` computed outside
    the trace (on CPU, numpy's radix argsort via
    ``repro.kernels.radix_sort.bucket_argsort`` beats XLA's comparison
    sort), and the kernel skips its own sort.  The permutation must be
    exactly the stable argsort — stability defines it uniquely, so any
    conforming producer yields bit-identical results.

    Overflow: when the segment's new codes do not fit (``cnt + new >
    capacity``) the returned ``cnt`` reads ``cnt + new``, above the
    capacity, and the rest of the table is not valid: the jit wrappers
    keep the call's input state instead, so the caller can grow the table
    and run the segment again.
    """
    nb = codes.shape[0]
    cap = table.codes.shape[0]
    i32 = jnp.int32
    mcodes = jnp.where(valid, codes, EMPTY_CODE)
    if order is None:
        order = radix_argsort_jax(mcodes)  # stable: ties keep tuple order
    order = order.astype(i32)
    sc = mcodes[order]
    real = sc != EMPTY_CODE
    sk = jnp.where(real, kg[order], 0)
    head = jnp.concatenate([jnp.ones(1, bool), sc[1:] != sc[:-1]])
    # Lookup through the maintained code-sorted view.
    scodes = table.codes[table.perm]  # (cap,) sorted, EMPTY tail
    ins = jnp.searchsorted(scodes, sc).astype(i32)  # merge position
    pos = jnp.minimum(ins, cap - 1)
    fidx = table.perm[pos]  # candidate slab index
    has = (scodes[pos] == sc) & real
    # Within-group running values, each group seeded with its table value.
    seeded = []
    for col, x, c in zip(table.vals, values, combines):
        sa = jnp.where(real, x[order], jnp.zeros((), x.dtype))
        base = jnp.where(has, col[fidx], jnp.zeros((), col.dtype))
        if c == "sum":
            seeded.append(jnp.where(head, base + sa, sa))
        else:
            seeded.append(jnp.where(head & has, _COMBINE[c](base, sa), sa))
    running_sorted = segmented_scan(seeded, head, combines)
    iota = jnp.arange(nb, dtype=i32)
    inv = jnp.zeros(nb, i32).at[order].set(iota)  # tuple → sorted position
    running = tuple(r[inv] for r in running_sorted)
    # ---- table update ----------------------------------------------------
    headpos = _prefix(jnp.where(head, iota, 0), "max")  # each group's head
    tail = jnp.concatenate([head[1:], jnp.ones(1, bool)])
    newhead = head & real & ~has
    nc_in = _prefix(newhead.astype(i32), "sum")  # new codes up to here
    total_new = nc_in[-1]
    rank = nc_in - 1  # code-order rank among new codes (valid at newheads)
    dest = table.cnt.astype(i32) + rank  # slab append position
    # Slab index of each group (existing: the hit; new: the append slot),
    # the same at each of its positions, since only a head counts a new
    # code; a group's tail writes it.  Other positions point beyond the
    # capacity, and drop.
    widx = jnp.where(tail & real, jnp.where(has, fidx, dest), cap)
    # seq/owner change only for new entries: a hit rewrites its own.
    seq_new = (table.epoch << jnp.int64(32)) | order[headpos]
    old = (table.codes, *table.vals, table.seq, table.owner)
    upd = (sc, *running_sorted, jnp.where(has, table.seq[fidx], seq_new),
           jnp.where(has, table.owner[fidx], sk))
    # float64 columns, whose bits XLA:TPU cannot read, gather instead
    # through each slot's writer: its sorted position + 1 in the last row.
    words = [[] if c.dtype == jnp.float64 else _words(c) for c in old]
    slab = jnp.stack(
        [w for ws in words for w in ws] + [jnp.zeros(cap, jnp.uint32)]
    )
    rows = [w for c, x, ws in zip(old, upd, words) if ws
            for w in _words(x.astype(c.dtype))]
    slab = slab.at[:, widx].set(
        jnp.stack(rows + [(iota + 1).astype(jnp.uint32)]), mode="drop"
    )
    src = slab[-1].astype(i32) - 1  # -1: the slot was not written
    cols = []
    for col, x, ws in zip(old, upd, words):
        if ws:
            cols.append(_unwords(slab[:len(ws)], col.dtype))
            slab = slab[len(ws):]
        else:
            cols.append(jnp.where(src >= 0, x[jnp.maximum(src, 0)], col))
    codes2, *vals2, seq2, owner2 = cols
    # Merge the pre-sorted new codes into the sorted view: each new code's
    # slab index at its merged position; an unmarked position p below the
    # new count takes the old view's entry p − (marks up to p); beyond it
    # the EMPTY tail stays, ascending by slab index, so the entries the
    # append consumes are exactly the FIRST ``total_new`` EMPTY pointers.
    npos = jnp.where(newhead, ins + rank, cap)
    mark = jnp.full(cap, -1, i32).at[npos].set(dest, mode="drop")
    marked = mark >= 0
    p = jnp.arange(cap, dtype=i32)
    below = p < table.cnt.astype(i32) + total_new
    shift = jnp.where(below, _prefix(marked.astype(i32), "sum"), 0)
    perm2 = jnp.where(marked, mark, table.perm[p - shift])
    return (
        TableState(
            codes2,
            tuple(vals2),
            seq2,
            owner2,
            perm2,
            table.cnt + total_new.astype(table.cnt.dtype),
            table.epoch + 1,
        ),
        running,
    )


# --------------------------------------------------------------------------
# Compile caches.  Keyed by the fn_jit *object* — declare bodies at module
# level (or memoize the factory) so topology factories reuse one identity
# and every engine in the process shares the compiled programs.
# --------------------------------------------------------------------------

_JITTED: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _hold_on_overflow(state: dict, new: dict) -> dict:
    """Keep a call's whole state as it came in once any keyed table
    overflowed (its ``cnt`` above its capacity), so the call can run again
    on grown tables and apply once.  The counts pass through: they carry
    the overflow to the host, which puts them back."""
    tables = [v for v in new.values() if isinstance(v, TableState)]
    if not tables:
        return new
    over = jnp.any(
        jnp.stack([jnp.any(t.cnt > t.codes.shape[-1]) for t in tables])
    )

    def hold(old, nw):
        return jnp.where(over, old, nw)

    out = {}
    for name, nw in new.items():
        held = jax.tree_util.tree_map(hold, state[name], nw)
        out[name] = (
            held._replace(cnt=nw.cnt) if isinstance(nw, TableState) else held
        )
    return out


def _jitted_plain(fn):
    entry = _JITTED.setdefault(fn, {})
    if "plain" not in entry:

        @functools.wraps(fn)  # the device ops keep the body's name
        def call(state, *args):
            state2, outputs, out_counts = fn(state, *args)
            return _hold_on_overflow(state, state2), outputs, out_counts

        entry["plain"] = jax.jit(call, donate_argnums=0)
    return entry["plain"]


def _jitted_sharded(fn, mesh, axis):
    entry = _JITTED.setdefault(fn, {})
    key = ("shard", id(mesh), axis)
    if key not in entry:
        from jax.sharding import PartitionSpec as P

        def call(state, kgs, starts, ends, keys, values, ts):
            def shard(state_in, kgs_l, st_l, en_l, keys_r, values_r, ts_r):
                nb = keys_r.shape[0]
                state2, outputs, out_counts = fn(
                    state_in, kgs_l, st_l, en_l, keys_r, values_r, ts_r
                )
                if out_counts is not None:
                    raise ValueError(
                        "shard_map execution requires 1:1 (or output-free) "
                        "fn_jit bodies — out_counts must be None"
                    )
                leaves = jax.tree_util.tree_leaves(state_in)
                if leaves:
                    num_kg = leaves[0].shape[0]
                    touched = (
                        jnp.zeros(num_kg, bool).at[kgs_l].set(True, mode="drop")
                    )
                    t_any = (
                        jax.lax.psum(touched.astype(jnp.int32), axis) > 0
                    )

                    def merge(orig, new):
                        t = touched.reshape(
                            (num_kg,) + (1,) * (new.ndim - 1)
                        )
                        summed = jax.lax.psum(
                            jnp.where(t, new, jnp.zeros((), new.dtype)), axis
                        )
                        ta = t_any.reshape((num_kg,) + (1,) * (new.ndim - 1))
                        return jnp.where(ta, summed, orig)

                    state_m = jax.tree_util.tree_map(merge, state_in, state2)
                else:
                    state_m = state2
                if outputs is None:
                    return state_m, None
                ok = tuple_valid(st_l, en_l, nb)

                def omerge(o):
                    return jax.lax.psum(
                        jnp.where(ok, o, jnp.zeros((), o.dtype)), axis
                    )

                return state_m, jax.tree_util.tree_map(omerge, outputs)

            state_m, outputs = jax.shard_map(
                shard,
                mesh=mesh,
                in_specs=(P(), P(axis), P(axis), P(axis), P(), P(), P()),
                out_specs=P(),
            )(state, kgs, starts, ends, keys, values, ts)
            return state_m, outputs, None

        entry[key] = jax.jit(call)
    return entry[key]


def _jitted_sharded_tables(fn, mesh, axis, table_names):
    """Run-sharded execution for keyed-table operators.

    Tables are **key-group-sharded**: every :class:`TableState` leaf
    carries a leading shard axis ``(d, ...)`` and shard ``s`` owns exactly
    the key groups ``{k : k*d // nkg == s}``.  The host lays the run
    arrays out shard-major (each shard's runs tile a contiguous tuple
    block), so a shard updates only its own sub-table — no cross-shard
    merge is needed for tables, ownership *is* the merge.  Scalar columns
    and outputs merge exactly like :func:`_jitted_sharded` (psum of
    touched/valid-masked selects); a key group's runs land on one shard
    only, so the duplicate-key-group hazard of plain run-sharding cannot
    occur here.
    """
    entry = _JITTED.setdefault(fn, {})
    key = ("shard_tab", id(mesh), axis, table_names)
    if key not in entry:
        from jax.sharding import PartitionSpec as P

        def call(state, kgs, starts, ends, keys, values, ts):
            state_spec = {
                name: (
                    TableState(*([P(axis)] * 7))
                    if name in table_names
                    else P()
                )
                for name in state
            }

            def shard(state_in, kgs_l, st_l, en_l, keys_r, values_r, ts_r):
                nb = keys_r.shape[0]
                st_loc = {
                    name: (
                        jax.tree_util.tree_map(lambda x: x[0], v)
                        if name in table_names
                        else v
                    )
                    for name, v in state_in.items()
                }
                state2, outputs, out_counts = fn(
                    st_loc, kgs_l, st_l, en_l, keys_r, values_r, ts_r
                )
                if out_counts is not None:
                    raise ValueError(
                        "shard_map execution requires 1:1 (or output-free) "
                        "fn_jit bodies — out_counts must be None"
                    )
                touched = None
                t_any = None
                out_state = {}
                for name, new in state2.items():
                    if name in table_names:
                        out_state[name] = jax.tree_util.tree_map(
                            lambda x: x[None], new
                        )
                        continue
                    orig = state_in[name]
                    leaves_o = jax.tree_util.tree_leaves(orig)
                    num_kg = leaves_o[0].shape[0]
                    if touched is None:
                        touched = (
                            jnp.zeros(num_kg, bool)
                            .at[kgs_l]
                            .set(True, mode="drop")
                        )
                        t_any = (
                            jax.lax.psum(touched.astype(jnp.int32), axis) > 0
                        )

                    def merge(o, nw):
                        t = touched.reshape(
                            (num_kg,) + (1,) * (nw.ndim - 1)
                        )
                        summed = jax.lax.psum(
                            jnp.where(t, nw, jnp.zeros((), nw.dtype)), axis
                        )
                        ta = t_any.reshape((num_kg,) + (1,) * (nw.ndim - 1))
                        return jnp.where(ta, summed, o)

                    out_state[name] = jax.tree_util.tree_map(merge, orig, new)
                if outputs is None:
                    return out_state, None
                ok = tuple_valid(st_l, en_l, nb)

                def omerge(o):
                    return jax.lax.psum(
                        jnp.where(ok, o, jnp.zeros((), o.dtype)), axis
                    )

                return out_state, jax.tree_util.tree_map(omerge, outputs)

            state_m, outputs = jax.shard_map(
                shard,
                mesh=mesh,
                in_specs=(state_spec, P(axis), P(axis), P(axis), P(), P(), P()),
                out_specs=(state_spec, P()),
            )(state, kgs, starts, ends, keys, values, ts)
            return _hold_on_overflow(state, state_m), outputs, None

        entry[key] = jax.jit(call)
    return entry[key]


# --------------------------------------------------------------------------
# Per-operator runtime state.
# --------------------------------------------------------------------------


class _OpState:
    __slots__ = (
        "op",
        "spec",
        "base",
        "nkg",
        "fields",
        "has_tables",
        "has_vectors",
        "shards",
        "cols",
        "caps",
        "cnt_host",
        "col_auth",
        "value_names",
        "out_dtype",
        "out_names",
        "seen_keys",
        "shapes",
        "compiled",
    )

    def __init__(self, op: int, spec, base: int, shards: int = 0) -> None:
        self.op = op
        self.spec = spec
        self.base = base
        self.nkg = spec.num_keygroups
        self.fields: tuple[StateField, ...] = (
            spec.state_schema.fields if spec.state_schema is not None else ()
        )
        self.has_tables = any(f.kind == "table" for f in self.fields)
        self.has_vectors = any(f.kind == "vector" for f in self.fields)
        # > 0 ⇒ keyed tables live key-group-sharded: every TableState leaf
        # carries a leading (shards,) axis and cnt_host tracks per shard.
        self.shards = shards if self.has_tables else 0
        self.caps: dict[str, int] = {}
        self.cnt_host: dict[str, object] = {}
        self.col_auth = np.zeros(self.nkg, dtype=bool)
        cols = {}
        for f in self.fields:
            if f.kind == "scalar":
                cols[f.name] = jnp.full(self.nkg, f.init, dtype=f.dtype)
            elif f.kind == "vector":
                cols[f.name] = VectorState(
                    data=jnp.zeros((self.nkg, f.length), dtype=f.dtype),
                    cnt=jnp.zeros(self.nkg, dtype=jnp.int32),
                )
            else:
                cap = _MIN_TABLE_CAP
                self.caps[f.name] = cap
                if self.shards:
                    self.cnt_host[f.name] = np.zeros(self.shards, np.int64)
                    cols[f.name] = _stack_tables(
                        [_empty_table(cap, f)] * self.shards
                    )
                else:
                    self.cnt_host[f.name] = 0
                    cols[f.name] = _empty_table(cap, f)
        self.cols = cols
        self.value_names = (
            spec.schema.value.names if spec.schema is not None else None
        )
        out_schema = spec.out_schema
        self.out_dtype = None if out_schema is None else out_schema.value
        self.out_names = (
            None if out_schema is None else out_schema.value.names
        )
        self.seen_keys: set = set()  # (shape, capacities) launched
        # Each shape launched, at any capacity: its jitted function and its
        # arguments' abstract values (what a compile ahead lowers).
        self.shapes: dict = {}
        self.compiled: dict = {}  # (shape, capacities) compiled ahead

    def shard_of(self, lkgs: np.ndarray) -> np.ndarray:
        """Owning shard per local key group (monotone in the key group)."""
        return (np.asarray(lkgs, dtype=np.int64) * self.shards) // self.nkg


def _empty_table(cap: int, f: StateField) -> TableState:
    return TableState(
        codes=jnp.full(cap, EMPTY_CODE, dtype=jnp.int64),
        vals=tuple(jnp.zeros(cap, dtype=f.dtype) for _ in f.combines),
        seq=jnp.zeros(cap, dtype=jnp.int64),
        owner=jnp.zeros(cap, dtype=jnp.int32),
        perm=jnp.arange(cap, dtype=jnp.int32),
        cnt=jnp.zeros((), dtype=jnp.int32),
        epoch=jnp.ones((), dtype=jnp.int64),
    )


def _stack_tables(tables: list[TableState]) -> TableState:
    """Stack per-shard sub-tables along a new leading shard axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *tables)


def _host_vals(vals) -> np.ndarray:
    """A table's value columns on the host, stacked on a leading axis."""
    return np.stack([np.asarray(c) for c in vals])


def _dict_entries(store, ost: _OpState, f: StateField, lkgs) -> tuple:
    """The dict entries of key groups ``lkgs``, key group by key group in
    insertion order: their codes, their values as a (columns, entries)
    array and their owning key groups."""
    codes, rows, owners = [], [], []
    enc = f.key_encode
    for lk in lkgs:
        for key, val in store[ost.base + int(lk)].get(f.name, {}).items():
            codes.append(enc(key))
            rows.append(val)
            owners.append(lk)
    vals = np.asarray(rows, dtype=f.dtype).reshape(len(rows), len(f.combines))
    return codes, vals.T, owners


class JitRuntime:
    """Executes fn_jit operators over device state columns for one Engine."""

    def __init__(
        self,
        topology: Topology,
        store,
        metrics,
        kg_op: np.ndarray,
        *,
        mesh=None,
        mesh_axis: Optional[str] = None,
    ) -> None:
        self._store = store
        self._metrics = metrics
        self._kg_op = kg_op
        self._mesh = mesh
        if mesh is not None and mesh_axis is None:
            mesh_axis = mesh.axis_names[0]
        self._mesh_axis = mesh_axis
        if mesh is not None:
            d = int(mesh.shape[mesh_axis])
            if d & (d - 1):
                raise ValueError("jit mesh axis size must be a power of two")
        self.compile_seconds = 0.0
        shards = 0 if mesh is None else int(mesh.shape[mesh_axis])
        self._by_op: dict[int, _OpState] = {}
        for op, spec in enumerate(topology.operators):
            if spec.fn_jit is not None:
                self._by_op[op] = _OpState(
                    op, spec, topology.kg_base(op), shards=shards
                )

    # ------------------------------------------------------------ execution
    @traced("jit.call")
    def execute(self, op, kgs, starts, ends, keys, values, ts):
        """Run one contiguous (node, operator) segment through the jit tier.

        ``kgs`` are global key-group ids; ``starts``/``ends`` are bounds
        relative to the ``keys``/``values``/``ts`` slice.  Returns
        ``(outputs, out_counts)`` exactly like an ``fn_seg`` call.
        """
        ost = self._by_op[op]
        n = len(keys)
        r = len(kgs)
        # One launch per call (its result reads count as device_fetches).
        self._metrics.jit_host_syncs += 1
        if ost.has_vectors and len(set(kgs)) != r:
            # Window rings read pre-call occupancy per key group, so a call
            # with duplicate key groups (a budget-leftover segment
            # concatenated with a fresh one after a migration replay) would
            # shift from a stale ring.  Fall back to the numpy fn_seg tier
            # on the oracle dicts for this call.
            if ost.spec.fn_seg is None:
                raise ValueError(
                    f"operator {ost.spec.name!r} declares vector state but "
                    "no fn_seg fallback for duplicate-key-group segments"
                )
            for kg in set(int(k) for k in kgs):
                self.ensure_dict(kg)
            self._metrics.seg_calls += 1
            self._metrics.seg_tuples += n
            return ost.spec.fn_seg(
                self._store.raw(), list(kgs), list(starts), list(ends),
                keys, values, ts,
            )
        nb = _bucket(n, _MIN_TUPLE_BUCKET)
        rb = _bucket(r, _MIN_RUN_BUCKET)
        if self._mesh is not None:
            rb = _bucket(rb, int(self._mesh.shape[self._mesh_axis]))
        lkgs = np.asarray(kgs, dtype=np.int64) - ost.base
        st_arr = np.asarray(starts, dtype=np.int64)
        en_arr = np.asarray(ends, dtype=np.int64)
        if ost.fields:
            with span("jit.prepare"):
                self._prepare_state(ost, lkgs)
        if ost.shards:
            return self._execute_sharded_tables(
                ost, lkgs, st_arr, en_arr, keys, values, ts, n, r
            )
        with span("jit.prepare"):
            # Fresh padded buffers per call: jax zero-copies numpy on CPU,
            # so a reused scratch could be read after we overwrite it.
            kg_pad = np.full(rb, ost.nkg, dtype=np.int64)
            kg_pad[:r] = lkgs
            s_pad = np.full(rb, n, dtype=np.int64)
            s_pad[:r] = st_arr
            e_pad = np.full(rb, n, dtype=np.int64)
            e_pad[:r] = en_arr
            key_pad = np.zeros(nb, dtype=keys.dtype)
            key_pad[:n] = keys
            ts_pad = np.zeros(nb, dtype=np.float64)
            ts_pad[:n] = ts
            if ost.value_names is None:
                v_arg = np.zeros(nb, dtype=values.dtype)
                v_arg[:n] = values
            else:
                v_arg = {}
                for name in ost.value_names:
                    col = values[name]
                    pad = np.zeros(nb, dtype=col.dtype)
                    pad[:n] = col
                    v_arg[name] = pad
        fn = ost.spec.fn_jit
        use_shard = (
            self._mesh is not None
            and not ost.has_tables
            and len(set(kgs)) == r
        )
        if use_shard:
            # Plain run-sharding merges per-shard state by key-group
            # ownership — sound for per-key-group columns (table operators
            # take the key-group-sharded path above instead).  Duplicate key
            # groups in one call (budget-leftover segments concatenated with
            # a fresh batch) must not shard-split: two shards would both
            # update the kg from the same base and the merge would
            # double-count it — fall back to the plain call.
            jitted = _jitted_sharded(fn, self._mesh, self._mesh_axis)
        else:
            jitted = _jitted_plain(fn)
        _, outputs, out_counts = self._run(
            ost, (nb, rb, use_shard), jitted,
            (kg_pad, s_pad, e_pad, key_pad, v_arg, ts_pad),
        )
        ost.col_auth[lkgs] = True
        self._metrics.jit_calls += 1
        self._metrics.jit_tuples += n
        with span("jit.fetch"):
            if outputs is None:
                return None, None
            ok, ov, ot = outputs
            if out_counts is None:
                total, lens = n, None
            else:
                self._metrics.device_fetches += 1
                lens_arr = np.asarray(out_counts)[:r]
                total = int(lens_arr.sum())
                lens = lens_arr.tolist()
            ok_np = np.asarray(ok)[:total]
            ot_np = np.asarray(ot)[:total]
            ov_np = self._fetch_values(ost, ov, total)
            self._metrics.device_fetches += 2
        return (ok_np, ov_np, ot_np), lens

    def _launch(self, ost: _OpState, key, jitted, args):
        """Dispatch one jitted call: a program compiled ahead for ``key``
        runs as it is; a new ``key`` compiles (or loads from the cache)
        inside the dispatch, which is timed without waiting for the
        device."""
        with span("jit.launch"):
            first = key not in ost.seen_keys
            ost.seen_keys.add(key)
            program = ost.compiled.get(key)
            if program is not None:
                return program(ost.cols, *args)
            if not first:
                return jitted(ost.cols, *args)
            self._metrics.jit_compiles += 1
            with span("jit.compile"):
                t0 = time.perf_counter()
                result = jitted(ost.cols, *args)
                self.compile_seconds += time.perf_counter() - t0
            return result

    def _run(self, ost: _OpState, shape_key: tuple, jitted, args):
        """Launch one call and read its tables' counts back.  A call whose
        new keys overflowed a table left the state as it was: put the
        counts back, grow the table to twice what it would have held and
        launch the same arguments again (the first outputs are dropped).

        ``table_grow_s`` takes the time a capacity change costs here: a
        whole relaunch after an overflow, and the dispatch of a shape's
        first launch at a capacity it has not run at (with its compile or
        cache load, unless compiled ahead); ``_grow`` adds its own."""
        retry = False
        while True:
            caps = tuple(sorted(ost.caps.items()))
            new_cap = (
                (shape_key, caps) not in ost.seen_keys
                and shape_key in ost.shapes
            )
            t0 = time.perf_counter()
            with span("jit.retry") if retry else _NO_SPAN:
                result = self._launch(ost, (shape_key, caps), jitted, args)
                if new_cap and not retry:
                    self._metrics.table_grow_s += time.perf_counter() - t0
                ost.cols = result[0]
                with span("jit.fetch"):
                    over = self._fetch_counts(ost, result[0])
            if retry:
                self._metrics.table_grow_s += time.perf_counter() - t0
            if shape_key not in ost.shapes:
                ost.shapes[shape_key] = (jitted, jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args
                ))
            if not over:
                return result
            retry = True
            self._metrics.table_retries += 1
            self._metrics.jit_host_syncs += 1
            for f in ost.fields:
                if f.kind == "table":
                    t = ost.cols[f.name]
                    before = np.asarray(ost.cnt_host[f.name], dtype=np.int32)
                    ost.cols[f.name] = t._replace(
                        cnt=jax.device_put(before, t.cnt.sharding)
                    )
                    if f.name in over:
                        self._grow(ost, f, int(np.max(over[f.name])))

    def _fetch_counts(self, ost: _OpState, state_new: dict) -> dict:
        """Read each keyed table's entry count back (one fetch each, per
        shard when sharded).  Returns the counts of the tables the call
        overflowed (above their capacity), the host's counts left as
        before the call; else refreshes the counts and the table gauges
        (every capacity bump and dict push comes before a call, so the
        gauges see them)."""
        seen = {}
        for f in ost.fields:
            if f.kind == "table":
                cnt = state_new[f.name].cnt
                seen[f.name] = (
                    np.asarray(cnt, dtype=np.int64) if ost.shards else int(cnt)
                )
                self._metrics.device_fetches += 1
        over = {
            name: cnt for name, cnt in seen.items()
            if np.max(cnt) > ost.caps[name]
        }
        if not over and seen:
            ost.cnt_host.update(seen)
            self._table_gauges()
        return over

    def _fetch_values(self, ost: _OpState, ov, total: int, idx=slice(None)):
        """The output value columns on the host: the first ``total`` rows,
        then ``idx`` (one fetch per column)."""
        if not isinstance(ov, dict):
            self._metrics.device_fetches += 1
            return np.asarray(ov)[:total][idx]
        if ost.out_dtype is None:
            raise ValueError(
                f"fn_jit of operator {ost.spec.name!r} returned record "
                "columns but the operator declares no out_schema"
            )
        ov_np = np.empty(total, dtype=ost.out_dtype)
        for name in ost.out_names:
            ov_np[name] = np.asarray(ov[name])[:total][idx]
        self._metrics.device_fetches += len(ost.out_names)
        return ov_np

    def _table_gauges(self) -> None:
        """Entries and slots summed over every keyed table (host counts)."""
        entries = capacity = 0
        for ost in self._by_op.values():
            for name, cap in ost.caps.items():
                cnt = ost.cnt_host[name]
                if ost.shards:
                    entries += int(np.sum(cnt))
                    capacity += cap * ost.shards
                else:
                    entries += cnt
                    capacity += cap
        self._metrics.table_entries = entries
        self._metrics.table_capacity = capacity

    def _execute_sharded_tables(
        self, ost, lkgs, st_arr, en_arr, keys, values, ts, n, r
    ):
        """Key-group-sharded execution of a keyed-table operator.

        The host lays the call out shard-major: runs stable-sorted by their
        owning shard, tuples gathered run-major so every shard's runs tile
        a contiguous block, each shard padded to a common run bucket.  Per
        key group, run order and within-run tuple order are preserved
        (stable sort; a key group lives wholly on one shard), and equal
        codes share a key group — so the per-code tie-break order inside
        :func:`keyed_running_agg` is unchanged and the result is
        bit-identical to the plain call, modulo the documented prefix-sum
        float policy.  Outputs come back positionally over the permuted
        tuples and are ungathered on the host (1:1 bodies only).
        """
        with span("jit.prepare"):
            d = ost.shards
            shard_ids = ost.shard_of(lkgs)
            order_runs = np.argsort(shard_ids, kind="stable")
            lens = en_arr - st_arr
            if r:
                perm = np.concatenate(
                    [np.arange(st_arr[i], en_arr[i]) for i in order_runs]
                )
            else:
                perm = np.empty(0, np.int64)
            rs_per = np.bincount(shard_ids, minlength=d)
            rbs = _bucket(int(rs_per.max()) if r else 1, _MIN_RUN_BUCKET)
            nb = _bucket(n, _MIN_TUPLE_BUCKET)
            new_lens = lens[order_runs]
            new_ends = np.cumsum(new_lens)
            new_starts = new_ends - new_lens
            kg_pad = np.full(d * rbs, ost.nkg, dtype=np.int64)
            s_pad = np.empty(d * rbs, dtype=np.int64)
            e_pad = np.empty(d * rbs, dtype=np.int64)
            pos = 0
            off = 0
            for s in range(d):
                cnt_s = int(rs_per[s])
                blk_end = int(new_ends[pos + cnt_s - 1]) if cnt_s else off
                base_i = s * rbs
                s_pad[base_i : base_i + rbs] = blk_end
                e_pad[base_i : base_i + rbs] = blk_end
                if cnt_s:
                    kg_pad[base_i : base_i + cnt_s] = lkgs[
                        order_runs[pos : pos + cnt_s]
                    ]
                    s_pad[base_i : base_i + cnt_s] = new_starts[pos : pos + cnt_s]
                    e_pad[base_i : base_i + cnt_s] = new_ends[pos : pos + cnt_s]
                pos += cnt_s
                off = blk_end
            key_pad = np.zeros(nb, dtype=keys.dtype)
            key_pad[:n] = np.asarray(keys)[perm]
            ts_pad = np.zeros(nb, dtype=np.float64)
            ts_pad[:n] = np.asarray(ts)[perm]
            if ost.value_names is None:
                v_arg = np.zeros(nb, dtype=values.dtype)
                v_arg[:n] = np.asarray(values)[perm]
            else:
                v_arg = {}
                for name in ost.value_names:
                    col = values[name]
                    pad = np.zeros(nb, dtype=col.dtype)
                    pad[:n] = np.asarray(col)[perm]
                    v_arg[name] = pad
        fn = ost.spec.fn_jit
        table_names = tuple(
            sorted(f.name for f in ost.fields if f.kind == "table")
        )
        jitted = _jitted_sharded_tables(
            fn, self._mesh, self._mesh_axis, table_names
        )
        _, outputs, _ = self._run(
            ost, (nb, rbs, "shard_tab"), jitted,
            (kg_pad, s_pad, e_pad, key_pad, v_arg, ts_pad),
        )
        ost.col_auth[lkgs] = True
        self._metrics.jit_calls += 1
        self._metrics.jit_tuples += n
        with span("jit.fetch"):
            if outputs is None:
                return None, None
            ok, ov, ot = outputs
            inv = np.empty(n, dtype=np.int64)
            inv[perm] = np.arange(n)
            ok_np = np.asarray(ok)[:n][inv]
            ot_np = np.asarray(ot)[:n][inv]
            ov_np = self._fetch_values(ost, ov, n, inv)
            self._metrics.device_fetches += 2
        return (ok_np, ov_np, ot_np), None

    # ----------------------------------------------------- state coherence
    def _prepare_state(self, ost: _OpState, lkgs: np.ndarray) -> None:
        """Push dict-authoritative state, then keep every table at least
        twice its entries (the largest shard's, when sharded)."""
        pend = lkgs[~ost.col_auth[lkgs]]
        if len(pend):
            self._push(ost, pend)
        for f in ost.fields:
            if f.kind != "table":
                continue
            cnt = int(np.max(ost.cnt_host[f.name]))
            if _table_cap(cnt) > ost.caps[f.name]:
                self._grow(ost, f, cnt)

    @traced("jit.grow")
    def _grow(self, ost: _OpState, f: StateField, entries: int) -> None:
        """Extend the slab to twice ``entries``.  Every slab leaf is padded
        on the host and put back, so no device program runs at the new
        shape and a doubling compiles nothing here; the sorted view's EMPTY
        tail (ascending by slab index) extends with the fresh indices — no
        re-sort needed."""
        t0 = time.perf_counter()
        new_cap = _table_cap(entries)
        t = ost.cols[f.name]
        old = ost.caps[f.name]
        self._metrics.table_grows += 1
        # codes, seq, owner, perm and each value column
        self._metrics.device_fetches += 4 + len(f.combines)

        def grown(x, tail):
            h = np.asarray(x)
            out = np.empty(h.shape[:-1] + (new_cap,), dtype=h.dtype)
            out[..., :old] = h
            out[..., old:] = tail
            return jax.device_put(out, x.sharding) if ost.shards else jnp.asarray(out)

        ost.cols[f.name] = TableState(
            codes=grown(t.codes, EMPTY_CODE),
            vals=jax.tree_util.tree_map(lambda x: grown(x, 0), t.vals),
            seq=grown(t.seq, 0),
            owner=grown(t.owner, 0),
            perm=grown(t.perm, np.arange(old, new_cap)),
            cnt=t.cnt,
            epoch=t.epoch,
        )
        ost.caps[f.name] = new_cap
        self._compile_ahead(ost)
        self._metrics.table_grow_s += time.perf_counter() - t0

    def _compile_ahead(self, ost: _OpState) -> None:
        """Compile every shape launched so far at each capacity its tables
        may grow into, up to twice their fields' ``expected_entries``:
        a table growing there later runs a program compiled here and
        compiles nothing on the stream's path (on a TPU a large table's
        program compiles for tens of seconds)."""
        steps = []
        for f in ost.fields:
            if f.kind != "table":
                continue
            cap = ost.caps[f.name]
            top = max(cap, _table_cap(f.expected_entries))
            steps.append(
                [(f.name, cap << i) for i in range((top // cap).bit_length())]
            )
        if all(len(s) == 1 for s in steps):
            return
        for caps in itertools.product(*steps):
            caps = tuple(sorted(caps))
            state = self._state_spec(ost, dict(caps))
            for shape_key, (jitted, args) in ost.shapes.items():
                key = (shape_key, caps)
                if key in ost.seen_keys or key in ost.compiled:
                    continue
                self._metrics.jit_compiles += 1
                with span("jit.compile"):
                    t0 = time.perf_counter()
                    ost.compiled[key] = jitted.lower(state, *args).compile()
                    self.compile_seconds += time.perf_counter() - t0

    @staticmethod
    def _state_spec(ost: _OpState, caps: dict) -> dict:
        """The abstract values of the operator's state with its tables at
        ``caps``."""

        def spec(x, cap=None):
            shape = x.shape if cap is None else x.shape[:-1] + (cap,)
            return jax.ShapeDtypeStruct(shape, x.dtype, sharding=x.sharding)

        out = {}
        for name, col in ost.cols.items():
            if name in caps:
                cap = caps[name]
                out[name] = TableState(
                    codes=spec(col.codes, cap),
                    vals=tuple(spec(v, cap) for v in col.vals),
                    seq=spec(col.seq, cap),
                    owner=spec(col.owner, cap),
                    perm=spec(col.perm, cap),
                    cnt=spec(col.cnt),
                    epoch=spec(col.epoch),
                )
            else:
                out[name] = jax.tree_util.tree_map(spec, col)
        return out

    def _push(self, ost: _OpState, pend: np.ndarray) -> None:
        """Rebuild the columns with the pushed key groups' dict state.

        Scalar fields scatter; table fields rebuild the packed slab host
        side (stale entries of the pushed key groups drop, their dict
        entries re-append with fresh sequence numbers above every kept
        one, and the sorted view is a host argsort — stable, so the EMPTY
        tail stays ascending by slab index).
        """
        store = self._store.raw()
        m = len(pend)
        for f in ost.fields:
            if f.kind == "scalar":
                rows = np.fromiter(
                    (
                        store[ost.base + int(lk)].get(f.name, f.init)
                        for lk in pend
                    ),
                    dtype=f.dtype,
                    count=m,
                )
                ost.cols[f.name] = (
                    ost.cols[f.name].at[jnp.asarray(pend)].set(rows)
                )
                continue
            if f.kind == "vector":
                v = ost.cols[f.name]
                data = np.zeros((m, f.length), dtype=f.dtype)
                cnt = np.zeros(m, dtype=np.int32)
                for j, lk in enumerate(pend):
                    ring = store[ost.base + int(lk)].get(f.name, [])
                    cnt[j] = len(ring)
                    data[j, : len(ring)] = ring
                idx = jnp.asarray(pend)
                ost.cols[f.name] = VectorState(
                    data=v.data.at[idx].set(data),
                    cnt=v.cnt.at[idx].set(cnt),
                )
                continue
            if ost.shards:
                self._push_sharded_table(ost, f, pend)
                continue
            t = ost.cols[f.name]
            cnt = ost.cnt_host[f.name]
            codes = np.asarray(t.codes)[:cnt]
            vals = _host_vals(t.vals)[:, :cnt]
            seq = np.asarray(t.seq)[:cnt]
            owner = np.asarray(t.owner)[:cnt]
            # codes, seq, owner, each value column and the epoch
            self._metrics.device_fetches += 4 + len(f.combines)
            keep = ~np.isin(owner, pend)
            new_c, new_v, new_o = _dict_entries(store, ost, f, pend)
            n_keep = int(keep.sum())
            total = n_keep + len(new_c)
            cap = ost.caps[f.name]
            if _table_cap(total) > cap:
                cap = ost.caps[f.name] = _table_cap(total)
                self._metrics.table_grows += 1
            pc = np.full(cap, EMPTY_CODE, dtype=np.int64)
            pv = np.zeros((len(f.combines), cap), dtype=f.dtype)
            ps = np.zeros(cap, dtype=np.int64)
            po = np.zeros(cap, dtype=np.int32)
            pc[:n_keep] = codes[keep]
            pv[:, :n_keep] = vals[:, keep]
            ps[:n_keep] = seq[keep]
            po[:n_keep] = owner[keep]
            base_seq = int(ps[:n_keep].max()) + 1 if n_keep else 0
            if new_c:
                pc[n_keep:total] = new_c
                pv[:, n_keep:total] = new_v
                ps[n_keep:total] = base_seq + np.arange(len(new_c))
                po[n_keep:total] = new_o
            max_seq = int(ps[:total].max()) if total else 0
            epoch = max(int(t.epoch), (max_seq >> 32) + 1)
            ost.cols[f.name] = TableState(
                codes=jnp.asarray(pc),
                vals=tuple(jnp.asarray(v) for v in pv),
                seq=jnp.asarray(ps),
                owner=jnp.asarray(po),
                perm=jnp.asarray(
                    np.argsort(pc, kind="stable").astype(np.int32)
                ),
                cnt=jnp.asarray(np.int32(total)),
                epoch=jnp.asarray(np.int64(epoch)),
            )
            ost.cnt_host[f.name] = total
        ost.col_auth[pend] = True

    def _push_sharded_table(
        self, ost: _OpState, f: StateField, pend: np.ndarray
    ) -> None:
        """Per-shard restatement of the flat table rebuild: only the shards
        owning pushed key groups are rebuilt; the rest copy through (their
        EMPTY perm tail extends with fresh indices on a capacity bump)."""
        store = self._store.raw()
        d = ost.shards
        t = ost.cols[f.name]
        cnt_arr = np.asarray(ost.cnt_host[f.name], dtype=np.int64).copy()
        codes_h = np.asarray(t.codes)
        vals_h = _host_vals(t.vals)  # (columns, shards, capacity)
        seq_h = np.asarray(t.seq)
        owner_h = np.asarray(t.owner)
        perm_h = np.asarray(t.perm)
        epoch_h = np.asarray(t.epoch).copy()
        # codes, seq, owner, perm, each value column and the epoch
        self._metrics.device_fetches += 5 + len(f.combines)
        shard_ids = ost.shard_of(pend)
        old_cap = codes_h.shape[1]
        cap = ost.caps[f.name]
        per_shard = {}
        for s in sorted(set(shard_ids.tolist())):
            kgs_s = pend[shard_ids == s]
            cnt = int(cnt_arr[s])
            keep = ~np.isin(owner_h[s, :cnt], kgs_s)
            new_c, new_v, new_o = _dict_entries(store, ost, f, kgs_s)
            total = int(keep.sum()) + len(new_c)
            per_shard[s] = (cnt, keep, new_c, new_v, new_o, total)
            cap = max(cap, _table_cap(total))
        if cap > old_cap:
            self._metrics.table_grows += 1
        ost.caps[f.name] = cap
        pc = np.full((d, cap), EMPTY_CODE, dtype=np.int64)
        pv = np.zeros((len(f.combines), d, cap), dtype=f.dtype)
        ps = np.zeros((d, cap), dtype=np.int64)
        po = np.zeros((d, cap), dtype=np.int32)
        pp = np.zeros((d, cap), dtype=np.int32)
        for s in range(d):
            if s not in per_shard:
                pc[s, :old_cap] = codes_h[s]
                pv[:, s, :old_cap] = vals_h[:, s]
                ps[s, :old_cap] = seq_h[s]
                po[s, :old_cap] = owner_h[s]
                pp[s, :old_cap] = perm_h[s]
                pp[s, old_cap:] = np.arange(old_cap, cap)
                continue
            cnt, keep, new_c, new_v, new_o, total = per_shard[s]
            n_keep = int(keep.sum())
            pc[s, :n_keep] = codes_h[s, :cnt][keep]
            pv[:, s, :n_keep] = vals_h[:, s, :cnt][:, keep]
            ps[s, :n_keep] = seq_h[s, :cnt][keep]
            po[s, :n_keep] = owner_h[s, :cnt][keep]
            base_seq = int(ps[s, :n_keep].max()) + 1 if n_keep else 0
            if new_c:
                pc[s, n_keep:total] = new_c
                pv[:, s, n_keep:total] = new_v
                ps[s, n_keep:total] = base_seq + np.arange(len(new_c))
                po[s, n_keep:total] = new_o
            pp[s] = np.argsort(pc[s], kind="stable").astype(np.int32)
            max_seq = int(ps[s, :total].max()) if total else 0
            epoch_h[s] = max(int(epoch_h[s]), (max_seq >> 32) + 1)
            cnt_arr[s] = total
        ost.cols[f.name] = TableState(
            codes=jnp.asarray(pc),
            vals=tuple(jnp.asarray(v) for v in pv),
            seq=jnp.asarray(ps),
            owner=jnp.asarray(po),
            perm=jnp.asarray(pp),
            cnt=jnp.asarray(cnt_arr.astype(np.int32)),
            epoch=jnp.asarray(epoch_h),
        )
        ost.cnt_host[f.name] = cnt_arr

    def _to_dict(self, ost: _OpState, lk: int, host: dict) -> dict:
        """Materialize one key group's columns as the oracle state dict."""
        out: dict = {}
        for f in ost.fields:
            if f.kind == "scalar":
                out[f.name] = f.py(host[f.name][lk])
            elif f.kind == "vector":
                data, cnt = host[f.name]
                out[f.name] = [
                    f.py(x) for x in data[lk][: int(cnt[lk])]
                ]
            else:
                codes, vals, bounds = host[f.name]
                a, z = bounds[lk], bounds[lk + 1]
                keys = map(f.key_decode, codes[a:z].tolist())
                py = f.py
                if isinstance(f.combine, str):  # one column: a scalar
                    rows = map(py, vals[0, a:z].tolist())
                else:
                    rows = (
                        tuple(map(py, row)) for row in vals[:, a:z].T.tolist()
                    )
                out[f.name] = dict(zip(keys, rows))
        return out

    def _host_cols(self, ost: _OpState) -> dict:
        """Every state field on the host.  A table's entries come sorted by
        key group, then by insertion (``seq``), with each key group's bounds:
        ``(codes, values (columns, entries), bounds)``."""
        host = {}
        for f in ost.fields:
            if f.kind == "scalar":
                self._metrics.device_fetches += _FIELD_FETCHES[f.kind]
                host[f.name] = np.asarray(ost.cols[f.name])
                continue
            if f.kind == "vector":
                self._metrics.device_fetches += _FIELD_FETCHES[f.kind]
                v = ost.cols[f.name]
                host[f.name] = (np.asarray(v.data), np.asarray(v.cnt))
                continue
            # codes, seq, owner and each value column
            self._metrics.device_fetches += 3 + len(f.combines)
            t = ost.cols[f.name]
            cnt = ost.cnt_host[f.name]
            codes_h = np.asarray(t.codes)
            vals_h = _host_vals(t.vals)
            seq_h = np.asarray(t.seq)
            owner_h = np.asarray(t.owner)
            if ost.shards:
                # Flatten the per-shard slabs into one valid-entry view; a
                # key group's entries live wholly in its owning shard.
                live = [slice(0, int(cnt[s])) for s in range(ost.shards)]
                codes_h, seq_h, owner_h = (
                    np.concatenate([arr[s, c] for s, c in enumerate(live)])
                    for arr in (codes_h, seq_h, owner_h)
                )
                vals_h = np.concatenate(
                    [vals_h[:, s, c] for s, c in enumerate(live)], axis=1
                )
            else:
                codes_h, seq_h, owner_h = (
                    arr[:cnt] for arr in (codes_h, seq_h, owner_h)
                )
                vals_h = vals_h[:, :cnt]
            order = np.lexsort((seq_h, owner_h))  # stable: slab order on ties
            bounds = np.searchsorted(
                owner_h[order], np.arange(ost.nkg + 1)
            ).tolist()
            host[f.name] = (codes_h[order], vals_h[:, order], bounds)
        return host

    def ensure_dict(self, kg: int) -> None:
        """Make the python store dict authoritative for one key group.

        Called by the engine before any per-run ``fn`` fallback or state
        serialization touches a jit-tier operator's key group.
        """
        op = int(self._kg_op[kg])
        ost = self._by_op.get(op)
        if ost is None or not ost.fields:
            return
        lk = kg - ost.base
        if not ost.col_auth[lk]:
            return
        self._store.raw()[kg] = self._to_dict(ost, lk, self._host_cols(ost))
        ost.col_auth[lk] = False

    def invalidate(self, kg: int) -> None:
        """Dict state was externally replaced (migration install)."""
        op = int(self._kg_op[kg])
        ost = self._by_op.get(op)
        if ost is not None and ost.fields:
            ost.col_auth[kg - ost.base] = False

    def sync_store(self) -> None:
        """Refresh the store dicts of every column-authoritative key group
        (columns stay authoritative — this is the read-only statistics /
        conformance snapshot taken at ``end_period``)."""
        store = self._store.raw()
        for ost in self._by_op.values():
            if not ost.fields:
                continue
            lks = np.flatnonzero(ost.col_auth)
            if not len(lks):
                continue
            host = self._host_cols(ost)
            for lk in lks.tolist():
                store[ost.base + lk] = self._to_dict(ost, lk, host)

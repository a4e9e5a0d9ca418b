"""Time the pieces of a keyed table update, each as its own jitted program.

The device trace names every fusion of a jitted operator ``fusion``, so it
cannot say where the time of ``keyed_running_agg`` goes.  This script
times its pieces standalone at the benchmark cells' shapes (65,536 tuples
a call; Job 2's one float64 column at 16,384 slots; q17's seven int64
columns at 524,288 and 1,048,576 slots), then the whole call at each shape:

* scatters of 65,536 updates, int64 / float64 / int32: a permutation of
  the segment (the sorted → tuple order un-permute) and indices into a
  table (the slab writes, most of them dropped as out of range);
* the slab writes of ``keyed_running_agg``: 6 or 20 uint32 words a slot
  (Job 2's and q17's), scattered at 65,536 columns of a (words, slots)
  array and at 65,536 rows of a (slots, words) one; and rows of sixteen
  int32 words;
* gathers of 65,536 and of 1,048,576 elements;
* the capacity-sized merge scatter of the code-sorted view, and
  ``searchsorted`` with one query per slot and with one per tuple;
* int32 prefix sums and maxima, in one level and in two;
* the radix argsort of the segment's codes.

Every time is the median of three bursts, each the mean of ``--reps``
back-to-back calls that end in ``block_until_ready``; compiling is not
timed.  Prints one JSON line per piece and writes them all to
``chiprun_out/table_split.json``.  Refuses any backend but a TPU unless
``--tiny`` shrinks every shape for a rehearsal on the CPU.  ``--only``
times just the pieces it names.

    python benchmarks/table_split.py [--reps 20] [--tiny] [--only a,b]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

NB = 65_536  # tuples per call, every cell
JOB2_CAP = 16_384
Q17_CAPS = (524_288, 1_048_576)
Q17_COMBINES = ("sum",) * 4 + ("min", "max", "sum")


def timed(fn, args, reps):
    import jax

    jax.block_until_ready(fn(*args))  # compile and warm
    bursts = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        bursts.append((time.perf_counter() - t0) / reps)
    return statistics.median(bursts) * 1e3


def table_state(rng, cap, combines, dtype, fill=0.3):
    """A table ``fill`` full of random codes, with its code-sorted view."""
    from repro.engine import jitexec as jx
    import jax.numpy as jnp

    cnt = int(cap * fill)
    keys = np.unique(rng.integers(0, 1 << 40, size=2 * cnt))[:cnt]
    keys = rng.permutation(keys)
    codes = np.full(cap, jx.EMPTY_CODE, np.int64)
    codes[:cnt] = keys
    vals = tuple(
        jnp.asarray(rng.integers(-1000, 1000, size=cap).astype(dtype))
        for _ in combines
    )
    return jx.TableState(
        codes=jnp.asarray(codes), vals=vals,
        seq=jnp.asarray(np.arange(cap, dtype=np.int64)),
        owner=jnp.asarray((codes % 400).astype(np.int32)),
        perm=jnp.asarray(np.argsort(codes, kind="stable").astype(np.int32)),
        cnt=jnp.asarray(np.int32(cnt)), epoch=jnp.asarray(np.int64(2)),
    ), keys


def segment(rng, keys, nb, new, ncols, dtype):
    """``nb`` tuples: hits on ``keys`` and ``new`` codes not in the table."""
    import jax.numpy as jnp

    codes = rng.choice(keys, size=nb)
    fresh = np.setdiff1d(rng.integers(1 << 40, 1 << 41, size=2 * new), keys)
    codes[rng.choice(nb, size=new, replace=False)] = fresh[:new]
    vals = tuple(
        jnp.asarray(rng.integers(1, 1000, size=nb).astype(dtype))
        for _ in range(ncols)
    )
    return (jnp.asarray(codes), jnp.asarray(codes % 400), vals,
            jnp.asarray(np.ones(nb, bool)))


def pieces(nb, job2_cap, q17_caps, reps, only=None):
    import jax
    import jax.numpy as jnp

    from repro.engine import jitexec as jx
    from repro.kernels.radix_sort import radix_argsort_jax

    rng = np.random.default_rng(17)
    rows = []

    def put(name, fn, *args, **facts):
        if only and name not in only:
            return
        ms = timed(jax.jit(fn), args, reps)
        rows.append({"piece": name, "ms": ms, **facts})
        print(json.dumps(rows[-1]), flush=True)

    def scatter(dst, idx, v):
        return dst.at[idx].set(v, mode="drop")

    def gather(src, idx):
        return src[idx]

    perm_nb = jnp.asarray(rng.permutation(nb).astype(np.int32))
    for cap in (job2_cap,) + q17_caps:
        # Slab writes: a group's tail per distinct code, the rest dropped.
        idx = rng.integers(0, 4 * cap, size=nb)
        idx = jnp.asarray(np.where(idx < cap, idx, cap).astype(np.int32))
        for w in (6, 20):  # Job 2's and q17's words per slot
            put("scatter_words_uint32", lambda d, i, v: d.at[:, i].set(
                v, mode="drop"), jnp.zeros((w, cap), jnp.uint32), idx,
                jnp.ones((w, nb), jnp.uint32), updates=nb, into=cap, words=w)
            put("scatter_rows_uint32", scatter, jnp.zeros((cap, w), jnp.uint32),
                idx, jnp.ones((nb, w), jnp.uint32), updates=nb, into=cap,
                words=w)
        for dt in ("int64", "float64", "int32"):
            put(f"scatter_{dt}", scatter, jnp.zeros(cap, dt), idx,
                jnp.ones(nb, dt), updates=nb, into=cap)
        put("scatter_rows16_int32", scatter, jnp.zeros((cap, 16), jnp.int32),
            idx, jnp.ones((nb, 16), jnp.int32), updates=nb, into=cap)
        src = jnp.asarray(rng.integers(0, 1 << 40, size=cap))
        put("gather_int64", gather, src, jnp.asarray(
            rng.integers(0, cap, size=nb).astype(np.int32)), n=nb, of=cap)
        put("gather_int64", gather, src, jnp.asarray(
            rng.integers(0, cap, size=cap).astype(np.int32)), n=cap, of=cap)
        put("gather_int32", gather, src.astype(jnp.int32), jnp.asarray(
            rng.integers(0, cap, size=cap).astype(np.int32)), n=cap, of=cap)
        # The merge of the code-sorted view: every slot moves.
        put("oldpos_scatter_int32", scatter, jnp.zeros(cap, jnp.int32),
            jnp.asarray(rng.permutation(cap).astype(np.int32)),
            jnp.arange(cap, dtype=jnp.int32), updates=cap, into=cap)
        scodes = jnp.sort(src)
        sc = jnp.sort(jnp.asarray(rng.integers(0, 1 << 40, size=nb)))
        put("searchsorted_per_slot", jnp.searchsorted, sc, scodes,
            queries=cap, into=nb)
        put("searchsorted_per_tuple", jnp.searchsorted, scodes, sc,
            queries=nb, into=cap)
        marks = jnp.asarray(rng.random(cap) < 0.01)
        put("cumsum_int32", lambda m: jnp.cumsum(m, dtype=jnp.int32), marks,
            n=cap)
        put("prefix_sum_int32_two_level", lambda m: jx._prefix(
            m.astype(jnp.int32), "sum"), marks, n=cap)
    for dt in ("int64", "float64", "int32"):
        put(f"scatter_{dt}", scatter, jnp.zeros(nb, dt), perm_nb,
            jnp.ones(nb, dt), updates=nb, into=nb, indices="permutation")
    heads = jnp.asarray(rng.random(nb) < 0.1)
    put("cummax_int32", lambda h: jax.lax.cummax(
        jnp.where(h, jnp.arange(nb, dtype=jnp.int32), 0)), heads, n=nb)
    put("radix_argsort", radix_argsort_jax,
        jnp.asarray(rng.integers(0, 1 << 41, size=nb)), n=nb)

    agg = jax.jit(jx.keyed_running_agg, static_argnames="combines")
    shapes = [(job2_cap, ("sum",), np.float64, 0)]
    new = min(4_270, nb // 16)  # q17's new keys per call
    shapes += [(cap, Q17_COMBINES, np.int64, new) for cap in q17_caps]
    for cap, combines, dtype, new in shapes:
        if only and "keyed_running_agg" not in only:
            break
        table, keys = table_state(rng, cap, combines, dtype)
        codes, kg, vals, valid = segment(rng, keys, nb, new, len(combines), dtype)
        ms = timed(lambda *a: agg(*a, combines=combines),
                   (table, codes, kg, vals, valid), reps)
        rows.append({"piece": "keyed_running_agg", "ms": ms, "columns":
                     len(combines), "cap": cap, "new": new, "n": nb})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes, any backend (a rehearsal)")
    ap.add_argument("--only", default="",
                    help="comma-separated pieces to time (default: all)")
    args = ap.parse_args(argv)
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        print(f"platform {dev.platform!r}: no TPU, refusing to run",
              file=sys.stderr)
        return 2
    only = set(filter(None, args.only.split(",")))
    if args.tiny:
        rows = pieces(1024, 256, (2048,), args.reps, only)
    else:
        rows = pieces(NB, JOB2_CAP, Q17_CAPS, args.reps, only)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "reps": args.reps, "rows": rows}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "table_split.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["device"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())

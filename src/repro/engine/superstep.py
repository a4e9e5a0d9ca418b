"""Device-resident superstep: route → drain → fn_jit fused into one jit call.

The per-operator compiled tier (:mod:`repro.engine.jitexec`) crosses the
host↔device boundary once per operator per tick: the host drains segments,
dispatches one padded program per operator, downloads the outputs, hashes
and sorts them on the host and pushes the runs back into numpy queues.  For
a linear chain of 1:1 ``fn_jit`` operators all of that inter-operator
traffic is avoidable — the routing hash is :func:`repro.engine.topology.mix32`
(pure integer arithmetic), the routing sort is a bucketed stable argsort
(:mod:`repro.kernels.radix_sort`), and the drained runs of tick ``t`` are
exactly the runs routed at tick ``t-1``.

This module fuses the whole tick for such chains:

* **Fused tick** (:meth:`SuperstepRuntime.try_fused_tick`) — one donated
  ``jax.jit`` call executes every fused operator's body *and* the device-side
  routing of its outputs (hash → stable bucketed argsort → gather).  Routed
  outputs stay on the device as *pending columns*; the queues hold **shadow
  segments** — run metadata (key groups, bounds, costs) with ``None`` arrays
  — so drain accounting, budgets, backpressure and migration bookkeeping
  replay bit-exactly on the host from the downloaded per-edge
  (source key group × destination key group) count matrices.  One host
  crossing per tick (``metrics.jit_host_syncs``), independent of chain depth.

* **K-tick scan** (:meth:`SuperstepRuntime.run_supersteps`) — steady-state
  mode: ``lax.scan`` wraps K fused ticks so the host boundary is crossed
  once per K supersteps.  Source batches are staged (hashed, radix-sorted,
  padded) up front.  When every non-terminal fused operator declares
  ``OperatorSpec.jit_key_map``, the entire routing schedule — every hop's
  hash, stable radix permutation and per-edge count matrix — is a pure
  function of the staged keys and is evaluated host-side during staging
  (numpy's radix path, ~35× faster than XLA's CPU comparison sort), so the
  compiled scan body carries no sorts at all; otherwise the scan routes on
  device and returns per-tick pair matrices as scan outputs.  Either way
  the statistics are folded into the engine in aggregate.  This is the
  throughput path benchmarked by ``engine_throughput/superstep_jit``; it
  reproduces
  every pinned aggregate (metrics, states, sink outputs, arrivals, usage,
  send pairs, queue costs) but records no per-admission latency samples and
  performs no per-tick credit checks — use :meth:`Engine.tick` when those
  matter.

Reconfiguration hook: every fused tick re-reads ``Router.table`` (cached on
``Router.version``), falls back to the classic tick — after
:meth:`flush_to_host` materializes the pending device columns into real
segment arrays — whenever a migration is in flight, a node is dead, a
budget would bind mid-segment, or the queues hold anything the fused replay
cannot express.  ``redirect``/``serialize``/``fail_node`` flush first, so
migration envelopes (:mod:`repro.engine.serde`) are byte-identical to the
interpreted oracle's at any superstep boundary.

Eligibility is static (checked once per engine): a single source followed by
a linear chain of ``jit_fusible`` 1:1 ``fn_jit`` operators with declared
matching schemas, identity partition keys of integer dtype and scalar-only
state fields.  Anything else simply never fuses — the engine behaves exactly
like the per-operator tier.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.engine import jitexec as jx
from repro.engine.router import concat_batches
from repro.engine.topology import (
    _MASK31,
    _MIX_C1,
    _MIX_C2,
    _identity_key,
    _mixed_keygroups,
    mix32,
)
from repro.kernels.radix_sort import bucket_argsort, bucket_argsort_jax

__all__ = ["SuperstepRuntime", "mix32_jax", "local_keygroups_jax", "plan_chain"]


# --------------------------------------------------------------------------
# Device replica of the routing hash (bit-identical to topology.mix32).
# --------------------------------------------------------------------------


def mix32_jax(x: jax.Array) -> jax.Array:
    """Traceable :func:`repro.engine.topology.mix32`: int array → uint32.

    ``astype(uint64)`` sign-extends negative int32/int64 lanes exactly like
    numpy's ``astype`` (value mod 2^64), so every step below matches the
    host mix bit for bit.
    """
    u = x.astype(jnp.uint64)
    h = ((u ^ (u >> jnp.uint64(32))) & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(_MIX_C1)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(_MIX_C2)
    h = h ^ (h >> jnp.uint32(16))
    return h


def local_keygroups_jax(keys: jax.Array, nkg: int) -> jax.Array:
    """Traceable local key-group ids (``topology._mixed_keygroups`` − base)."""
    h = mix32_jax(keys) & jnp.uint32(_MASK31)
    if nkg & (nkg - 1) == 0:
        loc = h & jnp.uint32(nkg - 1)
    else:
        loc = h % jnp.uint32(nkg)
    return loc.astype(jnp.int64)


# --------------------------------------------------------------------------
# Static fusion plan.
# --------------------------------------------------------------------------


class _Plan:
    """Static description of the fusible chain: source, then fused ops."""

    __slots__ = ("source", "fops", "fset", "specs", "nkg", "base",
                 "key_maps", "static_route")

    def __init__(self, source, fops, specs, nkg, base):
        self.source = source
        self.fops = fops  # fused operator ids, chain order
        self.fset = frozenset(fops)
        self.specs = specs
        self.nkg = nkg
        self.base = base
        # Host-evaluable key transforms (OperatorSpec.jit_key_map) for the
        # non-terminal fused operators.  When every one is declared, the
        # K-tick scan's routing schedule (hash → stable radix permutation →
        # pair-count matrices) is a pure function of the staged input keys,
        # so run_supersteps evaluates it on the host and the compiled scan
        # body carries no sorts at all.
        self.key_maps = [s.jit_key_map for s in specs[:-1]]
        self.static_route = all(m is not None for m in self.key_maps)


def plan_chain(engine) -> Optional[_Plan]:
    """Static superstep eligibility; ``None`` → this engine never fuses."""
    topo = engine.topology
    if engine._jit_mesh is not None:
        return None
    if not engine.use_schema:
        return None
    downs, ups = topo.downstream(), topo.upstream()
    sources = [i for i, o in enumerate(topo.operators) if o.is_source]
    if len(sources) != 1:
        return None
    src = sources[0]
    if topo.operators[src].fn is not None or topo.operators[src].schema is None:
        return None
    chain = [src]
    cur = src
    while downs[cur]:
        if len(downs[cur]) != 1:
            return None
        nxt = downs[cur][0]
        if len(ups[nxt]) != 1:
            return None
        chain.append(nxt)
        cur = nxt
    if len(chain) < 2 or len(chain) != topo.num_operators:
        return None
    if not engine._op_terminal[chain[-1]]:
        return None
    prev_out = topo.operators[src].schema
    for pos, op in enumerate(chain[1:]):
        spec = topo.operators[op]
        terminal = op == chain[-1]
        if engine._op_fn_jit[op] is None or not spec.jit_fusible:
            return None
        if spec.fn is None or spec.schema is None:
            return None
        if spec.key_fn is not _identity_key or spec.key_by_value is not None:
            return None
        if not np.issubdtype(spec.schema.key, np.integer):
            return None
        fields = spec.state_schema.fields if spec.state_schema is not None else ()
        if any(f.kind != "scalar" for f in fields):
            return None
        # The routed edge must be conformance-free: producer output layout
        # identical to this operator's declared input layout.
        if prev_out is None:
            return None
        if spec.schema.key != prev_out.key or spec.schema.value != prev_out.value:
            return None
        if not terminal:
            if spec.out_schema is None:
                return None
            prev_out = spec.out_schema
    fops = chain[1:]
    return _Plan(
        src,
        fops,
        [topo.operators[o] for o in fops],
        [topo.operators[o].num_keygroups for o in fops],
        [topo.kg_base(o) for o in fops],
    )


class _DevicePending:
    """Routed-but-undrained tuples of one operator, resident on device.

    ``keys``/``values``/``ts`` are the comp-sorted padded columns produced by
    the fused routing step (valid rows ``[0, n)``, garbage tail beyond —
    safe under the ``jit_fusible`` run-bounds contract); the matching shadow
    segments in the node queues carry the run metadata referencing them.
    """

    __slots__ = ("keys", "values", "ts", "n")

    def __init__(self, keys, values, ts, n):
        self.keys = keys
        self.values = values
        self.ts = ts
        self.n = n


class SuperstepRuntime:
    """Fused superstep execution for one :class:`repro.engine.Engine`."""

    def __init__(self, engine) -> None:
        self.engine = engine
        self.plan = plan_chain(engine)
        self._pending: dict[int, Optional[_DevicePending]] = {}
        self._fused_cache: dict = {}
        self._scan_cache: dict = {}
        self._seen_keys: set = set()
        self._tables_version = -1
        self._tables: list = []

    # ------------------------------------------------------------ plumbing
    def _jrt(self):
        eng = self.engine
        if eng._jit is None:
            from repro.engine.jitexec import JitRuntime

            eng._jit = JitRuntime(
                eng.topology, eng.store, eng.metrics, eng._kg_op,
                mesh=eng._jit_mesh, mesh_axis=eng._jit_mesh_axis,
            )
        return eng._jit

    def _dev_tables(self):
        """Per fused edge, the downstream operator's router-table slice on
        device — re-uploaded only when ``Router.version`` moved (the per-
        superstep reconfiguration hook)."""
        eng = self.engine
        router = eng.router
        if router.version != self._tables_version:
            self._tables = [
                jnp.asarray(
                    router.table[self.plan.base[i + 1]:
                                 self.plan.base[i + 1] + self.plan.nkg[i + 1]]
                )
                for i in range(len(self.plan.fops) - 1)
            ]
            self._tables_version = router.version
        return self._tables

    def flush_to_host(self) -> None:
        """Materialize pending device columns into their shadow segments.

        Run metadata (bounds, costs, queue order) is already exact; only the
        ``None`` array slots are filled, so a subsequent classic tick drains
        precisely what the fused tick would have.  Idempotent and cheap when
        nothing is pending.
        """
        if not self._pending:
            return
        eng = self.engine
        mats = {}
        for op, p in self._pending.items():
            if p is None:
                continue
            keys_np = np.asarray(p.keys)
            ts_np = np.asarray(p.ts)
            if isinstance(p.values, dict):
                dt = eng._op_schema[op].value
                vals_np = np.empty(len(keys_np), dtype=dt)
                for nm in dt.names:
                    vals_np[nm] = np.asarray(p.values[nm])
            else:
                vals_np = np.asarray(p.values)
            mats[op] = (keys_np, vals_np, ts_np)
        for q in eng._queues:
            for seg in q._segs:
                if seg[0] is None and seg[3] in mats:
                    k, v, t = mats[seg[3]]
                    seg[0], seg[1], seg[2] = k, v, t
        self._pending = {}

    # ----------------------------------------------------- dynamic gating
    def _collect(self):
        """Validate this tick for fusion and collect the drain layout.

        Read-only: replicates every branch decision of the classic SoA drain
        (whole-budget eligibility, contiguity, FIFO order) without mutating
        anything, so a ``None`` return falls back to the classic tick with
        the queues untouched.
        """
        eng = self.engine
        plan = self.plan
        if plan is None:
            return None
        if eng.router.has_in_flight() or eng._backlog or not bool(eng.alive.all()):
            return None
        src, fset = plan.source, plan.fset
        entries: dict[int, list] = {op: [] for op in plan.fops}
        src_segs: list = []
        mode: dict[int, Optional[str]] = {op: None for op in plan.fops}
        nonempty = 0
        for node, q in enumerate(eng._queues):
            if not q:
                continue
            nonempty += 1
            budget = eng.service_rate * eng._capacity_list[node]
            segs = q._segs
            last = segs[-1]
            for seg in segs:
                if seg[8] != 0 or not seg[9]:  # partially drained / non-contig
                    return None
                op = seg[3]
                if op == src:
                    if seg[0] is None:
                        return None
                    src_segs.append((node, seg))
                elif op in fset:
                    m = "shadow" if seg[0] is None else "real"
                    if m == "shadow" and self._pending.get(op) is None:
                        return None
                    if mode[op] is None:
                        mode[op] = m
                    elif mode[op] != m:
                        return None  # mixed real+shadow (post-migration)
                    entries[op].append((node, seg))
                else:
                    return None
                costs = seg[7]
                rem = 0.0
                for c in costs:
                    rem += c
                if budget < rem:
                    return None  # classic would partial-drain this segment
                for c in costs:
                    budget -= c
                if budget <= 0 and seg is not last:
                    return None  # classic would stop draining this node
        for op, p in self._pending.items():
            if p is not None and mode.get(op) != "shadow":
                return None  # pending exists but its segments are gone
        return nonempty, src_segs, entries, mode

    # ------------------------------------------------------- fused device
    def _traced(self, key, active, nbs):
        """Build (or fetch) the fused whole-tick program for one shape key."""
        cached = self._fused_cache.get(key)
        if cached is not None:
            return cached
        plan = self.plan
        eng = self.engine
        num_nodes = eng.num_nodes
        collect = eng.collect_sinks
        fops = plan.fops
        nkgs = plan.nkg
        fns = [s.fn_jit for s in plan.specs]
        last = len(fops) - 1

        def fused(states, runs, inputs, tables):
            new_states = {}
            pend = {}
            pairs = {}
            term = None
            for i in active:
                kg_pad, s_pad, e_pad = runs[i]
                keys, values, ts = inputs[i]
                st, out, oc = fns[i](
                    states[i], kg_pad, s_pad, e_pad, keys, values, ts
                )
                if oc is not None:
                    raise ValueError(
                        f"operator {plan.specs[i].name!r} is jit_fusible but "
                        "returned out_counts — fused operators must be 1:1"
                    )
                new_states[i] = st
                if i == last:
                    if collect and out is not None:
                        term = out
                    continue
                if out is None:
                    raise ValueError(
                        f"non-terminal fused operator {plan.specs[i].name!r} "
                        "emitted None"
                    )
                ok, ov, ot = out
                nb = nbs[i]
                nkg_n = nkgs[i + 1]
                valid = jx.tuple_valid(s_pad, e_pad, nb)
                dst = local_keygroups_jax(ok, nkg_n)
                node = tables[i][dst]
                sent = num_nodes * nkg_n
                comp = jnp.where(valid, node * nkg_n + dst, sent)
                order = bucket_argsort_jax(comp, sent + 1)
                pk = ok[order]
                pt = ot[order]
                if isinstance(ov, dict):
                    pv = {nm: col[order] for nm, col in ov.items()}
                else:
                    pv = ov[order]
                ridx = jx.run_of_tuples(e_pad, nb)
                src_l = kg_pad[ridx]
                dcol = jnp.where(valid, dst, nkg_n)
                pr = (
                    jnp.zeros((nkgs[i] + 1, nkg_n + 1), jnp.int64)
                    .at[src_l, dcol]
                    .add(1, mode="drop")
                )
                pairs[i] = pr
                pend[i] = (pk, pv, pt)
            return new_states, pend, pairs, term

        jitted = jax.jit(fused)
        self._fused_cache[key] = jitted
        return jitted

    # ---------------------------------------------------------- fused tick
    def try_fused_tick(self) -> bool:
        """Attempt one fully fused superstep; ``False`` → caller must flush
        pendings and run the classic tick instead."""
        colln = self._collect()
        if colln is None:
            return False
        eng = self.engine
        plan = self.plan
        metrics = eng.metrics
        nonempty, src_segs, entries, mode = colln
        eng.metrics.ticks += 1
        eng._ticks_this_period += 1
        if nonempty == 0:
            return True  # empty tick: counters only, no device call
        jrt = self._jrt()

        # -- drain replay: accounting + input collection (node-asc, FIFO) --
        drained_kgs: list = []
        drained_costs: list = []
        src_items: list = []
        processed = src_emitted = 0
        # per fused op, in drain order: (node, kgs, starts, ends, k, v, t)
        drains: dict[int, list] = {op: [] for op in plan.fops}
        for node, q in enumerate(eng._queues):
            if not q:
                continue
            qcost = q.cost
            segs = q._segs
            while segs:
                seg = segs[0]
                keys, values, ts, op, kgs, starts, ends, costs, _, _ = seg
                drained_kgs.extend(kgs)
                drained_costs.extend(costs)
                for c in costs:
                    qcost -= c
                a0, zn = starts[0], ends[-1]
                processed += zn - a0
                if op == plan.source:
                    # Source pass-through forwards its whole slice (and the
                    # classic drain counts that as an emission).
                    src_emitted += zn - a0
                    lens = np.subtract(ends, starts)
                    kg_arr = np.repeat(np.asarray(kgs, dtype=np.int64), lens)
                    src_items.append(
                        ((keys[a0:zn], values[a0:zn], ts[a0:zn]), kg_arr, node)
                    )
                else:
                    drains[op].append((node, kgs, starts, ends, keys, values, ts))
                segs.popleft()
            q.cost = qcost
        metrics.processed_tuples += processed
        metrics.emitted_tuples += src_emitted

        # -- assemble the device call ----------------------------------------
        fops = plan.fops
        active = [i for i, op in enumerate(fops) if drains[op]]
        runs_args: dict[int, tuple] = {}
        in_args: dict[int, tuple] = {}
        lkgs_by_i: dict[int, np.ndarray] = {}
        n_by_i: dict[int, int] = {}
        nbs: dict[int, int] = {}
        src_node_of: dict[int, np.ndarray] = {}
        for i in active:
            op = fops[i]
            ost = jrt._by_op[op]
            ents = drains[op]
            rk: list = []
            node_map = np.full(plan.nkg[i], -1, dtype=np.int64)
            if mode[op] == "shadow":
                p = self._pending[op]
                n = p.n
                rs: list = []
                re_: list = []
                for node, kgs, starts, ends, _, _, _ in ents:
                    rk.extend(kgs)
                    rs.extend(starts)
                    re_.extend(ends)
                    for kg in kgs:
                        node_map[kg - plan.base[i]] = node
                k_in, v_in, t_in = p.keys, p.values, p.ts
                nb = len(p.keys)
            else:
                # Real segments (e.g. first tick, or after a migration
                # flush): concatenate exactly like _flush_jit_batch and
                # upload padded host buffers.
                cat_k, cat_v, cat_t = [], [], []
                rs, re_ = [], []
                off = 0
                for node, kgs, starts, ends, keys, values, ts in ents:
                    a0, zn = starts[0], ends[-1]
                    rk.extend(kgs)
                    rs.extend(a - a0 + off for a in starts)
                    re_.extend(z - a0 + off for z in ends)
                    cat_k.append(keys[a0:zn])
                    cat_v.append(values[a0:zn])
                    cat_t.append(ts[a0:zn])
                    off += zn - a0
                    for kg in kgs:
                        node_map[kg - plan.base[i]] = node
                keys_c = cat_k[0] if len(cat_k) == 1 else np.concatenate(cat_k)
                vals_c = cat_v[0] if len(cat_v) == 1 else np.concatenate(cat_v)
                ts_c = cat_t[0] if len(cat_t) == 1 else np.concatenate(cat_t)
                n = off
                nb = jx._bucket(n, jx._MIN_TUPLE_BUCKET)
                k_in = np.zeros(nb, dtype=keys_c.dtype)
                k_in[:n] = keys_c
                t_in = np.zeros(nb, dtype=np.float64)
                t_in[:n] = ts_c
                if ost.value_names is None:
                    v_in = np.zeros(nb, dtype=vals_c.dtype)
                    v_in[:n] = vals_c
                else:
                    v_in = {}
                    for nm in ost.value_names:
                        col = vals_c[nm]
                        pad = np.zeros(nb, dtype=col.dtype)
                        pad[:n] = col
                        v_in[nm] = pad
            r = len(rk)
            rb = jx._bucket(r, jx._MIN_RUN_BUCKET)
            lkgs = np.asarray(rk, dtype=np.int64) - plan.base[i]
            if ost.fields:
                jrt._prepare_state(ost, lkgs)
            kg_pad = np.full(rb, ost.nkg, dtype=np.int64)
            kg_pad[:r] = lkgs
            s_pad = np.full(rb, n, dtype=np.int64)
            s_pad[:r] = np.asarray(rs, dtype=np.int64)
            e_pad = np.full(rb, n, dtype=np.int64)
            e_pad[:r] = np.asarray(re_, dtype=np.int64)
            runs_args[i] = (kg_pad, s_pad, e_pad)
            in_args[i] = (k_in, v_in, t_in)
            lkgs_by_i[i] = lkgs
            n_by_i[i] = n
            nbs[i] = nb
            src_node_of[i] = node_map

        key = (
            tuple(active),
            tuple(nbs[i] for i in active),
            tuple(len(runs_args[i][0]) for i in active),
            eng.num_nodes,
            eng.collect_sinks,
        )
        jitted = self._traced(key, tuple(active), nbs)
        states = {i: jrt._by_op[fops[i]].cols for i in active}
        tables = {
            i: t
            for i, t in enumerate(self._dev_tables())
            if i in runs_args
        }
        first = key not in self._seen_keys
        if first:
            self._seen_keys.add(key)
            metrics.jit_compiles += 1
            t0 = time.perf_counter()
        result = jitted(states, runs_args, in_args, tables)
        if first:
            jax.block_until_ready(result)
            jrt.compile_seconds += time.perf_counter() - t0
        new_states, pend_dev, pairs_dev, term = result
        last = len(fops) - 1
        for i in active:
            ost = jrt._by_op[fops[i]]
            ost.cols = new_states[i]
            ost.col_auth[lkgs_by_i[i]] = True
            metrics.jit_calls += 1
            metrics.jit_tuples += n_by_i[i]
        metrics.jit_host_syncs += 1
        metrics.fused_ticks += 1

        # -- emission accounting + sink download (mirrors _flush_jit_batch) --
        for i in active:
            n = n_by_i[i]
            if n == 0:
                continue
            if i == last:
                if term is None and not eng.collect_sinks:
                    # Terminal output exists but was not fetched.
                    spec = plan.specs[i]
                    # Emission counts still mirror the classic path: a 1:1
                    # terminal operator emits its input count (None-output
                    # sinks like pure counters emit nothing).
                    if _emits(spec):
                        metrics.emitted_tuples += n
                        metrics.sink_tuples += n
                elif term is not None:
                    metrics.emitted_tuples += n
                    metrics.sink_tuples += n
                    ost = jrt._by_op[fops[i]]
                    ok, ov, ot = term
                    ok_np = np.asarray(ok)[:n]
                    ot_np = np.asarray(ot)[:n]
                    if isinstance(ov, dict):
                        ov_np = np.empty(n, dtype=ost.out_dtype)
                        for nm in ost.out_names:
                            ov_np[nm] = np.asarray(ov[nm])[:n]
                    else:
                        ov_np = np.asarray(ov)[:n]
                    metrics.sink_outputs.extend(
                        zip(ok_np.tolist(), ov_np.tolist(), ot_np.tolist())
                    )
            else:
                metrics.emitted_tuples += n

        if drained_kgs:
            np.add.at(eng._cpu_usage, drained_kgs, drained_costs)

        # -- routing replay, in sorted destination-operator order ------------
        producers: dict[int, tuple] = {}
        if src_items:
            producers[fops[0]] = ("source", None)
        for i in active:
            if i != last:
                producers[fops[i + 1]] = ("pairs", i)
        for i in range(last):
            # Downstream of an inactive/empty producer gets no new pending.
            if i not in pairs_dev:
                if fops[i + 1] not in producers:
                    self._pending[fops[i + 1]] = None
        for dop in sorted(producers):
            kind, i = producers[dop]
            if kind == "source":
                self._route_source_items(dop, src_items)
            else:
                pairs = np.asarray(pairs_dev[i])[
                    : plan.nkg[i], : plan.nkg[i + 1]
                ]
                self._replay_route(
                    i, dop, pairs, pend_dev.get(i), src_node_of[i]
                )
        return True

    def _route_source_items(self, dop: int, items: list) -> None:
        """Deliver the source's pass-through batches through the real
        router — identical to ``Engine._flush_outputs`` for one operator."""
        eng = self.engine
        schema = eng._op_schema[dop]
        if len(items) == 1:
            batch, src_kg, src_node = items[0]
            batch = eng._conform_batch(batch, schema)
            n = len(batch[0])
            src_kgs = src_kg
            src_nodes = np.full(n, src_node, dtype=np.int64)
        else:
            batches, kg_t, nd_t = zip(*items)
            batch = concat_batches(
                [eng._conform_batch(b, schema) for b in batches]
            )
            m = len(items)
            lens = np.fromiter((len(b[0]) for b in batches), np.int64, count=m)
            src_kgs = np.concatenate(list(kg_t))
            src_nodes = np.repeat(np.fromiter(nd_t, np.int64, count=m), lens)
        eng._route_batch(dop, batch, src_kgs=src_kgs, src_nodes=src_nodes)

    def _replay_route(self, i, dop, pairs, pend, src_node_of) -> None:
        """Host replay of ``_route_batch`` for a device-routed edge.

        ``pairs[src_lkg, dst_lkg]`` counts this tick's tuples on the edge;
        together with the router table and the producer's drain-node map it
        reproduces every statistic the classic route records — send pairs,
        cross/intra splits, serialization charges, arrivals, admissions —
        and pushes shadow segments whose costs walk the queues' float
        trajectories bit-exactly.
        """
        eng = self.engine
        plan = self.plan
        metrics = eng.metrics
        window = eng.window
        total = int(pairs.sum())
        if total == 0:
            self._pending[dop] = None
            return
        metrics.typed_batches += 1
        base_s, base_d = plan.base[i], plan.base[i + 1]
        nkg_d = plan.nkg[i + 1]
        sl, dl = np.nonzero(pairs)
        cnt = pairs[sl, dl]
        window.record_send_counts(sl + base_s, dl + base_d, cnt)
        dst_nodes_l = eng.router.table[base_d: base_d + nkg_d]
        cross = src_node_of[sl] != dst_nodes_l[dl]
        n_cross = int(cnt[cross].sum())
        if n_cross:
            g = len(eng._arrivals)
            both = np.zeros(g, dtype=np.int64)
            np.add.at(both, sl[cross] + base_s, cnt[cross])
            np.add.at(both, dl[cross] + base_d, cnt[cross])
            eng._cpu_usage += both * eng.ser_cost
            window.kg_usage["network"] += both
        metrics.cross_node_tuples += n_cross
        metrics.intra_node_tuples += total - n_cross
        counts_l = pairs.sum(axis=0)
        nzl = np.flatnonzero(counts_l)
        comp_l = dst_nodes_l[nzl] * nkg_d + nzl
        ordr = np.argsort(comp_l)  # distinct comps: plain argsort is exact
        nzl = nzl[ordr]
        counts = counts_l[nzl]
        ends = np.cumsum(counts)
        starts = ends - counts
        run_nodes = dst_nodes_l[nzl]
        uniq = nzl + base_d
        np.add.at(eng._arrivals, uniq, counts)
        costs = counts * eng._cost_per_tuple[dop]
        self._pending[dop] = _DevicePending(pend[0], pend[1], pend[2], total)
        queues = eng._queues
        if len(uniq) == 1:
            node = int(run_nodes[0])
            queues[node].push_runs(
                dop, None, None, None,
                uniq.tolist(), starts.tolist(), ends.tolist(), costs.tolist(),
                contig=True,
            )
            eng._record_admission(node, int(counts[0]))
            return
        gstarts = np.flatnonzero(
            np.concatenate(([True], run_nodes[1:] != run_nodes[:-1]))
        )
        unodes = run_nodes[gstarts].tolist()
        gends = np.append(gstarts[1:], len(run_nodes))
        kg_l, st_l = uniq.tolist(), starts.tolist()
        en_l, co_l = ends.tolist(), costs.tolist()
        node_counts = np.add.reduceat(counts, gstarts).tolist()
        service_rate = eng.service_rate
        caps = eng._capacity_list
        lat_append = eng.latency.samples.append
        gsl, gel = gstarts.tolist(), gends.tolist()
        for j in range(len(unodes)):
            a, z = gsl[j], gel[j]
            node = unodes[j]
            q = queues[node]
            q.push_runs(
                dop, None, None, None,
                kg_l[a:z], st_l[a:z], en_l[a:z], co_l[a:z],
                contig=True,
            )
            admitted = node_counts[j]
            lat_append(
                (
                    q.cost / max(service_rate * caps[node], 1e-9),
                    admitted if admitted < 16 else 16,
                )
            )

    # ------------------------------------------------------- K-tick scan
    def run_supersteps(self, batches) -> int:
        """Steady-state mode: K source batches through one ``lax.scan``.

        Batch ``t`` is ingested at the source (hash, typed conversion and
        the pass-through hop pre-applied host-side), reaches the first fused
        operator at scan step ``t`` and flows one chain hop per step; the
        host boundary is crossed once for all K ticks
        (``metrics.jit_host_syncs += 1``).  Aggregate statistics (metrics,
        arrivals, usage, send pairs, queue costs, states, sink outputs) are
        folded in exactly; per-admission latency samples and per-tick credit
        checks are not recorded — this is the throughput API, documented in
        ``docs/operator_authoring.md``.

        Requires empty queues (run ``tick()`` until drained first); leaves
        the final in-flight pendings materialized as real segments so
        subsequent classic ticks drain them.  Returns K.
        """
        eng = self.engine
        plan = self.plan
        if plan is None:
            raise RuntimeError("topology is not superstep-fusible")
        if self._pending:
            self.flush_to_host()
        if any(bool(q) for q in eng._queues):
            raise RuntimeError(
                "run_supersteps requires empty queues — tick() until drained"
            )
        if eng.router.has_in_flight() or not bool(eng.alive.all()):
            raise RuntimeError(
                "run_supersteps cannot run during a migration or with dead "
                "nodes — use tick()"
            )
        K = len(batches)
        if K == 0:
            return 0
        topo = eng.topology
        metrics = eng.metrics
        jrt = self._jrt()
        src, fops = plan.source, plan.fops
        op1 = fops[0]
        base1, nkg1 = plan.base[0], plan.nkg[0]
        schema = topo.operators[src].schema
        table = eng.router.table
        num_nodes = eng.num_nodes
        g = len(eng._arrivals)
        # Backpressure guard: the scan performs no per-tick credit checks,
        # so refuse workloads a single node's budget could not absorb.
        nmax = max(len(b[0]) for b in batches)
        worst = nmax * (
            eng._cost_per_tuple[src]
            + sum(eng._cost_per_tuple[o] for o in fops)
        )
        min_budget = eng.service_rate * min(eng._capacity_list)
        if worst >= min_budget:
            raise RuntimeError(
                "run_supersteps: a superstep's worst-case cost "
                f"({worst:.3g}) reaches the smallest node budget "
                f"({min_budget:.3g}); backpressure would bind — use tick()"
            )
        nb1 = jx._bucket(nmax, jx._MIN_TUPLE_BUCKET)
        arrivals_agg = np.zeros(g, dtype=np.int64)
        usage_agg = np.zeros(g, dtype=np.float64)
        pair_src_l: list = []
        pair_dst_l: list = []
        pair_cnt_l: list = []
        # -- stage the source hop (typed conversion, hash, radix sort) ------
        v_names = schema.value.names
        xs_k = np.zeros((K, nb1), dtype=schema.key)
        xs_t = np.zeros((K, nb1), dtype=np.float64)
        if v_names is None:
            xs_v = np.zeros((K, nb1), dtype=schema.value)
        else:
            xs_v = {
                nm: np.zeros((K, nb1), dtype=schema.value[nm]) for nm in v_names
            }
        xs_c = np.zeros((K, nkg1), dtype=np.int64)
        processed = emitted = 0
        cross_total = intra_total = 0
        # -- run layouts: every local kg, comp-sorted (static per table) ----
        perms = []
        for i, op in enumerate(fops):
            nk = plan.nkg[i]
            tl = table[plan.base[i]: plan.base[i] + nk]
            perms.append(np.argsort(tl * nk + np.arange(nk)))
        # -- host routing schedule (static_route chains only) ---------------
        # Every non-terminal fused op declares jit_key_map, so hop i's
        # routing of batch t is a pure function of the staged keys: evaluate
        # the hashes, stable radix permutations and per-edge count matrices
        # here with the host radix sort (~35× faster than XLA's CPU
        # comparison sort) and feed them to the scan as inputs.  Batch t
        # crosses hop i at scan step t+i, so row s of ord_x[i]/cnt_x[i]
        # holds batch s-i's schedule (identity/zeros during pipeline fill).
        static = plan.static_route
        nhops = len(fops) - 1
        if static:
            ns = np.zeros(K, dtype=np.int64)
            ord_x = [
                np.tile(np.arange(nb1, dtype=np.int64), (K, 1))
                for _ in range(nhops)
            ]
            cnt_x = [
                np.zeros((K, plan.nkg[i + 1]), dtype=np.int64)
                for i in range(nhops)
            ]
            pr_sum = [
                np.zeros((plan.nkg[i], plan.nkg[i + 1]), dtype=np.int64)
                for i in range(nhops)
            ]
            pr_last = [np.zeros_like(p) for p in pr_sum]
            pend_cnt = [
                np.zeros(plan.nkg[i + 1], dtype=np.int64) for i in range(nhops)
            ]
        for t, (bk, bv, bt) in enumerate(batches):
            n = len(bk)
            keys = np.asarray(bk, dtype=schema.key)
            values = schema.typed_values(bv)
            ts = np.asarray(bt, dtype=np.float64)
            src_kgs = topo.keygroups_of(src, keys, values)
            np.add.at(
                usage_agg, src_kgs, np.full(n, eng._cost_per_tuple[src])
            )
            np.add.at(arrivals_agg, src_kgs, 1)
            processed += n
            emitted += n  # source pass-through forwards every tuple
            kg1 = topo.keygroups_of(op1, keys, values)
            l1 = kg1 - base1
            comp = table[kg1] * nkg1 + l1
            nbkt = num_nodes * nkg1
            order = bucket_argsort(
                comp.astype(np.int16) if nbkt <= 32767 else comp, nbkt
            )
            np.add.at(arrivals_agg, kg1, 1)
            codes = src_kgs * np.int64(g) + kg1
            ucodes, ucnt = np.unique(codes, return_counts=True)
            usl, udl = ucodes // g, ucodes % g
            pair_src_l.append(usl)
            pair_dst_l.append(udl)
            pair_cnt_l.append(ucnt)
            cr = table[usl] != table[udl]
            ncr = int(ucnt[cr].sum())
            cross_total += ncr
            intra_total += n - ncr
            if ncr:
                both = np.zeros(g, dtype=np.int64)
                np.add.at(both, usl[cr], ucnt[cr])
                np.add.at(both, udl[cr], ucnt[cr])
                usage_agg += both * eng.ser_cost
                eng.window.kg_usage["network"] += both
            metrics.typed_batches += 1
            xs_k[t, :n] = keys[order]
            xs_t[t, :n] = ts[order]
            if v_names is None:
                xs_v[t, :n] = values[order]
            else:
                sv = values[order]
                for nm in v_names:
                    xs_v[nm][t, :n] = sv[nm]
            xs_c[t] = np.bincount(l1, minlength=nkg1)
            if not static:
                continue
            ns[t] = n
            # Walk batch t down the chain: op i's input keys (in its run
            # layout) determine op i's emitted keys via jit_key_map, hence
            # the hop-i routing permutation and counts.  Hops beyond
            # K-1-t never execute inside this scan (the batch is still in
            # flight when it ends), so stop there.
            kcur = xs_k[t, :n]
            ccur = xs_c[t]
            for i in range(min(nhops - 1, K - 1 - t) + 1):
                kout = np.asarray(plan.key_maps[i](kcur))
                nkg_n = plan.nkg[i + 1]
                tl_n = table[plan.base[i + 1]: plan.base[i + 1] + nkg_n]
                dst = _mixed_keygroups(mix32(kout), 0, nkg_n)
                comph = tl_n[dst] * nkg_n + dst
                sent = num_nodes * nkg_n
                oh = bucket_argsort(
                    comph.astype(np.int16) if sent < 32767 else comph,
                    sent + 1,
                )
                src_l = np.repeat(perms[i], ccur[perms[i]])
                pr = np.bincount(
                    src_l * nkg_n + dst, minlength=plan.nkg[i] * nkg_n
                ).reshape(plan.nkg[i], nkg_n)
                pr_sum[i] += pr
                cnext = pr.sum(axis=0)
                ord_x[i][t + i, :n] = oh
                if t + i + 1 <= K - 1:
                    cnt_x[i][t + i + 1] = cnext
                else:
                    # Routed at the final step: stays pending, becomes the
                    # materialized segment counts after the scan.
                    pr_last[i] = pr
                    pend_cnt[i] = cnext
                kcur = kout[oh]
                ccur = cnext
        # K routed batches reach the first fused operator (typed edge).
        metrics.typed_batches += K
        # -- prepare state columns: any kg can receive tuples mid-scan ------
        for i, op in enumerate(fops):
            ost = jrt._by_op[op]
            if ost.fields:
                jrt._prepare_state(ost, np.arange(ost.nkg, dtype=np.int64))
        key = (K, nb1, eng.collect_sinks, eng.router.version)
        scan_fn = self._scan_cache.get(key)
        if scan_fn is None:
            scan_fn = self._build_scan(K, nb1, perms, static)
            self._scan_cache[key] = scan_fn
        states0 = tuple(jrt._by_op[op].cols for op in fops)
        pend0 = []
        for i in range(len(fops) - 1):
            nxt = plan.specs[i].out_schema
            zk = jnp.zeros(nb1, dtype=nxt.key)
            zt = jnp.zeros(nb1, dtype=jnp.float64)
            if nxt.value.names is None:
                zv = jnp.zeros(nb1, dtype=nxt.value)
            else:
                zv = {
                    nm: jnp.zeros(nb1, dtype=nxt.value[nm])
                    for nm in nxt.value.names
                }
            if static:
                pend0.append((zk, zv, zt))
            else:
                pend0.append(
                    (zk, zv, zt, jnp.zeros(plan.nkg[i + 1], dtype=jnp.int64))
                )
        if static:
            xs = (xs_k, xs_v, xs_t, tuple([xs_c] + cnt_x), tuple(ord_x))
        else:
            xs = (xs_k, xs_v, xs_t, xs_c)
        first = key not in self._seen_keys
        if first:
            self._seen_keys.add(key)
            metrics.jit_compiles += 1
            t0 = time.perf_counter()
        (statesK, pendK), ys = scan_fn(states0, tuple(pend0), xs)
        jax.block_until_ready((statesK, pendK, ys))
        if first:
            jrt.compile_seconds += time.perf_counter() - t0
        if static:
            # Routing statistics were computed host-side during staging —
            # the scan only returns states, pendings and sink outputs.
            ys_pairs = term_counts = None
            term_out = ys
        else:
            ys_pairs, term_counts, term_out = ys
        # -- fold the scan outputs into the engine ---------------------------
        metrics.ticks += K
        eng._ticks_this_period += K
        metrics.jit_host_syncs += 1
        metrics.fused_ticks += K
        metrics.jit_calls += K * len(fops)
        last = len(fops) - 1
        for i, op in enumerate(fops):
            ost = jrt._by_op[op]
            ost.cols = statesK[i]
            if i == 0:
                in_agg = xs_c.sum(axis=0)
            elif static:
                in_agg = pr_sum[i - 1].sum(axis=0)
            else:
                in_agg = np.asarray(ys_pairs[i - 1]).sum(axis=(0, 1))
            touched = np.flatnonzero(in_agg)
            ost.col_auth[touched] = True
            drained = int(in_agg.sum())
            if i > 0:
                # The last tick's routed tuples stay queued, undrained.
                if static:
                    lastp = pr_last[i - 1].sum(axis=0)
                else:
                    lastp = np.asarray(ys_pairs[i - 1][K - 1]).sum(axis=0)
                drained -= int(lastp.sum())
                dr = in_agg - lastp
            else:
                dr = in_agg
            idx = np.flatnonzero(dr)
            np.add.at(
                usage_agg, idx + plan.base[i],
                dr[idx] * eng._cost_per_tuple[op],
            )
            processed += drained
            metrics.jit_tuples += drained
            if i == last:
                # A None-output sink (pure counter) emits nothing at all.
                if _emits(plan.specs[i]):
                    if static:
                        sunk = int(ns[: max(K - last, 0)].sum())
                    else:
                        sunk = int(np.asarray(term_counts).sum())
                    metrics.sink_tuples += sunk
                    emitted += sunk
            else:
                if static:
                    emitted += int(pr_sum[i].sum())
                else:
                    emitted += int(np.asarray(ys_pairs[i]).sum())
        metrics.processed_tuples += processed
        metrics.emitted_tuples += emitted
        # edge statistics (aggregate, exact integer sums)
        for i in range(last):
            pr = pr_sum[i] if static else np.asarray(ys_pairs[i]).sum(axis=0)
            sl, dl = np.nonzero(pr)
            if len(sl):
                pair_src_l.append(sl + plan.base[i])
                pair_dst_l.append(dl + plan.base[i + 1])
                pair_cnt_l.append(pr[sl, dl])
                tl_s = table[plan.base[i]: plan.base[i] + plan.nkg[i]]
                tl_d = table[plan.base[i + 1]:
                             plan.base[i + 1] + plan.nkg[i + 1]]
                cr = tl_s[sl] != tl_d[dl]
                cnt = pr[sl, dl]
                ncr = int(cnt[cr].sum())
                cross_total += ncr
                intra_total += int(cnt.sum()) - ncr
                if ncr:
                    both = np.zeros(g, dtype=np.int64)
                    np.add.at(both, sl[cr] + plan.base[i], cnt[cr])
                    np.add.at(both, dl[cr] + plan.base[i + 1], cnt[cr])
                    usage_agg += both * eng.ser_cost
                    eng.window.kg_usage["network"] += both
                np.add.at(
                    arrivals_agg, dl + plan.base[i + 1], pr[sl, dl]
                )
                metrics.typed_batches += K
        metrics.cross_node_tuples += cross_total
        metrics.intra_node_tuples += intra_total
        eng._arrivals += arrivals_agg
        eng._cpu_usage += usage_agg
        if pair_src_l:
            eng.window.record_send_counts(
                np.concatenate(pair_src_l),
                np.concatenate(pair_dst_l),
                np.concatenate(pair_cnt_l),
            )
        # sink outputs, tick order
        if eng.collect_sinks and term_out is not None:
            if static:
                # The sink at step t processes batch t-last (zero during
                # the pipeline-fill steps).
                cnts = np.zeros(K, dtype=np.int64)
                if K > last:
                    cnts[last:] = ns[: K - last]
            else:
                cnts = np.asarray(term_counts)
            ost = jrt._by_op[fops[last]]
            ok_all = np.asarray(term_out[0])
            ot_all = np.asarray(term_out[2])
            ov = term_out[1]
            if isinstance(ov, dict):
                ov_all = np.empty(ok_all.shape, dtype=ost.out_dtype)
                for nm in ost.out_names:
                    ov_all[nm] = np.asarray(ov[nm])
            else:
                ov_all = np.asarray(ov)
            for t in range(K):
                c = int(cnts[t])
                if c:
                    metrics.sink_outputs.extend(
                        zip(
                            ok_all[t, :c].tolist(),
                            ov_all[t, :c].tolist(),
                            ot_all[t, :c].tolist(),
                        )
                    )
        # -- materialize the final pendings as real segments ----------------
        for i in range(last):
            dop = fops[i + 1]
            if static:
                pk, pv, pt = pendK[i]
                counts_l = pend_cnt[i]
            else:
                pk, pv, pt, counts_dev = pendK[i]
                counts_l = np.asarray(counts_dev)
            total = int(counts_l.sum())
            if total == 0:
                continue
            keys_np = np.asarray(pk)
            ts_np = np.asarray(pt)
            if isinstance(pv, dict):
                dt = eng._op_schema[dop].value
                vals_np = np.empty(len(keys_np), dtype=dt)
                for nm in dt.names:
                    vals_np[nm] = np.asarray(pv[nm])
            else:
                vals_np = np.asarray(pv)
            nk = plan.nkg[i + 1]
            perm = perms[i + 1]
            cp = counts_l[perm]
            ends_all = np.cumsum(cp)
            starts_all = ends_all - cp
            nz = cp > 0
            kgs = perm[nz] + plan.base[i + 1]
            starts = starts_all[nz]
            ends = ends_all[nz]
            counts = cp[nz]
            tl_d = table[plan.base[i + 1]: plan.base[i + 1] + nk]
            run_nodes = tl_d[perm[nz]]
            costs = counts * eng._cost_per_tuple[dop]
            for node in np.unique(run_nodes):
                m = run_nodes == node
                eng._queues[int(node)].push_runs(
                    dop, keys_np, vals_np, ts_np,
                    kgs[m].tolist(), starts[m].tolist(), ends[m].tolist(),
                    costs[m].tolist(), contig=True,
                )
        return K

    def _build_scan(self, K: int, nb: int, perms: list, static: bool) -> object:
        """Trace the K-tick scan for the current shapes/table layout.

        ``static`` (chains where every non-terminal operator declares
        ``jit_key_map``): the routing schedule — per-step run counts and
        gather permutations — arrives precomputed in the xs, so each step
        is counts-gather → cumsum → fn_jit → gather, with no device sort
        and no pair-matrix scatter.  Otherwise the body routes on device
        (hash → stable bucketed argsort) and returns the pair matrices as
        scan outputs.
        """
        eng = self.engine
        plan = self.plan
        fops = plan.fops
        nkgs = plan.nkg
        fns = [s.fn_jit for s in plan.specs]
        num_nodes = eng.num_nodes
        collect = eng.collect_sinks
        last = len(fops) - 1
        tables = [
            jnp.asarray(
                eng.router.table[plan.base[i + 1]:
                                 plan.base[i + 1] + plan.nkg[i + 1]]
            )
            for i in range(last)
        ]
        perms_dev = [jnp.asarray(p) for p in perms]

        def body(carry, x):
            states, pends = carry
            if static:
                xk, xv, xt, cnts, ords = x
            else:
                xk, xv, xt, xc = x
            new_states = []
            new_pends = []
            ys_pairs = []
            term_cnt = jnp.zeros((), jnp.int64)
            term_out = None
            for i in range(len(fops)):
                if i == 0:
                    keys, values, ts = xk, xv, xt
                    counts = cnts[0] if static else xc
                elif static:
                    keys, values, ts = pends[i - 1]
                    counts = cnts[i]
                else:
                    keys, values, ts, counts = pends[i - 1]
                perm = perms_dev[i]
                # int32 (run ends are bounded by the batch): XLA:TPU's int64
                # window scan inside the scan body overruns scoped VMEM.
                cp = counts[perm].astype(jnp.int32)
                e_run = jnp.cumsum(cp, dtype=jnp.int32)
                s_run = e_run - cp
                st, out, oc = fns[i](
                    states[i], perm, s_run, e_run, keys, values, ts
                )
                if oc is not None:
                    raise ValueError(
                        "superstep scan requires 1:1 fused operators"
                    )
                new_states.append(st)
                total = e_run[-1]
                if i == last:
                    term_cnt = total.astype(jnp.int64)
                    if collect and out is not None:
                        term_out = out
                    continue
                ok, ov, ot = out
                if static:
                    order = ords[i]
                else:
                    nkg_n = nkgs[i + 1]
                    valid = jnp.arange(nb) < total
                    dst = local_keygroups_jax(ok, nkg_n)
                    node = tables[i][dst]
                    sent = num_nodes * nkg_n
                    comp = jnp.where(valid, node * nkg_n + dst, sent)
                    order = bucket_argsort_jax(comp, sent + 1)
                pk = ok[order]
                pt = ot[order]
                if isinstance(ov, dict):
                    pv = {nm: col[order] for nm, col in ov.items()}
                else:
                    pv = ov[order]
                if static:
                    new_pends.append((pk, pv, pt))
                    continue
                src_l = perm[jx.run_of_tuples(e_run, nb)]
                dcol = jnp.where(valid, dst, nkg_n)
                pr = (
                    jnp.zeros((nkgs[i] + 1, nkg_n + 1), jnp.int64)
                    .at[src_l, dcol]
                    .add(1, mode="drop")
                )
                ys_pairs.append(pr[: nkgs[i], :nkg_n])
                dcounts = pr[: nkgs[i], :nkg_n].sum(axis=0)
                new_pends.append((pk, pv, pt, dcounts))
            if static:
                y = term_out
            else:
                y = (tuple(ys_pairs), term_cnt, term_out)
            return (tuple(new_states), tuple(new_pends)), y

        def run(states0, pends0, xs):
            return jax.lax.scan(body, (states0, pends0), xs)

        return jax.jit(run)


def _emits(spec) -> bool:
    """Whether a fused terminal operator's fn_jit emits outputs.

    Probed statically by tracing against the declared shapes is overkill —
    the convention in this codebase is that counting sinks return
    ``(state, None, None)``; anything with an out_schema or a declared
    ``schema`` emitting body returns arrays.  We probe with jax's shape
    inference once per spec.
    """
    cached = getattr(spec, "_superstep_emits", None)
    if cached is not None:
        return cached

    def probe():
        import numpy as _np

        nkg = spec.num_keygroups
        key_dt = spec.schema.key
        kg = jnp.zeros(1, jnp.int64)
        s = jnp.zeros(1, jnp.int64)
        e = jnp.ones(1, jnp.int64)
        keys = jnp.zeros(1, key_dt)
        ts = jnp.zeros(1, jnp.float64)
        if spec.schema.value.names is None:
            values = jnp.zeros(1, spec.schema.value)
        else:
            values = {
                nm: jnp.zeros(1, spec.schema.value[nm])
                for nm in spec.schema.value.names
            }
        fields = spec.state_schema.fields if spec.state_schema else ()
        state = {
            f.name: jnp.full(nkg + 1, f.init, dtype=f.dtype) for f in fields
        }
        _, out, _ = jax.eval_shape(
            lambda st, k, a, z, ky, v, t: spec.fn_jit(st, k, a, z, ky, v, t),
            state, kg, s, e, keys, values, ts,
        )
        return out is not None

    emits = probe()
    spec._superstep_emits = emits
    return emits

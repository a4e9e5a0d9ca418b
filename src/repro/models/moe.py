"""Mixture-of-Experts FFN with TPU-native sort-based dispatch.

Hardware adaptation (DESIGN.md §2): GPU MoE implementations scatter tokens
with CUDA kernels; the TPU-idiomatic equivalent is sort-based dispatch —
argsort tokens by expert, bucket into per-expert capacity slots, run a
batched (E, C, d) × (E, d, f) einsum on the MXU (repro.kernels.moe_gemm),
and combine with gather + weighted scatter-add.

Distribution: **expert parallelism via partial-manual shard_map** over the
``model`` mesh axis.  Tokens stay replicated across the model axis (their
batch dim is data-sharded by GSPMD's auto mode); each model shard routes all
tokens locally, computes only its E/ep local experts, and one psum over
``model`` combines contributions — the same per-layer collective volume as a
row-parallel dense MLP.  A pure-GSPMD formulation was measured first and
rejected: the global argsort de-shards the token stream and the dispatch
gather crosses the expert-sharded dim, costing ~23× useful FLOPs (§Perf log).

This is also where the paper's technique becomes first-class for the MoE
architectures: experts are *key groups* (repro.core), ``tokens_per_expert``
statistics feed ``gLoad_k``, and the controller's expert-placement decisions
permute the expert→shard assignment (repro/launch/serve.py).

Dispatch is per sequence (vmapped over batch): capacity C = S·k/E · cf per
row; overflow beyond C drops that expert's contribution for the token
(standard capacity-factor semantics); gates renormalized over the top-k.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import (
    _ACTIVATION_RULES,
    ParamSpec,
    current_mesh,
    norm_specs,
)


def moe_specs(cfg: ModelConfig) -> dict[str, ParamSpec]:
    assert cfg.moe is not None
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    specs: dict[str, ParamSpec] = {
        "router": ParamSpec((d, e), ("embed_nofsdp", None)),
        # Expert weights: EP over "model" AND FSDP over "data" — without the
        # data shard, dbrx's expert optimizer state is 80 GB/device.
        "w_up": ParamSpec((e, d, f), ("expert", "embed", None)),
        "w_down": ParamSpec((e, f, d), ("expert", None, "embed")),
        **{f"norm_{k}": v for k, v in norm_specs(cfg.norm_kind, d).items()},
    }
    if cfg.mlp_kind in ("swiglu", "geglu"):
        specs["w_gate"] = ParamSpec((e, d, f), ("expert", "embed", None))
    return specs


def _activation(cfg: ModelConfig, gate: jax.Array, up: jax.Array) -> jax.Array:
    if cfg.mlp_kind == "swiglu":
        return jax.nn.silu(gate) * up
    if cfg.mlp_kind == "geglu":
        return jax.nn.gelu(gate, approximate=True) * up
    return jax.nn.gelu(up, approximate=True)


def _row_dispatch_compute(
    cfg: ModelConfig,
    tokens: jax.Array,  # (S, d) one sequence
    router: jax.Array,
    w_gate: jax.Array | None,  # (E_loc, d, f)
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    lo: jax.Array | int,
    e_local: int,
    capacity: int,
) -> jax.Array:
    """Sort-based dispatch + compute for the experts in [lo, lo+e_local)."""
    moe = cfg.moe
    assert moe is not None
    s, d = tokens.shape
    e, k = moe.num_experts, moe.top_k

    logits = (tokens @ router).astype(jnp.float32)  # (S, E)
    gates, chosen = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(gates, axis=-1)

    flat_e = chosen.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(s, dtype=jnp.int32), k)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # Position within the expert bucket (stable sort ⇒ earlier tokens win).
    pos = jnp.arange(s * k) - jnp.searchsorted(se, se, side="left")

    local = (se >= lo) & (se < lo + e_local) & (pos < capacity)
    n_slots = e_local * capacity
    # Out-of-range writes use index n_slots and are dropped.
    slot = jnp.where(local, (se - lo) * capacity + pos, n_slots)
    used = jnp.zeros((n_slots,), bool).at[slot].set(True, mode="drop")
    gate_slot = jnp.zeros((n_slots,), jnp.float32).at[slot].set(sg, mode="drop")
    tok_slot = jnp.zeros((n_slots,), jnp.int32).at[slot].set(st, mode="drop")

    xin = tokens[tok_slot] * used[:, None].astype(tokens.dtype)
    xin = xin.reshape(e_local, capacity, d)
    up = jnp.einsum("ecd,edf->ecf", xin, w_up)
    if w_gate is not None:
        h = _activation(cfg, jnp.einsum("ecd,edf->ecf", xin, w_gate), up)
    else:
        h = _activation(cfg, up, up)
    expert_out = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(n_slots, d)

    contrib = expert_out.astype(jnp.float32) * (gate_slot * used)[:, None]
    out = jnp.zeros((s, d), jnp.float32).at[tok_slot].add(contrib)
    return out.astype(tokens.dtype)


def _moe_local(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Portable single-shard path (all experts local)."""
    moe = cfg.moe
    b, s, d = x.shape
    capacity = max(int(s * moe.top_k / moe.num_experts * moe.capacity_factor), 1)
    row = lambda tokens: _row_dispatch_compute(
        cfg,
        tokens,
        p["router"],
        p.get("w_gate"),
        p["w_up"],
        p["w_down"],
        lo=0,
        e_local=moe.num_experts,
        capacity=capacity,
    )
    return jax.vmap(row)(x)


def _moe_expert_parallel(
    cfg: ModelConfig, p: dict, x: jax.Array, rules: dict
) -> jax.Array:
    """Expert parallelism: fully-manual shard_map over the whole mesh.

    Layout (no GSPMD freedom — a pure-GSPMD and a partial-manual variant were
    both measured to all-reduce the f32 expert hiddens over data, 2.1 TB/layer
    on dbrx; see §Perf log):

      x        P(batch_axes, None, None)   tokens local to their data shard
      router   P()                          replicated (d×E is tiny)
      w_*      P("model", "data", None)     EP over model + ZeRO over data
      body:    all_gather w over "data"  →  (e_loc, d, f)     [ZeRO gather]
               sort-dispatch + expert einsums for local experts
               psum over "model"            [row-parallel combine]
    """
    moe = cfg.moe
    mesh = current_mesh()
    ep = mesh.shape["model"]
    e_local = moe.num_experts // ep
    s = x.shape[1]
    capacity = max(int(s * moe.top_k / moe.num_experts * moe.capacity_factor), 1)
    batch_axes = rules.get("batch")
    fsdp = rules.get("embed") is not None

    w_gate = p.get("w_gate")
    has_gate = w_gate is not None
    w_spec = P("model", "data" if fsdp else None, None)

    def body(x_, router, *ws):
        if fsdp:
            ws = tuple(jax.lax.all_gather(w, "data", axis=1, tiled=True) for w in ws)
        if has_gate:
            wg, wu, wd = ws
        else:
            wg, (wu, wd) = None, ws
        lo = jax.lax.axis_index("model") * e_local
        row = lambda tokens: _row_dispatch_compute(
            cfg, tokens, router, wg, wu, wd,
            lo=lo, e_local=e_local, capacity=capacity,
        )
        out = jax.vmap(row)(x_)
        return jax.lax.psum(out, "model")

    weights = (w_gate, p["w_up"], p["w_down"]) if has_gate else (p["w_up"], p["w_down"])
    in_specs = (P(batch_axes, None, None), P(), *([w_spec] * len(weights)))
    out_specs = P(batch_axes, None, None)
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=set(mesh.axis_names),
        check_vma=False,
    )
    return mapped(x, p["router"], *weights)


def moe_forward(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    *,
    return_router_stats: bool = False,
):
    """x: (B, S, d) → (B, S, d) [, router stats for the controller]."""
    moe = cfg.moe
    assert moe is not None

    rules = _ACTIVATION_RULES[-1]
    mesh = current_mesh()
    use_ep = (
        rules is not None
        and rules.get("expert") == "model"
        and mesh is not None
        and "model" in mesh.shape
        and moe.num_experts % mesh.shape["model"] == 0
    )
    if use_ep:
        out = _moe_expert_parallel(cfg, p, x, rules)
    else:
        out = _moe_local(cfg, p, x)

    if return_router_stats:
        logits = (x.reshape(-1, x.shape[-1]) @ p["router"]).astype(jnp.float32)
        _, chosen = jax.lax.top_k(logits, moe.top_k)
        tokens_per_expert = jnp.bincount(chosen.reshape(-1), length=moe.num_experts)
        return out, {"tokens_per_expert": tokens_per_expert, "router_logits": logits}
    return out


def load_balancing_loss(
    router_logits: jax.Array, chosen: jax.Array, e: int
) -> jax.Array:
    """Switch-style auxiliary loss (density × mean gate probability)."""
    probs = jax.nn.softmax(router_logits, axis=-1)  # (T, E)
    density = jnp.mean(jax.nn.one_hot(chosen[..., 0], e, dtype=probs.dtype), axis=0)
    return e * jnp.sum(density * probs.mean(axis=0))

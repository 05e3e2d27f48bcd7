"""Mixture-of-Experts FFN with expert parallelism.

Design (TPU-native, see DESIGN.md §4):
  * routing (top-k over router logits) is computed replicated — it is cheap
    (T x E) and must agree across shards;
  * dispatch/compute/combine run inside ``jax.shard_map`` with the expert
    axis sharded over ``model`` (EP): each shard scatters its *local* tokens
    into the capacity buffers of its *local* experts, runs the batched
    expert FFNs, and contributes partial token outputs; a ``psum`` over
    ``model`` combines contributions from experts living on other shards.
    Communication per MoE layer = one all-reduce of the (tokens, d_model)
    output — the TP-style EP layout (bytes independent of top-k).
  * with ``cfg.fsdp`` the expert weights are additionally sharded over
    ``data`` and all-gathered just-in-time inside the block (ZeRO-3).
  * tokens over capacity ``C = ceil(T_local * k / E * capacity_factor)`` are
    dropped (contribute zero), standard capacity-based semantics; the aux
    load-balance loss keeps drop rates low.

The single-device path (``mesh=None`` or |model| == 1) runs the identical
math with all experts local — used by unit tests for parity.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers.ffn import ffn_apply, ffn_init
from repro.layers.linear import dense_init

def _pack_experts(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(E, out, in) latent -> ((E, out, ceil(in/32)) uint32, (E, out) alpha)."""
    e, o, i = w.shape
    bits = (w >= 0).astype(jnp.uint32)
    pad = (-i) % 32
    if pad:
        bits = jnp.pad(bits, ((0, 0), (0, 0), (0, pad)))
    grouped = bits.reshape(e, o, -1, 32)
    lanes = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    packed = jnp.sum(grouped * lanes, axis=-1, dtype=jnp.uint32)
    return packed, jnp.mean(jnp.abs(w), axis=-1)


def moe_init(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.expert_ffn_dim, m.num_experts
    ks = jax.random.split(key, 5)
    std = cfg.init_std
    packed = cfg.quant.packed and "moe" in cfg.quant.targets

    def experts(k, out, inn):
        w = jax.random.normal(k, (e, out, inn), jnp.float32) * std
        if packed:
            pw, alpha = _pack_experts(w)
            return {"packed": pw, "alpha": alpha}
        return w.astype(dtype)

    p = {
        "router": dense_init(ks[0], d, e, std=std, dtype=jnp.float32),
        "w_gate": experts(ks[1], f, d),
        "w_up": experts(ks[2], f, d),
        "w_down": experts(ks[3], d, f),
    }
    if m.num_shared:
        p["shared"] = ffn_init(
            ks[4], d, m.num_shared * f, "swiglu", std=std, dtype=dtype,
            quant=cfg.quant,
        )
    return p


def _route(params: dict, x2: jax.Array, cfg: ModelConfig):
    """x2: (T, d) -> (idx (T,K), gates (T,K) f32, aux metrics)."""
    m = cfg.moe
    logits = (x2.astype(jnp.float32) @ params["router"]["w"].T.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, idx = jax.lax.top_k(logits, m.top_k)
    gates = jax.nn.softmax(top_vals, axis=-1)

    # Switch-style load-balance loss + router z-loss.
    e = m.num_experts
    me = jnp.mean(probs, axis=0)                                   # (E,)
    assign = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1)       # (T, E)
    ce = jnp.mean(assign, axis=0) / m.top_k
    aux = e * jnp.sum(me * ce) * m.aux_loss
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_loss
    return idx, gates, aux + z


def _packed_expert_mm(x: jax.Array, w: dict) -> jax.Array:
    """Batched XNOR-popcount contraction against packed expert weights.

    x: (E, C, K) real; w["packed"]: (E, O, Kw) uint32; -> (E, C, O).
    The xor/popcount broadcast stays inside one XLA reduce fusion at decode
    capacities (the prefill-scale variant belongs in the Pallas kernel — see
    EXPERIMENTS.md §Perf on the fusion-scale limit).
    """
    e, c, k = x.shape
    beta = jnp.mean(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    bits = (x >= 0).astype(jnp.uint32)
    pad = (-k) % 32
    if pad:
        bits = jnp.pad(bits, ((0, 0), (0, 0), (0, pad)))
    lanes = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    xp = jnp.sum(bits.reshape(e, c, -1, 32) * lanes, axis=-1, dtype=jnp.uint32)
    agree = jax.lax.population_count(xp[:, :, None, :] ^ ~w["packed"][:, None, :, :])
    acc = jnp.sum(agree.astype(jnp.int32), axis=-1)          # (E, C, O)
    kw = xp.shape[-1]
    dot = (2 * acc - 2 * kw * 32 + k).astype(jnp.float32)
    return (dot * w["alpha"][:, None, :] * beta).astype(x.dtype)


def _expert_compute(
    x2: jax.Array,
    idx: jax.Array,
    gates: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    e_lo,
    num_experts: int,
    capacity: int,
) -> jax.Array:
    """Capacity dispatch -> batched expert SwiGLU -> combine (local experts).

    x2: (T, d); idx/gates: (T, K); w_*: (E_loc, ...); ``e_lo``: first local
    expert id.  Returns this shard's partial output (T, d).
    """
    t, d = x2.shape
    k = idx.shape[1]
    e_loc = (w_gate["packed"] if isinstance(w_gate, dict) else w_gate).shape[0]
    dtype = x2.dtype

    slot_expert = idx.reshape(-1)                   # (T*K,) expert id per slot
    slot_gate = gates.reshape(-1)
    slot_token = jnp.repeat(jnp.arange(t), k)

    # position of each slot within its expert's capacity buffer (global order,
    # identical on every shard)
    onehot = jax.nn.one_hot(slot_expert, num_experts, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(t * k), slot_expert]
    keep = pos < capacity
    local = keep & (slot_expert >= e_lo) & (slot_expert < e_lo + e_loc)

    flat_idx = jnp.where(local, (slot_expert - e_lo) * capacity + pos, 0)
    contrib = jnp.where(local[:, None], x2[slot_token], 0).astype(dtype)
    buf = jnp.zeros((e_loc * capacity, d), dtype).at[flat_idx].add(contrib)
    buf = buf.reshape(e_loc, capacity, d)

    if isinstance(w_gate, dict):  # N2Net packed experts (XNOR-popcount FFN)
        g = _packed_expert_mm(buf, w_gate)
        u = _packed_expert_mm(buf, w_up)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
        down = _packed_expert_mm(h, w_down).astype(dtype)
    else:
        g = jnp.einsum("ecd,efd->ecf", buf, w_gate.astype(dtype))
        u = jnp.einsum("ecd,efd->ecf", buf, w_up.astype(dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
        down = jnp.einsum("ecf,edf->ecd", h, w_down.astype(dtype))

    slot_out = down.reshape(e_loc * capacity, d)[flat_idx]
    slot_out = jnp.where(local[:, None], slot_out, 0)
    slot_out = slot_out * slot_gate[:, None].astype(dtype)
    return slot_out.reshape(t, k, d).sum(axis=1)


def moe_apply(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> tuple[jax.Array, jax.Array]:
    """MoE FFN.  x: (B, S, d) -> (y, aux_loss)."""
    m = cfg.moe
    b, s, d = x.shape
    x2 = x.reshape(-1, d)
    idx, gates, aux = _route(params, x2, cfg)

    use_shard_map = (
        mesh is not None
        and "model" in mesh.axis_names
        and mesh.shape["model"] > 1
    )

    if not use_shard_map:
        t = x2.shape[0]
        capacity = max(1, math.ceil(t * m.top_k / m.num_experts * m.capacity_factor))
        y = _expert_compute(
            x2, idx, gates, params["w_gate"], params["w_up"], params["w_down"],
            e_lo=0, num_experts=m.num_experts, capacity=capacity,
        )
    else:
        y = _moe_shard_map(params, x2, idx, gates, cfg, mesh, (b, s))

    y = y.reshape(b, s, d)
    if m.num_shared:
        y = y + ffn_apply(params["shared"], x, cfg)
    return y, aux


def _moe_shard_map(params, x2, idx, gates, cfg: ModelConfig, mesh, bs) -> jax.Array:
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    model_size = mesh.shape["model"]
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]

    t_global = x2.shape[0]
    t_local = t_global // dp_size if t_global % dp_size == 0 else t_global
    capacity = max(1, math.ceil(t_local * m.top_k / m.num_experts * m.capacity_factor))
    e_per_shard = m.num_experts // model_size

    tok_spec = P(dp_axes if t_global % dp_size == 0 else None)
    packed = isinstance(params["w_gate"], dict)

    def wspec(fsdp_dim: int):
        if packed:  # packed experts fit without FSDP: model-sharded only
            return {"packed": P("model", None, None), "alpha": P("model", None)}
        spec = [None, None, None]
        spec[0] = "model"
        if cfg.fsdp:
            spec[fsdp_dim] = "data"
        return P(*spec)

    def block(x_loc, idx_loc, gates_loc, wg, wu, wd):
        if cfg.fsdp and not packed:
            wg = jax.lax.all_gather(wg, "data", axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=1, tiled=True)
        e_lo = jax.lax.axis_index("model") * e_per_shard
        part = _expert_compute(
            x_loc, idx_loc, gates_loc, wg, wu, wd,
            e_lo=e_lo, num_experts=m.num_experts, capacity=capacity,
        )
        return jax.lax.psum(part, "model")

    return jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(
            P(tok_spec[0], None),
            P(tok_spec[0], None),
            P(tok_spec[0], None),
            wspec(2),
            wspec(2),
            wspec(1),
        ),
        out_specs=P(tok_spec[0], None),
        check_vma=False,
    )(x2, idx, gates, params["w_gate"], params["w_up"], params["w_down"])

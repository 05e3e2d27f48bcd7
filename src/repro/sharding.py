"""Sharding rules: param/cache/batch pytrees -> PartitionSpecs.

Mesh axes: ``(pod, data, model)`` multi-pod or ``(data, model)`` single-pod.
  * batch dims shard over (pod, data) — pure DP across pods;
  * projection output/input dims shard over ``model`` (TP); MoE experts shard
    over ``model`` (EP); vocab shards over ``model``;
  * with ``cfg.fsdp`` the *other* weight dim additionally shards over
    ``data`` (ZeRO-3; GSPMD inserts the per-layer all-gathers).

Rules are matched on the param-tree path *suffix*; stacked leading dims
(scan-over-layers) are absorbed automatically (a rule shorter than the leaf
rank is left-padded with ``None``).  Any rule axis whose dimension is not
divisible by the mesh axis size is dropped (replicated) — recorded by
``explain()`` so the dry-run log shows what didn't shard.
"""
from __future__ import annotations

import re
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

# (path-suffix regex, base spec, fsdp spec) — first match wins.
# embed/table falls back to d_model sharding when the vocab doesn't divide
# (see param_specs) so odd vocabs (50280, 73448, 92553, ...) never replicate
# a multi-hundred-MB table.
_PARAM_RULES: list[tuple[str, tuple, tuple]] = [
    (r"embed/table$",            ("model", None),        ("model", "data")),
    (r"moe/router/w$",           ("model", None),        ("model", None)),
    (r"(wq|wk|wv|wq_a|wq_b|wkv_a|wkv_b)/w$", ("model", None), ("model", "data")),
    (r"wo/w$",                   (None, "model"),        ("data", "model")),
    # packed BNN weights: out-channel dim over model (Kw packing keeps the
    # contraction dim word-aligned, so it stays unsharded)
    (r"w_packed$",               ("model", None),        ("model", None)),
    (r"/alpha$",                 ("model",),             ("model",)),
    (r"(w_gate|w_up|w_in)/w$",   ("model", None),        ("model", "data")),
    (r"(w_down|w_out)/w$",       (None, "model"),        ("data", "model")),
    (r"moe/w_(gate|up|down)/packed$", ("model", None, None), ("model", None, None)),
    (r"moe/w_(gate|up|down)/alpha$",  ("model", None),       ("model", None)),
    (r"moe/(w_gate|w_up)$",      ("model", None, None),  ("model", None, "data")),
    (r"moe/w_down$",             ("model", None, None),  ("model", "data", None)),
    (r"(z_proj|x_proj|dt_proj)/w$", ("model", None),     ("model", "data")),
    (r"bc_proj/w$",              (None, None),           (None, "data")),
    (r"out_proj/w$",             (None, "model"),        ("data", "model")),
    (r"conv_x_[wb]$",            None,                   None),  # last-dim model
    (r"conv_bc_[wb]$",           None,                   None),
    (r"(A_log|D|dt_bias)$",      ("model",),             ("model",)),
    (r"mamba/norm/scale$",       ("model",),             ("model",)),
    (r".*scale$",                (None,),                (None,)),
    (r".*bias$",                 (None,),                (None,)),
]

_CONV_RULES = {
    "conv_x_w": (None, "model"),
    "conv_x_b": ("model",),
    "conv_bc_w": (None, None),
    "conv_bc_b": (None,),
}


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
    return "/".join(parts)


def _fit_spec(base: tuple, shape: tuple, mesh: Mesh, log: list, path: str) -> P:
    """Left-pad for stacked dims; drop non-divisible axes."""
    pad = len(shape) - len(base)
    if pad < 0:
        base = base[-len(shape):] if len(shape) else ()
        pad = 0
    spec = [None] * pad + list(base)
    for i, ax in enumerate(spec):
        if ax is None:
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        if any(a not in mesh.axis_names for a in axes):
            spec[i] = None
            continue
        size = int(np.prod([mesh.shape[a] for a in axes]))
        if shape[i] % size != 0:
            log.append(f"{path}: dim {i} ({shape[i]}) % {ax}({size}) != 0 -> replicated")
            spec[i] = None
    return P(*spec)


def param_specs(
    cfg: ModelConfig, params: Any, mesh: Mesh, *, log: Optional[list] = None
) -> Any:
    """PartitionSpec tree matching a parameter pytree (arrays or SDS)."""
    log = log if log is not None else []

    def leaf_spec(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        if ps.endswith("embed/table"):
            # vocab over model; odd vocabs REPLICATE (measured: d_model-
            # sharding the table turns the unembed into a TP matmul whose
            # (B,S,V) f32 partial-sum all-reduce costs far more than the
            # 200-400MB of replicated table; uneven vocab sharding is
            # rejected by pjit input shardings).
            return _fit_spec(
                ("model", "data") if cfg.fsdp and "data" in mesh.axis_names
                else ("model", None),
                shape, mesh, log, ps,
            )
        for name, rule in _CONV_RULES.items():
            if ps.endswith(name):
                return _fit_spec(rule, shape, mesh, log, ps)
        for pat, base, fsdp in _PARAM_RULES:
            if base is None:
                continue
            if re.search(pat, ps):
                rule = fsdp if cfg.fsdp else base
                # FSDP needs the data axis present
                if cfg.fsdp and "data" not in mesh.axis_names:
                    rule = base
                return _fit_spec(rule, shape, mesh, log, ps)
        log.append(f"{ps}: no rule -> replicated")
        return P()

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def dp_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _dp_if_divisible(mesh: Mesh, n: int):
    axes = dp_axes(mesh)
    size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    return axes if size > 1 and n % size == 0 else None


def batch_specs(cfg: ModelConfig, batch: Any, mesh: Mesh) -> Any:
    """Input batch specs: leading batch dim over (pod, data) when divisible."""

    def leaf_spec(leaf):
        b = leaf.shape[0] if leaf.ndim else 1
        lead = _dp_if_divisible(mesh, b)
        return P(lead, *([None] * (leaf.ndim - 1)))

    return jax.tree_util.tree_map(leaf_spec, batch)


def cache_specs(cfg: ModelConfig, cache: Any, mesh: Mesh) -> Any:
    """Decode-cache specs.

    Layer-stacked arrays (L, B, S, ...): batch over (pod,data) when divisible,
    else the *sequence* axis shards over data (long-context, batch=1); heads /
    latent dims over model when divisible.
    """
    model_ok = "model" in mesh.axis_names

    def leaf_spec(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        if leaf.ndim == 0:  # index scalar
            return P()
        spec = [None] * leaf.ndim
        lead = _dp_if_divisible(mesh, shape[1]) if leaf.ndim > 1 else None
        if leaf.ndim > 1:
            spec[1] = lead
        if re.search(r"(^|/)(k|v)$", ps) and leaf.ndim == 5:
            # (L, B, S, KVH, D): prefer KVH over model; fall back to S over
            # model when KV heads don't divide (extreme GQA: kv=2..8 vs 16
            # model shards would otherwise replicate a 100s-of-GiB cache).
            if lead is None and shape[2] % mesh.shape.get("data", 1) == 0:
                spec[2] = "data"
            if model_ok and shape[3] % mesh.shape["model"] == 0:
                spec[3] = "model"
            elif model_ok and spec[2] is None and shape[2] % mesh.shape["model"] == 0:
                spec[2] = "model"
        elif re.search(r"c_kv$|k_rope$", ps) and leaf.ndim == 4:
            # (L, B, S, R): MLA latents have no head axis, so decode's
            # natural parallelism is SEQUENCE over model — scores and the
            # softmax partials stay shard-local (tiny psum of (B,H,1,R)
            # outputs) instead of rank-sharded scores that all-reduce a
            # (B,H,1,S) tensor per layer.
            if lead is None and shape[2] % mesh.shape.get("data", 1) == 0:
                spec[2] = "data"
            if model_ok and spec[2] is None and shape[2] % mesh.shape["model"] == 0:
                spec[2] = "model"
            elif model_ok and shape[3] % mesh.shape["model"] == 0:
                spec[3] = "model"
        elif re.search(r"(^|/)h$", ps) and leaf.ndim == 5:
            # SSD state (L, B, H, N, P)
            if model_ok and shape[2] % mesh.shape["model"] == 0:
                spec[2] = "model"
        elif "conv_x" in ps and leaf.ndim == 4:
            if model_ok and shape[3] % mesh.shape["model"] == 0:
                spec[3] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(leaf_spec, cache)


def to_named(tree: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def activation_spec(mesh: Mesh, batch: int) -> P:
    """(B, S, d) activations: batch over (pod, data)."""
    return P(_dp_if_divisible(mesh, batch), None, None)


# ---------------------------------------------------------------------------
# Fleet sharding (dataplane): streams over a 1-D device mesh
# ---------------------------------------------------------------------------

def fleet_mesh(num_devices: Optional[int] = None) -> Mesh:
    """1-D device mesh over a ``fleet`` axis for batched stream serving.

    The dataplane's fleet executor (``repro.dataplane.fleet``) vmaps one
    compiled program over the leading stream axis of ``(streams, chunk,
    bits)`` blocks; this mesh is what ``shard_streams`` splits that axis
    over, one group of simulated switches per device.  Defaults to every
    local device.
    """
    devices = jax.devices()
    n = len(devices) if num_devices is None else num_devices
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"need 1..{len(devices)} local devices, got {num_devices}"
        )
    return Mesh(np.asarray(devices[:n]), ("fleet",))


def shard_streams(fn, mesh: Mesh):
    """Wrap a ``(streams, ...) -> (streams, ...)`` batched function in
    ``shard_map`` over the ``fleet`` axis: each device independently runs
    ``fn`` on its local slice of streams (no collectives — streams never
    communicate, exactly like the independent switches they simulate)."""
    spec = P("fleet")
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )

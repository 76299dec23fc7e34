"""Mixture-of-Experts FFN with top-k routing (GShard / DeepSeekMoE family).

Two dispatch implementations with identical math:

* ``einsum``  — classic GShard one-hot dispatch (T,E,C). Exact reference,
                used for smoke tests, decode (tiny T) and small models.
* ``sorted``  — sort-based dispatch into an (E, C, d) buffer. O(T·k) index
                work + dense expert matmuls, no (T,E,C) tensor. This is the
                path production dry-runs lower; combined with expert sharding
                over the "model" mesh axis, GSPMD turns the scatter/gather
                into the expected all_to_all pattern.

Arctic's "dense residual" (parallel always-on FFN) and DeepSeek's shared
experts are expressed at the transformer layer level (models/transformer.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.sharding import constrain

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                      # per-expert hidden
    n_shared: int = 0              # always-on shared experts (DeepSeek)
    capacity_factor: float = 1.25
    dispatch: str = "sorted"       # "sorted" | "einsum" | "sharded"
    router_noise: float = 0.0


def moe_init(key: jax.Array, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> Params:
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    e = cfg.n_experts
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(cfg.d_ff)
    p = {
        "router": jax.random.normal(kr, (d_model, e), jnp.float32) * s_in,
        "w_gate": jax.random.normal(kg, (e, d_model, cfg.d_ff), dtype) * s_in,
        "w_up": jax.random.normal(ku, (e, d_model, cfg.d_ff), dtype) * s_in,
        "w_down": jax.random.normal(kd, (e, cfg.d_ff, d_model), dtype) * s_out,
    }
    if cfg.n_shared:
        k1, k2, k3 = jax.random.split(ks, 3)
        dff_s = cfg.n_shared * cfg.d_ff
        p["shared"] = {
            "w_gate": jax.random.normal(k1, (d_model, dff_s), dtype) * s_in,
            "w_up": jax.random.normal(k2, (d_model, dff_s), dtype) * s_in,
            "w_down": jax.random.normal(k3, (dff_s, d_model), dtype) * s_out,
        }
    return p


def _router(params: Params, x: jax.Array, cfg: MoEConfig
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (gates (T,k) f32, experts (T,k) int32, aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ params["router"])          # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss
    e = cfg.n_experts
    me = jnp.mean(probs, axis=0)                                  # mean prob
    ce = jnp.mean(jax.nn.one_hot(experts[:, 0], e), axis=0)       # top-1 load
    aux = e * jnp.sum(me * ce)
    return gates, experts.astype(jnp.int32), aux


def _expert_ffn(w_gate, w_up, w_down, x):
    """Batched SwiGLU over experts: x (E,C,d) -> (E,C,d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", x, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _moe_einsum(params: Params, x: jax.Array, cfg: MoEConfig
                ) -> Tuple[jax.Array, jax.Array]:
    t, d = x.shape
    e = cfg.n_experts
    cap = max(1, int(math.ceil(t * cfg.top_k * cfg.capacity_factor / e)))
    gates, experts, aux = _router(params, x, cfg)                 # (T,k)
    onehot_e = jax.nn.one_hot(experts, e, dtype=jnp.int32)        # (T,k,E)
    # position within expert = number of earlier (token, choice) hits
    flat = onehot_e.reshape(t * cfg.top_k, e)
    before = jnp.cumsum(flat, axis=0) - flat                      # exclusive count
    pos = jnp.sum(before.reshape(t, cfg.top_k, e) * onehot_e, axis=-1)  # (T,k)
    keep = pos < cap
    onehot_c = jax.nn.one_hot(jnp.clip(pos, 0, cap - 1), cap,
                              dtype=x.dtype) * keep[..., None].astype(x.dtype)
    disp = jnp.einsum("tke,tkc->tec", onehot_e.astype(x.dtype), onehot_c)
    xe = jnp.einsum("td,tec->ecd", x, disp)
    ye = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"], xe)
    comb = jnp.einsum("tke,tkc,tk->tec", onehot_e.astype(x.dtype), onehot_c,
                      gates.astype(x.dtype))
    y = jnp.einsum("ecd,tec->td", ye, comb)
    return y, aux


def _moe_sorted(params: Params, x: jax.Array, cfg: MoEConfig,
                capacity: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Sort-based dispatch: scatter tokens into an (E, C, d) buffer."""
    t, d = x.shape
    e = cfg.n_experts
    cap = capacity or max(1, int(math.ceil(t * cfg.top_k * cfg.capacity_factor / e)))
    gates, experts, aux = _router(params, x, cfg)
    flat_e = experts.reshape(-1)                                   # (T*k,)
    flat_g = gates.reshape(-1)
    flat_t = jnp.arange(t).repeat(cfg.top_k)
    order = jnp.argsort(flat_e)                                    # group by expert
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # position within expert group
    pos = jnp.arange(t * cfg.top_k) - jnp.searchsorted(se, se, side="left")
    ok = pos < cap
    buf_idx = se * cap + jnp.where(ok, pos, 0)
    buffer = jnp.zeros((e * cap, d), x.dtype)
    buffer = buffer.at[buf_idx].add(jnp.where(ok[:, None], x[st], 0))
    buffer = constrain(buffer.reshape(e, cap, d), "experts", None, None)
    ye = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"], buffer)
    ye = constrain(ye, "experts", None, None).reshape(e * cap, d)
    contrib = jnp.where(ok[:, None], ye[buf_idx] * sg[:, None].astype(x.dtype), 0)
    y = jnp.zeros((t, d), x.dtype).at[st].add(contrib)
    return y, aux


def _local_dispatch_ffn(w_gate, w_up, w_down, router, x_loc, cfg: MoEConfig,
                        model_axis: str, fsdp_axis: Optional[str],
                        all_axes: Optional[tuple] = None):
    """Per-device MoE body under shard_map (GShard expert parallelism).

    x_loc: (t_loc, d) local tokens. Experts are sharded over ``model_axis``
    (E_loc per device) with d_ff FSDP-sharded over ``fsdp_axis``. Dispatch:
    local top-k → local capacity buffers (E, C_loc, d) → all_to_all over the
    model axis → expert FFN → all_to_all back → weighted combine.
    Capacity is per-source-device (C_loc = t_loc·k·cf/E), the standard
    hierarchical GShard behaviour.
    """
    t_loc, d = x_loc.shape
    e = cfg.n_experts
    m = jax.lax.axis_size(model_axis)
    e_loc = e // m
    cap = max(1, int(math.ceil(t_loc * cfg.top_k * cfg.capacity_factor / e)))

    # router (replicated weights) ------------------------------------------
    logits = x_loc.astype(jnp.float32) @ router                  # (t,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    experts = experts.astype(jnp.int32)
    # aux loss from global statistics (psum over every mesh axis)
    me_loc = jnp.sum(probs, axis=0)
    ce_loc = jnp.sum(jax.nn.one_hot(experts[:, 0], e), axis=0)
    cnt = jnp.float32(t_loc)
    if all_axes is None:
        all_axes = (model_axis,) if fsdp_axis is None else (fsdp_axis, model_axis)
    me = jax.lax.psum(me_loc, all_axes)
    ce = jax.lax.psum(ce_loc, all_axes)
    n_tok = jax.lax.psum(cnt, all_axes)
    aux = e * jnp.sum((me / n_tok) * (ce / n_tok))

    # local dispatch into (E, cap, d) --------------------------------------
    onehot = jax.nn.one_hot(experts, e, dtype=jnp.int32)          # (t,k,E)
    flat = onehot.reshape(t_loc * cfg.top_k, e)
    before = jnp.cumsum(flat, axis=0) - flat
    pos = jnp.sum(before.reshape(t_loc, cfg.top_k, e) * onehot, -1)  # (t,k)
    keep = pos < cap
    flat_e = experts.reshape(-1)
    flat_t = jnp.arange(t_loc).repeat(cfg.top_k)
    flat_p = jnp.where(keep.reshape(-1), pos.reshape(-1), 0)
    ok = keep.reshape(-1)
    buf = jnp.zeros((e, cap, d), x_loc.dtype)
    buf = buf.at[flat_e, flat_p].add(
        jnp.where(ok[:, None], x_loc[flat_t], 0))

    # all_to_all: expert shards to their owners -----------------------------
    buf = buf.reshape(m, e_loc, cap, d)
    buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=0,
                             tiled=False)                         # (m, e_loc, cap, d)
    xe = buf.transpose(1, 0, 2, 3).reshape(e_loc, m * cap, d)

    # expert FFN (FSDP all-gather of the local expert weights) -------------
    if fsdp_axis is not None:
        w_gate = jax.lax.all_gather(w_gate, fsdp_axis, axis=2, tiled=True)
        w_up = jax.lax.all_gather(w_up, fsdp_axis, axis=2, tiled=True)
        w_down = jax.lax.all_gather(w_down, fsdp_axis, axis=1, tiled=True)
    ye = _expert_ffn(w_gate, w_up, w_down, xe)                    # (e_loc, m*cap, d)

    # return trip ------------------------------------------------------------
    ye = ye.reshape(e_loc, m, cap, d).transpose(1, 0, 2, 3)       # (m, e_loc, cap, d)
    ye = jax.lax.all_to_all(ye, model_axis, split_axis=0, concat_axis=0,
                            tiled=False)
    ye = ye.reshape(e, cap, d)

    # combine ---------------------------------------------------------------
    contrib = jnp.where(ok[:, None],
                        ye[flat_e, flat_p] *
                        gates.reshape(-1)[:, None].astype(x_loc.dtype), 0)
    y = jnp.zeros((t_loc, d), x_loc.dtype).at[flat_t].add(contrib)
    return y, aux


def _moe_shard_map(params: Params, x: jax.Array, cfg: MoEConfig,
                   mesh) -> Tuple[jax.Array, jax.Array]:
    """shard_map expert-parallel MoE. x: (B, S, d) with B|data-axes, S|model."""
    from jax.sharding import PartitionSpec as P
    from repro.runtime.sharding import data_axes

    dp = data_axes(mesh)
    fsdp = "data"
    b, s, d = x.shape

    all_axes = tuple(mesh.axis_names)

    def body(router, w_gate, w_up, w_down, x_blk):
        bb, ss, dd = x_blk.shape
        y, aux = _local_dispatch_ffn(w_gate, w_up, w_down, router,
                                     x_blk.reshape(bb * ss, dd), cfg,
                                     model_axis="model", fsdp_axis=fsdp,
                                     all_axes=all_axes)
        return y.reshape(bb, ss, dd), aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P("model", None, fsdp), P("model", None, fsdp),
                  P("model", fsdp, None), P(dp, "model", None)),
        out_specs=(P(dp, "model", None), P()),
    )(params["router"], params["w_gate"], params["w_up"], params["w_down"], x)
    return y, aux


def moe_apply(params: Params, x: jax.Array, cfg: MoEConfig
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (..., d) -> (moe_out, aux_loss). Shared experts included if any.

    Path selection: "sharded" uses the shard_map expert-parallel dispatch
    whenever an activation mesh is installed and shapes divide it (falling
    back to the local sorted dispatch otherwise — e.g. decode's single-token
    steps); "einsum" is the exact GShard reference.
    """
    from repro.runtime.sharding import data_axes, get_activation_mesh

    shape = x.shape
    if cfg.dispatch == "sharded" and x.ndim == 3:
        mesh = get_activation_mesh()
        if mesh is not None:
            b, s, _ = shape
            dp_size = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
            m_size = mesh.shape["model"]
            if (b % dp_size == 0 and s % m_size == 0
                    and cfg.n_experts % m_size == 0):
                y, aux = _moe_shard_map(params, x, cfg, mesh)
                if cfg.n_shared and "shared" in params:
                    sp = params["shared"]
                    flat = x.reshape(-1, shape[-1])
                    ys = (jax.nn.silu(flat @ sp["w_gate"]) *
                          (flat @ sp["w_up"])) @ sp["w_down"]
                    y = y + ys.reshape(shape)
                return y, aux
    flat = x.reshape(-1, shape[-1])
    if cfg.dispatch == "einsum":
        y, aux = _moe_einsum(params, flat, cfg)
    else:
        y, aux = _moe_sorted(params, flat, cfg)
    if cfg.n_shared and "shared" in params:
        sp = params["shared"]
        y = y + (jax.nn.silu(flat @ sp["w_gate"]) * (flat @ sp["w_up"])) @ sp["w_down"]
    return y.reshape(shape), aux

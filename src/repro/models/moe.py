"""Mixture-of-Experts FFN with capacity-bounded dispatch (GShard-style).

Token movement can't be expressed as a GSPMD annotation, so the MoE FFN is a
``shard_map`` *island* with explicit collectives (DESIGN.md §5):

* **EP mode** (``n_experts % model_size == 0``, e.g. DeepSeekMoE 64e/16):
  tokens are locally sorted by expert, packed into a capacity-bounded
  ``[E, C, D]`` buffer, exchanged with a single ``all_to_all`` over the
  ``model`` axis, processed by the owning shard (whose expert weights are
  FSDP-gathered over ``(pod, data)``), and exchanged back. Per-device
  dispatch work is O(local tokens); the only cross-device traffic is the
  two all_to_alls (≈ topk/E·capacity_factor of the activations).

* **TP mode** (``n_experts < model_size``, e.g. Mixtral 8e/16): every model
  shard processes all experts on an F/model_size weight slice and the down
  projection is psum-reduced. Expert weights are FSDP-gathered one expert
  at a time to bound the transient.

Tokens over capacity are dropped (the GShard convention); the router is
top-k with renormalised probabilities plus the standard load-balance aux
loss.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import ArchConfig
from repro.distributed import sharding as sh

__all__ = ["init_moe_params", "moe_ffn", "replace_router"]


def init_moe_params(cfg: ArchConfig, key: jax.Array) -> dict[str, Any]:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    std = 0.02
    pdt = cfg.param_dtype
    params = {
        "router": (jax.random.normal(ks[0], (d, e)) * std).astype(jnp.float32),
        "w1": (jax.random.normal(ks[1], (e, d, f)) * std).astype(pdt),
        "w3": (jax.random.normal(ks[2], (e, d, f)) * std).astype(pdt),
        "w2": (jax.random.normal(ks[3], (e, f, d)) * std).astype(pdt),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        k1, k2, k3 = jax.random.split(ks[4], 3)
        params["shared"] = {
            "w1": (jax.random.normal(k1, (d, fs)) * std).astype(pdt),
            "w3": (jax.random.normal(k2, (d, fs)) * std).astype(pdt),
            "w2": (jax.random.normal(k3, (fs, d)) * std).astype(pdt),
        }
    return params


def replace_router(moe_params: dict[str, Any], router_w) -> dict[str, Any]:
    """Copy of the MoE param dict with the router swapped in.

    The install seam ``repro.vq.router`` uses: accepts a per-layer ``[d, E]``
    matrix (broadcast over the leading axis when the params are a scanned
    ``[L, d, E]`` stack) or a full-shape replacement, and rejects shape
    mismatches and non-finite values eagerly — a NaN router column would
    silently flatten the softmax over every expert."""
    old = moe_params["router"]
    w = jnp.asarray(router_w, old.dtype)
    if w.shape != old.shape:
        if old.ndim == w.ndim + 1 and w.shape == old.shape[1:]:
            w = jnp.broadcast_to(w[None], old.shape)
        else:
            raise ValueError(
                f"router shape {w.shape} incompatible with existing {old.shape}"
            )
    if not bool(np.isfinite(np.asarray(w)).all()):
        raise ValueError("router contains non-finite values")
    return {**moe_params, "router": w}


def _dispatch(x_flat, probs, topk_idx, e, cap):
    """Pack top-k (token, expert) pairs into a capacity-bounded [E, C, D] buffer.

    Returns (buffer, sorted_tok, sorted_e, slot, keep, gate_sorted).
    """
    t, k = topk_idx.shape
    ids = topk_idx.reshape(-1)  # [T*k]
    src = jnp.repeat(jnp.arange(t), k)
    gate = probs.reshape(-1)
    order = jnp.argsort(ids, stable=True)
    sorted_e = ids[order]
    sorted_tok = src[order]
    gate_sorted = gate[order]
    counts = jax.ops.segment_sum(jnp.ones_like(sorted_e), sorted_e, num_segments=e)
    starts = jnp.cumsum(counts) - counts
    slot = jnp.arange(t * k) - starts[sorted_e]
    keep = slot < cap
    slot_safe = jnp.where(keep, slot, cap)  # cap = out-of-range ⇒ dropped
    buf = jnp.zeros((e, cap + 1, x_flat.shape[-1]), x_flat.dtype)
    buf = buf.at[sorted_e, slot_safe].set(x_flat[sorted_tok], mode="drop")
    return buf[:, :cap], sorted_tok, sorted_e, slot_safe, keep, gate_sorted


def _combine(out_buf, sorted_tok, sorted_e, slot, keep, gate_sorted, t):
    """Inverse of _dispatch: gather expert outputs back per token, gated."""
    rows = out_buf[sorted_e, jnp.minimum(slot, out_buf.shape[1] - 1)]
    rows = rows * (gate_sorted * keep)[:, None].astype(rows.dtype)
    return jax.ops.segment_sum(rows, sorted_tok, num_segments=t)


def _router(x_flat, router_w, top_k):
    logits = (x_flat.astype(jnp.float32) @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch/GShard): E * sum(frac_tokens * frac_prob)
    e = probs.shape[-1]
    me = probs.mean(0)
    ce = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / max(
        top_i.size, 1
    )
    aux = e * jnp.sum(me * ce)
    return top_p, top_i, aux


def _swiglu_experts(tokens, w1, w3, w2):
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", tokens, w1)) * jnp.einsum(
        "ecd,edf->ecf", tokens, w3
    )
    return jnp.einsum("ecf,efd->ecd", h, w2)


def moe_mode(n_experts: int, n_model: int) -> str:
    """"ep" (experts sharded over model), "ep_split" (each expert owned by
    n_model/E shards, capacity split — Mixtral 8e on a 16-way model axis),
    or "tp" (F sliced over model; fallback)."""
    if n_experts % n_model == 0 and n_experts >= n_model:
        return "ep"
    if n_model % n_experts == 0 and n_model > n_experts:
        return "ep_split"
    return "tp"


def moe_ffn(cfg: ArchConfig, params: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """MoE FFN over ``x [B, S, D]``. Returns (output, aux_loss)."""
    mesh = sh.current_mesh()
    e = cfg.n_experts
    n_model = sh.axis_size("model")
    mode = moe_mode(e, n_model)
    bd = sh.batch_axes()
    b, s, d = x.shape
    b_shardable = all(b % _safe_size(mesh, a) == 0 for a in bd) if mesh else True
    b_spec = bd if (bd and b_shardable) else None

    dtype = x.dtype
    w1 = params["w1"].astype(dtype) if cfg.cast_params_before_use else params["w1"]
    w3 = params["w3"].astype(dtype) if cfg.cast_params_before_use else params["w3"]
    w2 = params["w2"].astype(dtype) if cfg.cast_params_before_use else params["w2"]

    seq_ok = s % max(n_model, 1) == 0 and s > 1
    s_spec = "model" if seq_ok else None
    if mesh is None:
        y, aux = _moe_local(cfg, params["router"], w1, w3, w2, x.reshape(-1, d), e)
        out = y.reshape(b, s, d)
    elif mode == "ep":
        fn = jax.shard_map(
            partial(_moe_ep_island, cfg, e=e, n_model=n_model, bd=bd),
            mesh=mesh,
            in_specs=(
                P(b_spec, s_spec, None),
                P(None, None),
                P("model", bd if bd else None, None),  # w1 [E,D,F]: E->EP, D->fsdp
                P("model", bd if bd else None, None),  # w3
                P("model", None, bd if bd else None),  # w2 [E,F,D]: D->fsdp
            ),
            out_specs=(P(b_spec, s_spec, None), P()),
            check_vma=False,
        )
        out, aux = fn(x, params["router"], w1, w3, w2)
    elif mode == "ep_split":
        fn = jax.shard_map(
            partial(_moe_ep_split_island, cfg, e=e, n_model=n_model, bd=bd),
            mesh=mesh,
            in_specs=(
                P(b_spec, s_spec, None),
                P(None, None),
                # storage is TP-layout (F over model, D over bd) so expert
                # params shard over the full mesh; the island a2a-redistributes
                # F-slices to the owners
                P(None, bd if bd else None, "model"),  # w1 [E, D, F]
                P(None, bd if bd else None, "model"),  # w3
                P(None, "model", bd if bd else None),  # w2 [E, F, D]
            ),
            out_specs=(P(b_spec, s_spec, None), P()),
            check_vma=False,
        )
        out, aux = fn(x, params["router"], w1, w3, w2)
    else:
        fn = jax.shard_map(
            partial(_moe_tp_island, cfg, e=e, bd=bd),
            mesh=mesh,
            in_specs=(
                P(b_spec, None, None),
                P(None, None),
                P(None, bd if bd else None, "model"),
                P(None, bd if bd else None, "model"),
                P(None, "model", bd if bd else None),
            ),
            out_specs=(P(b_spec, None, None), P()),
            check_vma=False,
        )
        out, aux = fn(x, params["router"], w1, w3, w2)

    if cfg.n_shared_experts:
        from repro.models.layers import swiglu

        sp = params["shared"]
        out = out + swiglu(
            x,
            sp["w1"].astype(dtype),
            sp["w3"].astype(dtype),
            sp["w2"].astype(dtype),
        )
    return out, aux


def _safe_size(mesh, name):
    return mesh.shape[name] if mesh and name in mesh.axis_names else 1


def _capacity(cfg, t_loc, e):
    return max(1, math.ceil(t_loc * cfg.top_k / e * cfg.capacity_factor))


def _moe_local(cfg, router_w, w1, w3, w2, x_flat, e):
    """Single-shard reference path (also the trivial-mesh smoke path)."""
    t = x_flat.shape[0]
    cap = _capacity(cfg, t, e)
    top_p, top_i, aux = _router(x_flat, router_w, cfg.top_k)
    buf, *meta = _dispatch(x_flat, top_p, top_i, e, cap)
    out_buf = _swiglu_experts(buf, w1, w3, w2)
    return _combine(out_buf, *meta, t), aux


def _moe_ep_island(cfg, x, router_w, w1_loc, w3_loc, w2_loc, *, e, n_model, bd):
    """Expert-parallel island body. x [B_loc, S_loc, D]; weights are the
    local (expert-sharded + FSDP) slices."""
    b_loc, s_loc, d = x.shape
    e_loc = e // n_model
    x_flat = x.reshape(-1, d)
    t_loc = x_flat.shape[0]
    cap = _capacity(cfg, t_loc, e)

    top_p, top_i, aux = _router(x_flat, router_w, cfg.top_k)
    buf, *meta = _dispatch(x_flat, top_p, top_i, e, cap)

    # all_to_all: [E, C, D] -> [n_model, E_loc, C, D] -> exchange over model
    buf = buf.reshape(n_model, e_loc, cap, d)
    recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0, tiled=True)
    # recv[src*E_loc + e'] = tokens from shard src for local expert e'
    tokens = recv.reshape(n_model, e_loc, cap, d).transpose(1, 0, 2, 3)
    tokens = tokens.reshape(e_loc, n_model * cap, d)

    # FSDP-gather this shard's expert weights over the batch axes
    if bd:
        w1 = jax.lax.all_gather(w1_loc, bd, axis=1, tiled=True)
        w3 = jax.lax.all_gather(w3_loc, bd, axis=1, tiled=True)
        w2 = jax.lax.all_gather(w2_loc, bd, axis=2, tiled=True)
    else:
        w1, w3, w2 = w1_loc, w3_loc, w2_loc

    out = _swiglu_experts(tokens, w1, w3, w2)  # [E_loc, n_model*C, D]
    out = out.reshape(e_loc, n_model, cap, d).transpose(1, 0, 2, 3)
    back = jax.lax.all_to_all(
        out.reshape(n_model, e_loc, cap, d), "model",
        split_axis=0, concat_axis=0, tiled=True,
    )
    out_buf = back.reshape(e, cap, d)
    y = _combine(out_buf, *meta, t_loc).reshape(b_loc, s_loc, d)
    aux = jax.lax.pmean(aux, ("model",) + tuple(bd)) if bd else jax.lax.pmean(aux, "model")
    return y, aux


def _moe_ep_split_island(cfg, x, router_w, w1_loc, w3_loc, w2_loc, *, e, n_model, bd):
    """Capacity-split expert parallelism for n_model > E (Mixtral 8e / 16):
    expert ``e`` is owned by the ``r = n_model/E`` shards ``[e·r, (e+1)·r)``;
    each owner receives a 1/r slice of every source's capacity buffer, holds
    the expert's FULL weights (replicated over model, FSDP over bd), and the
    two all_to_alls are the only cross-device token traffic. Tokens stay on
    their (pod, data, model) shard — no sequence gather."""
    r = n_model // e
    b_loc, s_loc, d = x.shape
    x_flat = x.reshape(-1, d)
    t_loc = x_flat.shape[0]
    cap = -(-_capacity(cfg, t_loc, e) // r) * r  # multiple of r

    top_p, top_i, aux = _router(x_flat, router_w, cfg.top_k)
    buf, *meta = _dispatch(x_flat, top_p, top_i, e, cap)  # [E, cap, D]

    send = buf.reshape(n_model, cap // r, d)
    recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0, tiled=True)
    tokens = recv.reshape(n_model * (cap // r), d)

    # weight redistribution: shard s holds the s-th F-slice of EVERY expert;
    # owner t needs all F-slices of expert t//r. Stack slices by destination
    # expert and all_to_all — each shard receives its expert's full F (in
    # model-axis order), then FSDP-gathers D over the batch axes.
    dest = jnp.arange(n_model) // r  # static: expert id each shard owns

    def _collect(w_loc, f_axis):
        sendw = jnp.take(w_loc, dest, axis=0)  # [n_model, ..., F/n_model, ...]
        recvw = jax.lax.all_to_all(
            sendw, "model", split_axis=0, concat_axis=f_axis + 1, tiled=True
        )  # concat the F slices in shard order
        return recvw.reshape(recvw.shape[1:])  # drop the singleton src dim

    w1 = _collect(w1_loc, 1)  # [D_fsdp, F]
    w3 = _collect(w3_loc, 1)
    w2 = _collect(w2_loc, 0)  # [F, D_fsdp]
    if bd:
        w1 = jax.lax.all_gather(w1, bd, axis=0, tiled=True)
        w3 = jax.lax.all_gather(w3, bd, axis=0, tiled=True)
        w2 = jax.lax.all_gather(w2, bd, axis=1, tiled=True)

    h = jax.nn.silu(tokens @ w1) * (tokens @ w3)
    out = h @ w2  # [n_model * cap/r, D]

    back = jax.lax.all_to_all(
        out.reshape(n_model, cap // r, d), "model",
        split_axis=0, concat_axis=0, tiled=True,
    )
    out_buf = back.reshape(e, cap, d)
    y = _combine(out_buf, *meta, t_loc).reshape(b_loc, s_loc, d)
    axes = ("model",) + tuple(bd) if bd else ("model",)
    return y, jax.lax.pmean(aux, axes)


def _moe_tp_island(cfg, x, router_w, w1_loc, w3_loc, w2_loc, *, e, bd):
    """Tensor-parallel island body (E < model size): all experts on every
    model shard over an F/model slice; psum after the down projection.
    Weights are FSDP-gathered one expert at a time to bound the transient."""
    b_loc, s_loc, d = x.shape
    x_flat = x.reshape(-1, d)
    t_loc = x_flat.shape[0]
    cap = _capacity(cfg, t_loc, e)

    top_p, top_i, aux = _router(x_flat, router_w, cfg.top_k)
    buf, *meta = _dispatch(x_flat, top_p, top_i, e, cap)  # [E, C, D]

    outs = []
    for ei in range(e):
        if bd:
            w1 = jax.lax.all_gather(w1_loc[ei], bd, axis=0, tiled=True)
            w3 = jax.lax.all_gather(w3_loc[ei], bd, axis=0, tiled=True)
            w2 = jax.lax.all_gather(w2_loc[ei], bd, axis=1, tiled=True)
        else:
            w1, w3, w2 = w1_loc[ei], w3_loc[ei], w2_loc[ei]
        h = jax.nn.silu(buf[ei] @ w1) * (buf[ei] @ w3)  # [C, F_loc]
        outs.append(h @ w2)  # [C, D] partial over model
    out_buf = jnp.stack(outs)  # [E, C, D]
    out_buf = jax.lax.psum(out_buf, "model")
    y = _combine(out_buf, *meta, t_loc).reshape(b_loc, s_loc, d)
    axes = ("model",) + tuple(bd) if bd else ("model",)
    return y, jax.lax.pmean(aux, axes)

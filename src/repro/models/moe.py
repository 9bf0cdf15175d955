"""Top-k MoE with expert parallelism via shard_map.

Design (see DESIGN.md §5): tokens are batch-sharded over (pod, data); along
the ``model`` axis activations are replicated, so routing needs NO token
all-to-all — each model-rank selects the tokens routed to its local experts
into a fixed-capacity buffer, runs its experts as batched GEMMs, scatters
the results back, and a single psum over ``model`` combines contributions
(the same collective shape as a TP MLP).  Expert weights are additionally
FSDP-sharded over ``data`` and all-gathered on entry (ZeRO-3).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import Config, P_, batch_axes


def moe_specs(cfg: Config, n_layers: int) -> Dict[str, P_]:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_expert_ff
    L = (n_layers,)
    return {
        "router": P_(L + (d, e), ("layers", "embed", "expert")),
        "wg": P_(L + (e, d, f), ("layers", "expert", "embed", "expert_mlp")),
        "wu": P_(L + (e, d, f), ("layers", "expert", "embed", "expert_mlp")),
        "wd": P_(L + (e, f, d), ("layers", "expert", "expert_mlp", "embed")),
    }


def _capacity(n_tokens: int, cfg: Config, n_local: int) -> int:
    per_expert = (n_tokens * cfg.top_k * cfg.capacity_factor) / cfg.n_experts
    return max(cfg.top_k, int(-(-per_expert // 1)))  # ceil, floor k


def _moe_local(x, router, wg, wu, wd, *, cfg: Config, e_loc: int,
               capacity: int, has_model_axis: bool, fsdp_axes):
    """Per-shard MoE. x: (B_loc, S, D); expert weights hold e_loc experts.

    Two weight-layout strategies (cfg.moe_impl):
    * ``fsdp_gather`` — experts FSDP-sharded over 'data' on the embed axis;
      all-gathered per layer (ZeRO-3; right for training where T is large).
    * ``expert_tp``  — expert ffn axis sharded over 'data' and kept
      STATIONARY; the (small) token set is all-gathered over 'data' and the
      partial outputs psum'd back — removes the per-layer weight gathers
      (right for decode where T << weight size).
    """
    bdim, s, d = x.shape
    t = bdim * s
    k = cfg.top_k
    expert_tp = cfg.moe_impl == "expert_tp" and bool(fsdp_axes)
    if expert_tp:
        for ax in fsdp_axes:
            router = jax.lax.all_gather(router, ax, axis=0, tiled=True)
            x = jax.lax.all_gather(x, ax, axis=0, tiled=True)
        bdim = x.shape[0]
        t = bdim * s
    else:
        # ZeRO-3: gather the FSDP-sharded embed axis of the weights
        for ax in fsdp_axes:
            router = jax.lax.all_gather(router, ax, axis=0, tiled=True)
            wg = jax.lax.all_gather(wg, ax, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, ax, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, ax, axis=2, tiled=True)
    xf = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, k)            # (T, k)
    if cfg.norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    e0 = (jax.lax.axis_index("model") * e_loc) if has_model_axis else 0
    lidx = idx - e0
    local = (lidx >= 0) & (lidx < e_loc)              # (T, k)
    flat = jnp.where(local, lidx, e_loc).reshape(-1)  # (T*k,), e_loc = dump
    onehot = jax.nn.one_hot(flat, e_loc + 1, dtype=jnp.int32)
    rank = (jnp.cumsum(onehot, axis=0) - onehot)      # exclusive prefix count
    myrank = jnp.take_along_axis(rank, flat[:, None], axis=1)[:, 0]
    keep = (flat < e_loc) & (myrank < capacity)
    slot_e = jnp.where(keep, flat, e_loc)
    slot_c = jnp.where(keep, myrank, 0)
    tok = jnp.arange(t * k) // k

    buf = jnp.zeros((e_loc + 1, capacity, d), x.dtype)
    buf = buf.at[slot_e, slot_c].set(xf[tok])
    act = buf[:e_loc]
    g = jnp.einsum("ecd,edf->ecf", act, wg.astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", act, wu.astype(x.dtype))
    h = jax.nn.silu(g) * u
    y_buf = jnp.einsum("ecf,efd->ecd", h, wd.astype(x.dtype))
    y_buf = jnp.concatenate([y_buf, jnp.zeros((1, capacity, d), y_buf.dtype)], 0)

    vals = y_buf[slot_e, slot_c]
    vals = jnp.where(keep[:, None], vals, 0.0)
    vals = vals * weights.reshape(-1)[:, None].astype(vals.dtype)
    y = jnp.zeros((t, d), x.dtype).at[tok].add(vals)
    if cfg.moe_psum_dtype == "bf16":
        y = y.astype(jnp.bfloat16)
    if expert_tp:
        # partial over the ffn ('data'-sharded) axis + expert ('model') axis
        axes = ("model",) if has_model_axis else ()
        y = jax.lax.psum(y, axes + tuple(fsdp_axes))
        n_data = 1
        for ax in fsdp_axes:
            n_data *= jax.lax.axis_size(ax)
        my = jax.lax.axis_index(fsdp_axes[0])
        y = jax.lax.dynamic_slice_in_dim(y.reshape(bdim, s, d),
                                         my * (bdim // n_data),
                                         bdim // n_data, axis=0)
        return y.astype(x.dtype)
    if has_model_axis:
        y = jax.lax.psum(y, "model")
    return y.reshape(bdim, s, d).astype(x.dtype)


def moe_apply(x, p, cfg: Config, mesh) -> jnp.ndarray:
    """x: (B, S, D) batch-sharded; p holds this layer's MoE params."""
    names = mesh.axis_names
    sizes = dict(zip(names, mesh.devices.shape))
    model = sizes.get("model", 1)
    has_model = "model" in names and cfg.n_experts % max(model, 1) == 0 and model > 1
    e_loc = cfg.n_experts // model if has_model else cfg.n_experts
    b_axes = batch_axes(mesh)
    n_b = 1
    for a in b_axes:
        n_b *= sizes[a]
    t_loc = (x.shape[0] // max(n_b, 1)) * x.shape[1]

    fsdp_axes = tuple(a for a in ("data",) if a in names and
                      cfg.d_model % sizes[a] == 0 and sizes[a] > 1)
    if cfg.moe_impl == "expert_tp":
        fsdp_axes = tuple(a for a in fsdp_axes
                          if cfg.d_expert_ff % sizes[a] == 0)
        # tokens are all-gathered over 'data' inside the shard
        for a in fsdp_axes:
            t_loc *= sizes[a]
    capacity = _capacity(t_loc, cfg, e_loc)
    espec_embed = "data" if fsdp_axes else None
    x_spec = P(b_axes if b_axes else None, None, None)
    e_ax = None if not has_model else "model"
    if cfg.moe_impl == "expert_tp" and fsdp_axes:
        in_specs = (
            x_spec,
            P(espec_embed, None),                     # router (d, e)
            P(e_ax, None, "data"),                    # wg: ffn axis stationary
            P(e_ax, None, "data"),                    # wu
            P(e_ax, "data", None),                    # wd
        )
    else:
        in_specs = (
            x_spec,
            P(espec_embed, None),                     # router (d, e)
            P(e_ax, espec_embed, None),               # wg
            P(e_ax, espec_embed, None),               # wu
            P(e_ax, None, espec_embed),               # wd
        )
    fn = functools.partial(_moe_local, cfg=cfg, e_loc=e_loc, capacity=capacity,
                           has_model_axis=has_model, fsdp_axes=fsdp_axes)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=x_spec,
                         check_vma=False)(x, p["router"], p["wg"], p["wu"],
                                          p["wd"])

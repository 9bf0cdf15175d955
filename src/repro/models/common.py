"""Shared model machinery: param specs, logical-axis sharding rules, norms,
rotary embeddings (RoPE and M-RoPE), and the model Config dataclass.

Params are declared as trees of :class:`P_` leaves carrying logical axis
names; a rules table maps logical axes to mesh axes with automatic
divisibility fallback (an arch with 15 heads simply replicates its attention
weights over the ``model`` axis instead of failing).  Hillclimbing sharding
= editing the rules and re-lowering.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class P_:
    """A parameter leaf: shape + logical axis names + init."""
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | small
    dtype: Any = None             # defaults to cfg param dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def _is_leaf(x):
    return isinstance(x, P_)


def tree_map_specs(fn: Callable[[P_], Any], specs):
    return jax.tree_util.tree_map(fn, specs, is_leaf=_is_leaf)


def init_params(specs, key, param_dtype=jnp.float32):
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_is_leaf)
    keys = jax.random.split(key, max(len(leaves), 2))
    out = []
    for spec, k in zip(leaves, keys):
        dtype = spec.dtype or param_dtype
        if spec.init == "zeros":
            out.append(jnp.zeros(spec.shape, dtype))
        elif spec.init == "ones":
            out.append(jnp.ones(spec.shape, dtype))
        else:
            scale = 0.02 if spec.init == "normal" else 0.006
            out.append((jax.random.normal(k, spec.shape, jnp.float32) * scale)
                       .astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(specs, param_dtype=jnp.float32):
    return tree_map_specs(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or param_dtype), specs)


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

# default rules: FSDP over 'data' (embed axis of weights), TP/EP over 'model'
DEFAULT_RULES: Dict[str, Any] = {
    "batch": ("pod", "data"),
    "embed": "data",          # FSDP / ZeRO-3 weight sharding
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "expert": "model",
    "expert_mlp": None,
    "vocab": "model",
    "seq": None,
    "kv_seq": None,
    "layers": None,
    "ssm_inner": "model",
    "ssm_heads": "model",
    "ssm_state": None,
    "ssm_bc": None,
    "conv": None,
    "frames": None,
    # activation-only axes
    "act_embed": None,
    "act_heads": "model",
    "act_mlp": "model",
}


def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    return n


def resolve_spec(shape: Sequence[int], logical: Sequence[Optional[str]],
                 mesh: Mesh, rules: Dict[str, Any]) -> P:
    """Logical axes -> PartitionSpec with divisibility fallback."""
    used = set()
    parts = []
    for dim, name in zip(shape, logical):
        choice = None
        rule = rules.get(name) if name else None
        if rule is not None:
            axes = (rule,) if isinstance(rule, str) else tuple(rule)
            axes = tuple(a for a in axes if a in mesh.axis_names and a not in used)
            if axes and dim % _axes_size(mesh, axes) == 0:
                choice = axes if len(axes) > 1 else axes[0]
                used.update(axes)
        parts.append(choice)
    return P(*parts)


def param_shardings(specs, mesh: Mesh, rules=None):
    rules = rules or DEFAULT_RULES
    return tree_map_specs(
        lambda s: NamedSharding(mesh, resolve_spec(s.shape, s.logical, mesh, rules)),
        specs)


def manual_axes() -> set:
    """Mesh axes currently under manual control (inside a shard_map)."""
    am = jax.sharding.get_abstract_mesh()
    return {n for n, t in zip(am.axis_names, am.axis_types)
            if t == jax.sharding.AxisType.Manual}


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    skip = manual_axes()
    return tuple(a for a in ("pod", "data")
                 if a in mesh.axis_names and a not in skip)


def sharding_constraint(x: jnp.ndarray, mesh: Mesh, spec) -> jnp.ndarray:
    """``with_sharding_constraint`` of ``x`` to ``spec`` on ``mesh``."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain(x: jnp.ndarray, mesh: Mesh, logical: Sequence[Optional[str]],
              rules=None) -> jnp.ndarray:
    """with_sharding_constraint via logical axes (activations).

    Axes currently under manual shard_map control are dropped from the spec
    (mixing Manual with Auto in one PartitionSpec is an error)."""
    rules = dict(rules or DEFAULT_RULES)
    skip = manual_axes()
    if skip:
        rules = {k: (tuple(a for a in ((v,) if isinstance(v, str) else v)
                           if a not in skip) or None) if v else v
                 for k, v in rules.items()}
        rules = {k: (v[0] if isinstance(v, tuple) and len(v) == 1 else v)
                 for k, v in rules.items()}
    spec = resolve_spec(x.shape, logical, mesh, rules)
    return sharding_constraint(x, mesh, spec)


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Config:
    name: str
    family: str                   # dense | moe | ssm | hybrid | vlm | encdec
    n_layers: int
    d_model: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_head: int = 0
    d_ff: int = 0
    vocab: int = 32000
    qk_norm: bool = False
    rope_theta: float = 1e4
    attn_impl: str = "chunked"            # chunked (flash-style) | naive
    attn_chunk: int = 2048
    # dry-run accounting knobs: XLA cost_analysis counts a scanned body once,
    # so the dry-run compiles with layer_unroll in {1, k} and extrapolates.
    layer_unroll: int = 1
    group_unroll: int = 1                 # hybrid: outer (group) scan unroll
    attn_unroll: bool = False             # unroll the kv-chunk scan (<=16 steps)
    mrope_sections: Optional[Tuple[int, int, int]] = None  # vlm
    # MoE
    moe_impl: str = "fsdp_gather"         # fsdp_gather | expert_tp (inference)
    moe_psum_dtype: str = "f32"           # f32 | bf16 (combine all-reduce)
    n_experts: int = 0
    top_k: int = 0
    d_expert_ff: int = 0
    moe_dense_residual: bool = False        # arctic
    capacity_factor: float = 1.25
    norm_topk: bool = True
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_chunk: int = 128                  # SSD chunk length (perf knob)
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    conv_width: int = 4
    # hybrid (zamba2)
    hybrid_group: int = 6                   # 1 shared attn per group
    # encdec (whisper)
    n_enc_layers: int = 0
    enc_frames: int = 1500
    # numerics / training
    param_dtype: Any = jnp.float32
    act_dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "nothing"         # nothing | dots
    # notes for the dry-run table
    sub_quadratic: bool = False             # supports long_500k decode

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim


# ---------------------------------------------------------------------------
# Norms / activations / rotary embeddings
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm with f32 accumulation but NO f32 materialization of x.

    The sum of squares is computed as a dot with f32 accumulation
    (``preferred_element_type``), so the input is read in its own dtype.
    The naive ``x.astype(f32)`` formulation makes the saved residual's
    first backward use a convert — which XLA hoists out of the scan as a
    whole-stack bf16->f32 materialization of ALL saved activations
    (measured: +38 GiB temp and ~2x memory-roofline term on granite-8b;
    see EXPERIMENTS.md §Perf)."""
    sumsq = jnp.einsum("...d,...d->...", x, x,
                       preferred_element_type=jnp.float32)
    rs = jax.lax.rsqrt(sumsq / x.shape[-1] + eps)
    return x * rs[..., None].astype(x.dtype) * scale.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    g = jnp.einsum("bsd,df->bsf", x, w_gate.astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, w_up.astype(x.dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("bsf,fd->bsd", h, w_down.astype(x.dtype))


def _rope_freqs(d_half: int, theta: float, dtype=jnp.float32):
    return 1.0 / (theta ** (jnp.arange(d_half, dtype=dtype) / d_half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 1e4) -> jnp.ndarray:
    """Rotate-half RoPE. x: (B, S, H, D), positions: (B, S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = _rope_freqs(half, theta)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions: jnp.ndarray,
                sections: Tuple[int, int, int],
                theta: float = 1e6) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE. positions: (3, B, S) for (t, h, w);
    ``sections`` split the half-dim (sum == d_head // 2)."""
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    freqs = _rope_freqs(half, theta)  # (half,)
    # choose the position stream per frequency slot
    sec_id = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                        total_repeat_length=half)  # (half,)
    pos = positions.astype(jnp.float32)             # (3, B, S)
    ang = jnp.take(pos, sec_id, axis=0)             # (half, B, S) -> gather
    ang = jnp.moveaxis(ang, 0, -1) * freqs          # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean next-token CE; logits (B,S,V) any float dtype, labels (B,S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def checkpoint_policy(cfg: Config):
    """Resolve cfg.remat_policy to a jax.checkpoint policy."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable

"""build(cfg, mesh) -> ModelBundle: specs, init, train/prefill/serve steps,
and per-shape input_specs (ShapeDtypeStruct stand-ins for the dry-run).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import (Config, DEFAULT_RULES, abstract_params,
                                 batch_axes, init_params, param_shardings,
                                 resolve_spec)
from repro.models import encdec as encdec_mod
from repro.models import transformer as tf_mod
from repro.optim.adamw import OptConfig, adamw_init, adamw_update


# The four assigned input shapes: (seq_len, global_batch, kind)
SHAPES: Dict[str, Tuple[int, int, str]] = {
    "train_4k": (4096, 256, "train"),
    "prefill_32k": (32768, 32, "prefill"),
    "decode_32k": (32768, 128, "decode"),
    "long_500k": (524288, 1, "decode"),
    # reduced variants for smoke tests
    "smoke_train": (64, 2, "train"),
    "smoke_prefill": (64, 2, "prefill"),
    "smoke_decode": (64, 2, "decode"),
}


@dataclasses.dataclass
class ModelBundle:
    cfg: Config
    mesh: Mesh
    rules: Dict[str, Any]
    specs: Any
    opt_cfg: OptConfig

    # ---------------------------------------------------------------- params
    def init(self, key) -> Any:
        return init_params(self.specs, key, self.cfg.param_dtype)

    def abstract_params(self) -> Any:
        return abstract_params(self.specs, self.cfg.param_dtype)

    def param_shardings(self) -> Any:
        return param_shardings(self.specs, self.mesh, self.rules)

    def opt_shardings(self) -> Any:
        ps = self.param_shardings()
        return {"mu": ps, "nu": ps,
                "count": NamedSharding(self.mesh, P())}

    def abstract_opt_state(self) -> Any:
        z = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
            self.abstract_params())
        return {"mu": z, "nu": z, "count": jax.ShapeDtypeStruct((), jnp.int32)}

    # ----------------------------------------------------------------- steps
    def loss_fn(self, params, batch):
        if self.cfg.family == "encdec":
            return encdec_mod.loss_fn(params, self.cfg, self.mesh, batch)
        return tf_mod.loss_fn(params, self.cfg, self.mesh, batch)

    def train_step(self, params, opt_state, batch, microbatches: int = 1):
        """One optimizer step; with microbatches > 1, gradients are
        accumulated in f32 over a lax.scan (live activations /m)."""
        if microbatches == 1:
            loss, grads = jax.value_and_grad(self.loss_fn)(params, batch)
        else:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])

            mb = {k: split(v) for k, v in batch.items() if k != "positions"}
            if "positions" in batch:  # (3, B, S): the batch axis is axis 1
                p = batch["positions"]
                mb["positions"] = p.reshape(
                    (p.shape[0], microbatches, p.shape[1] // microbatches)
                    + p.shape[2:]).swapaxes(0, 1)

            def body(carry, micro):
                loss_acc, grad_acc = carry
                loss, grads = jax.value_and_grad(self.loss_fn)(params, micro)
                grad_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), grad_acc, grads)
                return (loss_acc + loss, grad_acc), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(body, (0.0, zeros), mb)
            loss = loss / microbatches
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
        new_params, new_opt, metrics = adamw_update(grads, opt_state, params,
                                                    self.opt_cfg)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    def train_step_compressed(self, params, opt_state, err_state, batch):
        """Train step with int8 error-feedback compression of the CROSS-POD
        gradient all-reduce (distributed-optimization trick; multi-pod mesh).

        shard_map is manual over the 'pod' axis only — data/model stay under
        GSPMD — so each pod computes gradients on its own batch shard and
        the pods exchange int8 payloads (1 byte/grad over the slow links).
        """
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import psum_compressed
        assert "pod" in self.mesh.axis_names, "needs a multi-pod mesh"

        def per_pod(params, opt_state, err_state, batch):
            loss, grads = jax.value_and_grad(self.loss_fn)(params, batch)
            flat_g, treedef = jax.tree_util.tree_flatten(grads)
            flat_e = treedef.flatten_up_to(err_state)
            new_g, new_e = [], []
            for g, e in zip(flat_g, flat_e):
                gm, em = psum_compressed(g, e, "pod")
                new_g.append(gm)
                new_e.append(em)
            grads = jax.tree_util.tree_unflatten(treedef, new_g)
            err = jax.tree_util.tree_unflatten(treedef, new_e)
            loss = jax.lax.pmean(loss, "pod")
            new_params, new_opt, metrics = adamw_update(
                grads, opt_state, params, self.opt_cfg)
            metrics["loss"] = loss
            return new_params, new_opt, err, metrics

        rep = jax.tree_util.tree_map(lambda _: P(), params)
        rep_opt = jax.tree_util.tree_map(lambda _: P(), opt_state)
        rep_err = jax.tree_util.tree_map(lambda _: P(), err_state)
        bspec = jax.tree_util.tree_map(lambda _: P("pod"), batch)
        out_specs = (rep, rep_opt, rep_err,
                     {"loss": P(), "grad_norm": P()})
        return jax.shard_map(per_pod, mesh=self.mesh,
                             in_specs=(rep, rep_opt, rep_err, bspec),
                             out_specs=out_specs, axis_names={"pod"},
                             check_vma=False)(params, opt_state, err_state,
                                              batch)

    def prefill_step(self, params, tokens):
        assert self.cfg.family not in ("encdec",), "use encode for encdec"
        max_seq = tokens.shape[1]
        return tf_mod.prefill(params, self.cfg, self.mesh, tokens, max_seq)

    def encode_step(self, params, frames):
        return encdec_mod.encode(params, self.cfg, self.mesh, frames)

    def serve_step(self, params, cache, token, positions=None):
        if self.cfg.family == "encdec":
            return encdec_mod.decode_step(params, self.cfg, self.mesh, cache,
                                          token, positions)
        return tf_mod.decode_step(params, self.cfg, self.mesh, cache, token,
                                  positions)

    # ------------------------------------------------------------- dry-run IO
    def cache_specs(self, batch: int, max_seq: int):
        if self.cfg.family == "encdec":
            return encdec_mod.init_cache_specs(self.cfg, batch, max_seq)
        return tf_mod.init_cache_specs(self.cfg, batch, max_seq)

    def cache_shardings(self, batch: int, max_seq: int):
        if self.cfg.family == "encdec":
            axes = encdec_mod.cache_logical_axes(self.cfg)
        else:
            axes = tf_mod.cache_logical_axes(self.cfg)
        specs = self.cache_specs(batch, max_seq)
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        m = sizes.get("model", 1)
        out = {}
        for name, sds in specs.items():
            logical = list(axes[name])
            # KV caches: when kv_heads don't divide TP, shard the KV sequence
            # instead (flash-decode style: softmax stats psums are tiny)
            if "kv_heads" in logical and m > 1:
                kv_i = logical.index("kv_heads")
                seq_i = logical.index("kv_seq")
                if sds.shape[kv_i] % m != 0 and sds.shape[seq_i] % m == 0:
                    logical[kv_i] = None
                    logical[seq_i] = "act_heads"  # -> 'model'
            # batch divisibility fallback
            if "batch" in logical:
                b_i = logical.index("batch")
                b_ax = batch_axes(self.mesh)
                n = 1
                for a in b_ax:
                    n *= sizes[a]
                if n and sds.shape[b_i] % max(n, 1) != 0:
                    logical[b_i] = None
            out[name] = NamedSharding(
                self.mesh, resolve_spec(sds.shape, tuple(logical), self.mesh,
                                        self.rules))
        return out

    def init_cache(self, batch: int, max_seq: int):
        shardings = self.cache_shardings(batch, max_seq)
        return {
            name: jax.device_put(jnp.zeros(s.shape, s.dtype), shardings[name])
            for name, s in self.cache_specs(batch, max_seq).items()
        }

    def batch_sharding(self, batch_size: Optional[int] = None):
        b = batch_axes(self.mesh)
        if b and batch_size is not None:
            sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
            n = 1
            for a in b:
                n *= sizes[a]
            if batch_size % n != 0:
                # try pods-only, then replicate (e.g. long_500k batch=1)
                b = tuple(a for a in b if a == "pod" and
                          batch_size % sizes[a] == 0)
        return NamedSharding(self.mesh, P(b if b else None, None))

    def input_specs(self, shape_name: str) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins + shardings for one assigned shape."""
        seq, gbatch, kind = SHAPES[shape_name]
        cfg = self.cfg
        tok = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
        bspec = self.batch_sharding(gbatch)
        out: Dict[str, Any] = {"kind": kind}
        if kind == "train":
            if cfg.family == "encdec":
                frames = jax.ShapeDtypeStruct((gbatch, cfg.enc_frames,
                                               cfg.d_model), jnp.float32)
                out["batch"] = {"frames": frames,
                                "tokens": tok((gbatch, seq)),
                                "labels": tok((gbatch, seq))}
                out["batch_shardings"] = {
                    "frames": NamedSharding(self.mesh, P(bspec.spec[0], None, None)),
                    "tokens": bspec, "labels": bspec}
            elif cfg.family == "vlm":
                out["batch"] = {"tokens": tok((gbatch, seq)),
                                "labels": tok((gbatch, seq)),
                                "positions": tok((3, gbatch, seq))}
                out["batch_shardings"] = {
                    "tokens": bspec, "labels": bspec,
                    "positions": NamedSharding(self.mesh,
                                               P(None, bspec.spec[0], None))}
            else:
                out["batch"] = {"tokens": tok((gbatch, seq)),
                                "labels": tok((gbatch, seq))}
                out["batch_shardings"] = {"tokens": bspec, "labels": bspec}
        elif kind == "prefill":
            if cfg.family == "encdec":
                out["frames"] = jax.ShapeDtypeStruct(
                    (gbatch, seq, cfg.d_model), jnp.float32)
                out["frames_sharding"] = NamedSharding(
                    self.mesh, P(bspec.spec[0], None, None))
            else:
                out["tokens"] = tok((gbatch, seq))
                out["tokens_sharding"] = bspec
        else:  # decode
            out["cache"] = self.cache_specs(gbatch, seq)
            out["cache_shardings"] = self.cache_shardings(gbatch, seq)
            out["token"] = tok((gbatch, 1))
            out["token_sharding"] = bspec
            if cfg.family == "vlm":
                out["positions"] = tok((3, gbatch, 1))
        return out


# VLM forward needs positions threaded through loss; patch via batch dict
# (transformer.loss_fn already reads batch["positions"]).


def build(cfg: Config, mesh: Mesh, rules: Optional[Dict[str, Any]] = None,
          opt_cfg: Optional[OptConfig] = None) -> ModelBundle:
    rules = dict(rules or DEFAULT_RULES)
    if cfg.family == "encdec":
        specs = encdec_mod.encdec_specs(cfg)
    else:
        specs = tf_mod.lm_specs(cfg)
    return ModelBundle(cfg=cfg, mesh=mesh, rules=rules, specs=specs,
                       opt_cfg=opt_cfg or OptConfig())

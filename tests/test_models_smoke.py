"""Per-architecture smoke tests: reduced config, one train + decode step on
CPU, asserting output shapes and no NaNs (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get, get_smoke
from repro.launch.mesh import make_host_mesh
from repro.models.model import build
from repro.optim.adamw import adamw_init


def _mesh():
    return make_host_mesh()


def _batch(cfg, b=2, s=16):
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.family == "vlm":
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (3, b, s))
        batch["positions"] = pos
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            key, (b, cfg.enc_frames, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke(arch)
    mesh = _mesh()
    bundle = build(cfg, mesh)
    params = bundle.init(jax.random.PRNGKey(1))
    opt = adamw_init(params)
    batch = _batch(cfg)
    with jax.set_mesh(mesh):
        step = jax.jit(bundle.train_step)
        new_params, new_opt, metrics = step(params, opt, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss), f"{arch}: loss={loss}"
    assert float(metrics["grad_norm"]) > 0
    # params actually changed
    l0 = jax.tree_util.tree_leaves(params)[0]
    l1 = jax.tree_util.tree_leaves(new_params)[0]
    assert not np.allclose(np.asarray(l0, np.float32),
                           np.asarray(l1, np.float32))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_smoke(arch)
    mesh = _mesh()
    bundle = build(cfg, mesh)
    params = bundle.init(jax.random.PRNGKey(2))
    b, max_seq = 2, 32
    with jax.set_mesh(mesh):
        cache = bundle.init_cache(b, max_seq)
        if cfg.family == "encdec":
            # fill cross-attention cache with zeros (already zeros)
            pass
        token = jnp.zeros((b, 1), jnp.int32)
        positions = None
        if cfg.family == "vlm":
            positions = jnp.zeros((3, b, 1), jnp.int32)
        step = jax.jit(bundle.serve_step)
        logits, new_cache = step(params, cache, token, positions)
    assert logits.shape == (b, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch
    assert int(new_cache["index"]) == 1


@pytest.mark.parametrize("arch", ["granite-8b", "qwen3-moe-30b-a3b",
                                  "qwen2-vl-72b", "mamba2-2.7b",
                                  "zamba2-2.7b"])
def test_smoke_prefill(arch):
    cfg = get_smoke(arch)
    mesh = _mesh()
    bundle = build(cfg, mesh)
    params = bundle.init(jax.random.PRNGKey(3))
    tokens = jnp.zeros((2, 16), jnp.int32)
    with jax.set_mesh(mesh):
        logits, cache = jax.jit(bundle.prefill_step)(params, tokens)
    assert logits.shape == (2, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert int(cache["index"]) == 16


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-2.7b"])
def test_prefill_then_decode_consistent(arch):
    """Prefilled recurrent state must continue correctly: prefill(t0..t14)
    then decode(t15) matches prefill(t0..t15)'s logits."""
    cfg = get_smoke(arch)
    mesh = _mesh()
    bundle = build(cfg, mesh)
    params = bundle.init(jax.random.PRNGKey(4))
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0, cfg.vocab)
    with jax.set_mesh(mesh):
        logits_full, _ = jax.jit(bundle.prefill_step)(params, toks)
        _, cache = jax.jit(bundle.prefill_step)(params, toks[:, :15])
        if cfg.family == "hybrid":  # widen shared-attn kv cache to >=16
            pad = 16 - cache["k"].shape[3]
            cache = dict(cache)
            cache["k"] = jnp.pad(cache["k"], ((0,0),(0,0),(0,pad),(0,0),(0,0)))
            cache["v"] = jnp.pad(cache["v"], ((0,0),(0,0),(0,pad),(0,0),(0,0)))
        logits_dec, _ = jax.jit(bundle.serve_step)(params, cache,
                                                   toks[:, 15:16])
    np.testing.assert_allclose(np.asarray(logits_dec, np.float32),
                               np.asarray(logits_full, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_full_configs_match_assignment():
    """The exact published numbers from the assignment table."""
    c = get("granite-8b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab) \
        == (36, 4096, 32, 8, 14336, 49152)
    c = get("qwen3-4b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab) \
        == (36, 2560, 32, 8, 9728, 151936) and c.qk_norm
    c = get("smollm-360m")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab) \
        == (32, 960, 15, 5, 2560, 49152)
    c = get("deepseek-coder-33b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab) \
        == (62, 7168, 56, 8, 19200, 32256)
    c = get("qwen3-moe-30b-a3b")
    assert (c.n_layers, c.d_model, c.n_experts, c.top_k, c.d_expert_ff) \
        == (48, 2048, 128, 8, 768)
    c = get("arctic-480b")
    assert (c.n_layers, c.d_model, c.n_experts, c.top_k) == (35, 7168, 128, 2)
    assert c.moe_dense_residual
    c = get("zamba2-2.7b")
    assert (c.n_layers, c.d_model, c.ssm_state) == (54, 2560, 64)
    c = get("qwen2-vl-72b")
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff) == (80, 8192, 64, 29568)
    c = get("mamba2-2.7b")
    assert (c.n_layers, c.d_model, c.ssm_state, c.vocab) == (64, 2560, 128, 50280)
    c = get("whisper-large-v3")
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff, c.vocab) \
        == (32, 1280, 20, 5120, 51866)

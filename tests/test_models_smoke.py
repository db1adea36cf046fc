"""Per-architecture smoke tests: reduced config, one forward + one train step
+ one decode step on CPU, asserting shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke
from repro.models import (decode_step, forward, init_caches, init_params,
                          loss_fn)

B, L = 2, 32


def make_batch(cfg, key):
    ks = jax.random.split(key, 4)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, L), 0, cfg.vocab),
        "labels": jax.random.randint(ks[1], (B, L), 0, cfg.vocab),
    }
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            ks[2], (B, cfg.frontend_len, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            ks[2], (B, cfg.frontend_len, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_smoke(arch)
    params = init_params(jax.random.key(0), cfg)
    batch = make_batch(cfg, jax.random.key(1))
    logits, aux = jax.jit(
        lambda p, b: forward(p, b["tokens"], cfg,
                             prefix_embeds=b.get("patch_embeds"),
                             frames=b.get("frames")))(params, batch)
    assert logits.shape == (B, L, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_descends(arch):
    cfg = get_smoke(arch)
    params = init_params(jax.random.key(0), cfg)
    batch = make_batch(cfg, jax.random.key(1))

    @jax.jit
    def step(p, b):
        (l, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b, cfg)
        p = jax.tree.map(lambda w, gw: w - 0.05 * gw, p, g)
        return p, l

    p1, l1 = step(params, batch)
    _, l2 = step(p1, batch)
    assert np.isfinite(float(l1)) and np.isfinite(float(l2))
    assert float(l2) < float(l1), f"{arch}: loss did not decrease ({l1}->{l2})"


def test_loss_fn_differentiates_where_attention_routes_to_kernels():
    """A 128-aligned sequence routes attention to the forward-only flash
    kernel under a pallas backend (as `backend="auto"` is on a TPU); the
    training objective must still differentiate, through the XLA path."""
    from repro import api

    cfg = get_smoke("qwen2_1p5b")
    params = init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (B, 128), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    assert api.ops.attention_route(lq=128, backend="pallas") == "pallas"
    with api.policy(backend="pallas"):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, cfg)
    (want, _), want_grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, cfg)
    assert float(loss) == float(want)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step(arch):
    cfg = get_smoke(arch)
    params = init_params(jax.random.key(0), cfg)
    caches = init_caches(cfg, batch=B, max_len=64)
    memory = None
    if cfg.family == "audio":
        frames = jax.random.normal(jax.random.key(2),
                                   (B, cfg.frontend_len, cfg.d_model))
        # encode once (prefill of the audio memory)
        from repro.models.transformer import _block_apply, _sinusoid
        from repro.models.layers import apply_norm
        mem = frames + _sinusoid(frames.shape[1], cfg.d_model, frames.dtype)
        for i in range(cfg.encoder_layers):
            p_i = jax.tree.map(lambda a: a[i], params["encoder"])
            mem, _, _ = _block_apply("enc", p_i, mem, cfg)
        memory = apply_norm(cfg.norm, params["enc_norm"], mem)
    token = jnp.zeros((B, 1), jnp.int32)
    logits, caches = jax.jit(
        lambda p, c, t: decode_step(p, c, t, cfg, memory=memory))(
            params, caches, token)
    assert logits.shape == (B, 1, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    # a second step must advance cache positions
    logits2, caches2 = jax.jit(
        lambda p, c, t: decode_step(p, c, t, cfg, memory=memory))(
            params, caches, token)
    assert bool(jnp.isfinite(logits2).all())


@pytest.mark.parametrize("arch", ["zamba2_2p7b", "xlstm_1p3b", "qwen2_1p5b"])
def test_prefill_decode_equivalence(arch):
    """Teacher-forced decode must reproduce the full-sequence forward —
    the cache path and the parallel path are the same function."""
    cfg = get_smoke(arch)
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(3), (1, 12), 0, cfg.vocab)
    full, _ = forward(params, tokens, cfg)
    # f32 caches: this test checks math equivalence, not bf16 cache rounding
    caches = init_caches(cfg, batch=1, max_len=16, dtype=jnp.float32)
    outs = []
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))
    for t in range(tokens.shape[1]):
        logits, caches = step(params, caches, tokens[:, t:t + 1])
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), rtol=2e-3,
                               atol=2e-3)


def test_shared_attention_is_shared():
    """zamba2: the shared_attn block must hold exactly ONE weight copy."""
    cfg = get_smoke("zamba2_2p7b")
    params = init_params(jax.random.key(0), cfg)
    seg = params["segments"][0]
    shared_keys = [k for k in seg if k.endswith("shared_attn")]
    assert len(shared_keys) == 1
    w = seg[shared_keys[0]]["attn"]["q"]["w"]
    assert w.ndim == 2      # un-stacked: one copy for all invocations
    mamba_keys = [k for k in seg if k.endswith("mamba")]
    assert seg[mamba_keys[0]]["mamba"]["in_proj"]["w"].ndim == 3  # stacked


def test_param_counts_full_configs():
    """Full-config param counts must be in the right ballpark (N for the
    roofline's MODEL_FLOPS = 6*N*D)."""
    from repro.configs import get_config
    from repro.models.transformer import init_params as ip
    expectations = {
        "olmo_1b": (0.9e9, 1.6e9),
        "qwen2_1p5b": (1.2e9, 2.0e9),
        # our mLSTM uses dense (not block-diagonal) qkv projections —
        # documented deviation in configs/xlstm_1p3b.py; params land at 3.6B
        "xlstm_1p3b": (3.0e9, 4.2e9),
        "zamba2_2p7b": (2.0e9, 3.4e9),
        "olmoe_1b_7b": (6.0e9, 8.0e9),
    }
    for arch, (lo, hi) in expectations.items():
        cfg = get_config(arch)
        shapes = jax.eval_shape(lambda k: ip(k, cfg), jax.random.key(0))
        n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
        assert lo < n < hi, f"{arch}: {n/1e9:.2f}B params outside [{lo/1e9}, {hi/1e9}]"

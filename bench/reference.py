"""Plain forward pass of a dense decoder, written from the published
description (Qwen2: RMSNorm, q/k/v biases, GQA; OLMo: LayerNorm without
parameters, MHA; both: rotate-half RoPE, SwiGLU, tied embeddings).

It imports nothing of the program and takes none of its objects: sizes come
from the configuration file and weights from `bench.weights`, layer by
layer. One sequence at a time, padded to a fixed length so that each layer
compiles once; causal attention keeps the padding out of every real
position.

It computes at a stated `Precision`: the dtype of the dense matmuls'
operands and of attention's (both accumulated in float32), of the
activations between operations (the residual stream included) and of the
stored keys and values. `as_run` reads the one the configuration file
states for its run; `CONTROL` is the step below it, every activation in
bfloat16, which a sound comparison has to tell apart from the program.
"""
from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W


@dataclasses.dataclass(frozen=True)
class Precision:
    operands: str       # matmul operands; float32 is taken at highest
    act: str            # activations, the residual stream included
    kv: str             # keys and values as the cache holds them
    attn: str           # operands of attention's two matmuls


CONTROL = Precision("bfloat16", "bfloat16", "bfloat16", "bfloat16")


def as_run(spec: dict) -> Precision:
    """The precision the configuration file states for its run."""
    r = spec["as_run"]
    return Precision(r["matmul_operands"], r["act_dtype"], r["kv_dtype"],
                     r["attention_operands"])


def _mm(a, b, prec: Precision, operands=None):
    """a @ b with operands in `operands` (`prec.operands` if not given),
    accumulated in float32."""
    dt = jnp.dtype(operands or prec.operands)
    hi = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
    return jnp.matmul(a.astype(dt), b.astype(dt), precision=hi,
                      preferred_element_type=jnp.float32)


def _norm(x, g, arch):
    eps = arch["norm_eps"]
    x = x.astype(jnp.float32)
    if arch["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g
    if arch["norm"] == "nonparam_layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps)
    raise ValueError(arch["norm"])


def _rope(x, theta):
    """x: (heads, L, D) float32, position i at row i."""
    length, d = x.shape[1], x.shape[2]
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, w: dict, spec: dict, prec: Precision):
    """One decoder layer over x (L, d_model), in `prec.act`."""
    arch = spec["architecture"]
    hq, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["derived"]["head_dim"]
    length = x.shape[0]
    act = jnp.dtype(prec.act)

    def linear(h, name):
        y = _mm(h, w[name], prec)
        bias = "b" + name[1:]
        if arch["qkv_bias"] and bias in w:
            y = y.astype(act) + w[bias].astype(act)
        return y.astype(act)

    h = _norm(x, w.get("ln1"), arch).astype(act)
    q, k, v = linear(h, "wq"), linear(h, "wk"), linear(h, "wv")
    q = q.reshape(length, hq, hd).transpose(1, 0, 2).astype(jnp.float32)
    k = k.reshape(length, hkv, hd).transpose(1, 0, 2).astype(jnp.float32)
    v = v.reshape(length, hkv, hd).transpose(1, 0, 2)
    q = _rope(q, spec["rope_theta"]).astype(act)
    k = _rope(k, spec["rope_theta"]).astype(prec.kv)
    v = v.astype(prec.kv)
    # GQA: query head h reads key/value head h // (hq // hkv)
    k = jnp.repeat(k, hq // hkv, axis=0)
    v = jnp.repeat(v, hq // hkv, axis=0)
    s = _mm(q, k.transpose(0, 2, 1), prec, prec.attn) * hd ** -0.5
    causal = jnp.tril(jnp.ones((length, length), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm(p, v, prec, prec.attn).astype(act)
    o = o.transpose(1, 0, 2).reshape(length, hq * hd)
    x = x + linear(o, "wo")
    h = _norm(x, w.get("ln2"), arch).astype(act)
    gate, up = linear(h, "wg"), linear(h, "wu")
    mid = (jax.nn.silu(gate.astype(jnp.float32)) * up).astype(act)
    return x + linear(mid, "wd")


@functools.lru_cache(maxsize=None)
def _programs(spec_json: str, prec: Precision):
    """The jitted pieces for one configuration and precision (compiled
    once each)."""
    spec = json.loads(spec_json)

    @jax.jit
    def embed(key, tokens):
        table = W.global_weights(key, spec)["embed"]
        return jnp.take(table, tokens, axis=0).astype(prec.act)

    @jax.jit
    def run_layer(key, i, x):
        return layer(x, W.layer_weights(key, i, spec), spec, prec)

    @jax.jit
    def logits(key, x, rows):
        g = W.global_weights(key, spec)
        h = _norm(x[rows], g.get("final_norm"), spec["architecture"])
        return _mm(h.astype(prec.act), g["embed"].T, prec)

    return embed, run_layer, logits


def forward_logits(spec: dict, key, tokens: np.ndarray, rows: np.ndarray,
                   prec: Precision):
    """Forward one padded sequence `tokens` (L,) at `prec`; the float32
    logits at each position in `rows`."""
    embed, run_layer, logits = _programs(json.dumps(spec, sort_keys=True),
                                         prec)
    x = embed(key, jnp.asarray(tokens, jnp.int32))
    for i in range(spec["num_hidden_layers"]):
        x = run_layer(key, jnp.asarray(i, jnp.int32), x)
    return logits(key, x, jnp.asarray(rows, jnp.int32))


@jax.jit
def gaps_below_best(logits, tokens):
    """At each row, how far the logit of `tokens[row]` lies below the
    row's best."""
    got = jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0]
    return logits.max(-1) - got

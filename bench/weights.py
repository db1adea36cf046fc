"""Seeded weights, made by the benchmark and never by the program.

Each weight has a role (`embed`, `wq`, ...) and a layer; its values are
`value(key, role, layer)`, a pure function of the seed. The program's
parameter tree is filled with them in one jitted call on the device, in the
layout and dtypes the program's own `init_params` would give (read from its
abstract tree, so the program's defaults are what is served). The plain
reference draws the same values layer by layer from the same seed, after the
program's state is freed: it takes nothing the program made.

Scales follow the program's initialisation (linear weights N(0, 1/d_in),
embedding N(0, 0.02^2)), except that biases and norm gains are drawn away
from 0 and 1, so that a path that drops them shows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# role -> (stream id, kind)
ROLES = {
    "embed": (1, "embed"), "final_norm": (2, "gain"),
    "ln1": (10, "gain"), "ln2": (11, "gain"),
    "wq": (20, "linear"), "bq": (21, "bias"),
    "wk": (22, "linear"), "bk": (23, "bias"),
    "wv": (24, "linear"), "bv": (25, "bias"),
    "wo": (26, "linear"),
    "wg": (30, "linear"), "wu": (31, "linear"), "wd": (32, "linear"),
}
GLOBAL_ROLES = ("embed", "final_norm")

# the program's parameter paths (dense decoders) -> roles
_PROGRAM_GLOBAL = {"embed/table": "embed", "final_norm/g": "final_norm"}
_PROGRAM_LAYER_PREFIX = "segments/0/0_dense/"
_PROGRAM_LAYER = {
    "ln1/g": "ln1", "ln2/g": "ln2",
    "attn/q/w": "wq", "attn/q/b": "bq", "attn/k/w": "wk", "attn/k/b": "bk",
    "attn/v/w": "wv", "attn/v/b": "bv", "attn/o/w": "wo",
    "mlp/gate/w": "wg", "mlp/up/w": "wu", "mlp/down/w": "wd",
}


def seed_key(seed: int):
    """A PRNG key from any integer seed (more than 32 bits included)."""
    s = seed % (1 << 64)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def role_shapes(spec: dict) -> dict:
    """Per-layer (or global) shape of every role the configuration has."""
    d, f = spec["hidden_size"], spec["intermediate_size"]
    hd = spec["derived"]["head_dim"]
    q = spec["num_attention_heads"] * hd
    kv = spec["num_key_value_heads"] * hd
    arch = spec["architecture"]
    shapes = {"embed": (spec["derived"]["embedding_size"], d),
              "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
              "wg": (d, f), "wu": (d, f), "wd": (f, d)}
    if arch["norm"] == "rmsnorm":
        shapes.update(final_norm=(d,), ln1=(d,), ln2=(d,))
    if arch["qkv_bias"]:
        shapes.update(bq=(q,), bk=(kv,), bv=(kv,))
    return shapes


def value(key, role: str, layer, shape) -> jax.Array:
    """The float32 values of `role` in `layer` (0 for global roles)."""
    stream, kind = ROLES[role]
    k = jax.random.fold_in(jax.random.fold_in(key, stream), layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "linear":
        return z * shape[0] ** -0.5
    if kind == "embed":
        return z * 0.02
    if kind == "gain":
        return 1.0 + 0.1 * z
    return 0.1 * z                                   # bias


def layer_weights(key, layer, spec: dict, dtype=jnp.float32) -> dict:
    """Every per-layer role of `layer`, in `dtype` (jit-able)."""
    shapes = role_shapes(spec)
    return {r: value(key, r, layer, s).astype(dtype)
            for r, s in shapes.items() if r not in GLOBAL_ROLES}


def global_weights(key, spec: dict, dtype=jnp.float32) -> dict:
    shapes = role_shapes(spec)
    return {r: value(key, r, 0, shapes[r]).astype(dtype)
            for r in GLOBAL_ROLES if r in shapes}


def _path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def program_roles(abstract_params, spec: dict) -> list:
    """(path, role, per_layer) for every leaf of the program's parameter
    tree; an error where the tree has a leaf with no role, or a role whose
    shape differs from the configuration's."""
    shapes = role_shapes(spec)
    layers = spec["num_hidden_layers"]
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract_params)[0]:
        p = _path_str(path)
        if p in _PROGRAM_GLOBAL:
            role, per_layer, shape = _PROGRAM_GLOBAL[p], False, leaf.shape
        elif p.startswith(_PROGRAM_LAYER_PREFIX) and \
                p[len(_PROGRAM_LAYER_PREFIX):] in _PROGRAM_LAYER:
            role = _PROGRAM_LAYER[p[len(_PROGRAM_LAYER_PREFIX):]]
            per_layer, shape = True, leaf.shape[1:]
            if leaf.shape[0] != layers:
                raise ValueError(f"{p}: {leaf.shape[0]} layers stacked, the "
                                 f"configuration has {layers}")
        else:
            raise ValueError(f"program parameter {p} {leaf.shape} has no "
                             f"role in the benchmark's weights")
        if role not in shapes or tuple(shape) != tuple(shapes[role]):
            raise ValueError(f"program parameter {p}: shape {tuple(shape)}, "
                             f"configuration {shapes.get(role)}")
        out.append((p, role, per_layer))
    missing = set(shapes) - {r for _, r, _ in out}
    if missing:
        raise ValueError(f"the program's tree lacks roles {sorted(missing)}")
    return out


def program_params(key, abstract_params, spec: dict):
    """The program's parameter tree filled with the seeded values, made on
    the device in one jitted call, each leaf in the program's dtype."""
    roles = program_roles(abstract_params, spec)
    flat, treedef = jax.tree_util.tree_flatten(abstract_params)
    shapes = role_shapes(spec)
    layers = spec["num_hidden_layers"]

    def build(k):
        leaves = []
        for leaf, (_, role, per_layer) in zip(flat, roles):
            if per_layer:
                v = jax.vmap(lambda i: value(k, role, i, shapes[role]))(
                    jnp.arange(layers))
            else:
                v = value(k, role, 0, shapes[role])
            leaves.append(v.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(key)

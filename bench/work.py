"""The yardstick's arithmetic: the chips' peaks, and the operations and bytes
that the served model's work needs, computed from the configuration's sizes.

Counts are of what the algorithm needs (valid tokens only, attention at each
token's real context, the KV cache read once at its stored width), never of
the blocks a kernel happens to visit, so the yardstick reads the same work
whatever implements it.
"""
from __future__ import annotations

import dataclasses

# Published peaks of one chip, keyed by `jax.Device.device_kind`.
# Source: Google Cloud documentation, "TPU v5e" (system architecture table).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_s": 197e12,
        "int8_op_s": 393e12,
        "hbm_byte_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of `device_kind`; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}   # per element, by dtype


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of a dense decoder that the arithmetic needs."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    kv_bytes: int       # bytes per element of the stored KV cache
    act_bytes: int      # bytes per element of the activations (q, out)

    @classmethod
    def from_spec(cls, spec: dict) -> "Shape":
        return cls(layers=spec["num_hidden_layers"],
                   d_model=spec["hidden_size"],
                   heads=spec["num_attention_heads"],
                   kv_heads=spec["num_key_value_heads"],
                   head_dim=spec["derived"]["head_dim"],
                   d_ff=spec["intermediate_size"],
                   vocab=spec["derived"]["embedding_size"],
                   kv_bytes=BYTES[spec["as_run"]["kv_dtype"]],
                   act_bytes=BYTES[spec["as_run"]["act_dtype"]])

    @property
    def layer_matmul_params(self) -> int:
        d, q, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return d * q + 2 * d * kv + q * d + 3 * d * self.d_ff


def tokens_flops(shape: Shape, total_tokens: int, total_keys: int) -> int:
    """Forward FLOPs of `total_tokens` tokens whose contexts (the keys each
    attends over, itself included) sum to `total_keys`: every linear, the
    attention at those contexts, and the lm_head."""
    dense = 2 * shape.layers * shape.layer_matmul_params + \
        2 * shape.d_model * shape.vocab
    return dense * total_tokens + \
        4 * shape.layers * shape.heads * shape.head_dim * total_keys


def prefix_keys(start: int, stop: int) -> int:
    """Sum of the contexts of prompt positions start..stop-1: position i
    attends over i + 1 keys."""
    return (stop * (stop + 1) - start * (start + 1)) // 2


def decode_attention_work(shape: Shape, keys: int) -> tuple:
    """(flops, bytes) of one decode row's attention over all layers: one
    query per head over `keys` cached keys; K and V read once, q in, out."""
    per_head_flops = 4 * shape.head_dim * keys
    kv = 2 * keys * shape.kv_heads * shape.head_dim * shape.kv_bytes
    qo = 2 * shape.heads * shape.head_dim * shape.act_bytes
    return (shape.layers * shape.heads * per_head_flops,
            shape.layers * (kv + qo))


def prefill_attention_work(shape: Shape, start: int, stop: int) -> tuple:
    """(flops, bytes) over all layers of the attention of prompt positions
    start..stop-1 of one row, done in one launch: each query over its causal
    context, the `stop` keys and values read once, q in and out."""
    n = stop - start
    flops = 4 * shape.layers * shape.heads * shape.head_dim * \
        prefix_keys(start, stop)
    kv = 2 * stop * shape.kv_heads * shape.head_dim * shape.kv_bytes
    qo = 2 * n * shape.heads * shape.head_dim * shape.act_bytes
    return flops, shape.layers * (kv + qo)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> tuple:
    """(share of the roofline in %, which bound binds): the least time the
    chip could take, the larger of flops over peak and bytes over bandwidth,
    over the time measured. None when nothing was measured."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None, None
    pk = peaks(device_kind)
    t_flop = flops / pk["bf16_flop_s"]
    t_byte = nbytes / pk["hbm_byte_s"]
    bound = "flops" if t_flop >= t_byte else "bytes"
    return 100.0 * max(t_flop, t_byte) / seconds, bound

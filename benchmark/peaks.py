"""Published peaks by `device_kind`, and the bytes a step must move, computed
from shapes. A device that is not in the table is an error, not a default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB of HBM at 819 GB/s a chip. JAX reports the kind as "TPU v5 lite".
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in CHIP_PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return CHIP_PEAKS[device_kind]


def tree_bytes(tree) -> int:
    """Bytes of every array in a parameter tree as the device holds it."""
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(tree))


def decode_weight_bytes(params) -> int:
    """Bytes of weights one decode round must read: every layer and the head
    once. Of the embedding table a round reads one row a sequence, so the
    table itself is left out (where the head is tied to it, it is the head)."""
    total = tree_bytes(params)
    if "lm_head" in params:
        total -= tree_bytes(params["embed"])
    return total


def kv_row_bytes(cfg, kv_quant: str, scale_bytes: int = 2) -> int:
    """Bytes of K and V one cached token holds over all layers: int8 payload
    with one scale a head, or bf16."""
    heads = cfg.n_kv_heads * cfg.n_layers * 2
    hd = cfg.resolved_head_dim
    return heads * (hd + scale_bytes) if kv_quant == "int8" else heads * hd * 2


def decode_round_bytes(params, cfg, kv_quant: str, live_tokens: float) -> float:
    """The least a decode round reads from HBM: the weights once, and the
    cached rows of every live sequence (`live_tokens` summed over them)."""
    return decode_weight_bytes(params) + kv_row_bytes(cfg, kv_quant) * live_tokens

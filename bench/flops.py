"""Operations and bytes the Qwen2-family forward pass needs, from shapes.

Counted from the published sizes of a configuration file (see
`references/qwen2.py::dims`): a multiply-add is two operations, and only
matrix products count (norms, rotary embedding, softmax and the embedding
lookup are left out). Bytes are what the algorithm must move at least:
every weight it uses once per call, the keys and values it reads, the keys
and values it writes.
"""
from __future__ import annotations

BF16 = 2


def matmul_params(d: dict) -> int:
    """Weights one token multiplies through: attention projections, the
    FFN and the output head."""
    D, H, K, h = d["D"], d["H"], d["K"], d["h"]
    attn = D * (H + 2 * K) * h + H * h * D
    return d["L"] * (attn + 3 * D * d["F"]) + D * d["V"]


def attention_flops(d: dict, keys: int) -> int:
    """Scores and weighted values of one query over `keys` positions."""
    return 4 * d["L"] * d["H"] * d["h"] * keys


def token_flops(d: dict, keys: int) -> int:
    """One token's forward pass at a context of `keys` positions."""
    return 2 * matmul_params(d) + attention_flops(d, keys)


def prompt_flops(d: dict, start: int, end: int) -> int:
    """Prompt positions [start, end), each attending to itself and all
    before it."""
    n = end - start
    keys = (end * (end + 1) - start * (start + 1)) // 2
    return 2 * matmul_params(d) * n + attention_flops(d, keys)


def kv_bytes_per_token(d: dict) -> int:
    return d["L"] * 2 * d["K"] * d["h"] * BF16


def weight_bytes(d: dict) -> float:
    """Bytes of the weights a call reads: all of them once."""
    D, H, K, h = d["D"], d["H"], d["K"], d["h"]
    attn = (D * (H + 2 * K) * h + H * h * D + (H + 2 * K) * h + 2 * D) * BF16
    ffn = 3 * D * d["F"] * BF16
    head = d["V"] * D * BF16      # the embedding lookup reads a few rows
    return d["L"] * (attn + ffn) + head + D * BF16


def decode_step(d: dict, batch: int, keys: int) -> tuple[float, float]:
    """(operations, bytes) of one decode step over `batch` live slots whose
    queries attend `keys` positions in total (each slot's context with its
    new token)."""
    ops = 2 * matmul_params(d) * batch + attention_flops(d, keys)
    nbytes = (weight_bytes(d) + kv_bytes_per_token(d) * keys
              + kv_bytes_per_token(d) * batch)
    return float(ops), float(nbytes)

"""Operations and bytes each unit of work needs, from its shapes: the
model's FLOPs per token and per step, and each attention kernel's FLOPs
and bytes per call, for the dense Llama layer (``Dims``, from a
configuration file; ``arch/llama.py`` counts through these), and the
roofline every architecture's kernels are read against.

Counting rules: a multiply-add is 2 FLOPs; a token at position ``p``
attends ``p + 1`` keys (itself included); the LM head runs once per
decoded token and once per prefill (the prompt's last position, the
only logits a prefill needs).  Bytes are what the algorithm must move
between HBM and the chip, at the served dtype: nothing re-read, no
padding, no page past a slot's length.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    hd: int
    ff: int
    vocab: int
    itemsize: int = 2

    @classmethod
    def of(cls, conf) -> "Dims":
        h = conf["num_attention_heads"]
        return cls(conf["num_hidden_layers"], conf["hidden_size"], h,
                   conf["num_key_value_heads"], conf["hidden_size"] // h,
                   conf["intermediate_size"], conf["vocab_size"],
                   {"bfloat16": 2, "float16": 2, "float32": 4}[
                       conf["torch_dtype"]])


def layer_matmul_flops(m: Dims) -> int:
    """Weight products of one layer for one token: q, k, v, o and the
    three MLP matrices."""
    q, kv = m.heads * m.hd, m.kv_heads * m.hd
    return 2 * m.d * (q + 2 * kv) + 2 * q * m.d + 3 * 2 * m.d * m.ff


def attn_flops(m: Dims, keys: int) -> int:
    """QK and PV of one query token over ``keys`` keys, all heads."""
    return 4 * m.heads * m.hd * keys


def decode_token_flops(m: Dims, pos: int) -> int:
    """The model FLOPs of decoding the token written at ``pos``."""
    return (m.layers * (layer_matmul_flops(m) + attn_flops(m, pos + 1))
            + 2 * m.d * m.vocab)


def prefill_flops(m: Dims, length: int, offset: int) -> int:
    """The model FLOPs of prefilling ``length`` tokens after ``offset``
    cached ones (a cold prefill has offset 0)."""
    keys = length * offset + length * (length + 1) // 2
    return (m.layers * (length * layer_matmul_flops(m)
                        + 4 * m.heads * m.hd * keys)
            + 2 * m.d * m.vocab)


def fused_decode_call(m: Dims, positions) -> tuple:
    """(FLOPs, bytes) of one fused paged decode call (one layer) over the
    active slots at ``positions``: RoPE, the new token's K/V page write,
    and attention over ``pos + 1`` keys.  Bytes: every key and value read
    once, the new K/V written, q read and the output written."""
    kv_row = 2 * m.kv_heads * m.hd * m.itemsize      # one token's K and V
    q_row = m.heads * m.hd * m.itemsize
    flops = sum(attn_flops(m, p + 1) for p in positions)
    nbytes = sum(kv_row * (p + 1) + kv_row + 2 * q_row for p in positions)
    return flops, nbytes


def prefill_attn_call(m: Dims, length: int, offset: int) -> tuple:
    """(FLOPs, bytes) of one paged prefill attention call (one layer):
    ``length`` causal queries after ``offset`` cached keys.  Bytes: q and
    the output, and the K/V of all ``offset + length`` keys once."""
    keys = length * offset + length * (length + 1) // 2
    kv_row = 2 * m.kv_heads * m.hd * m.itemsize
    q_row = m.heads * m.hd * m.itemsize
    return (4 * m.heads * m.hd * keys,
            kv_row * (offset + length) + 2 * q_row * length)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])

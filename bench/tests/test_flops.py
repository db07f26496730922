"""Each FLOP and byte count against a count by hand at a tiny shape:
d=8, 2 heads over 1 kv head of size 4, ff=16, vocab=10, 3 layers, bf16."""
import pytest

from bench import flops as F

M = F.Dims(layers=3, d=8, heads=2, kv_heads=1, hd=4, ff=16, vocab=10,
           itemsize=2)


def test_layer_matmuls():
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16, down 16x8: 2 per MAC
    assert F.layer_matmul_flops(M) == 2 * (64 + 32 + 32 + 64 + 128 * 3)


def test_decode_token_at_position_5_attends_6_keys():
    attn = 2 * 2 * 4 * 6 * 2                 # QK and PV, 2 heads, hd 4
    per_layer = F.layer_matmul_flops(M) + attn
    assert F.decode_token_flops(M, 5) == 3 * per_layer + 2 * 8 * 10


def test_prefill_of_3_tokens_after_2_cached():
    keys = 3 + 4 + 5                          # causal: 3, 4, 5 keys
    want = 3 * (3 * F.layer_matmul_flops(M) + 4 * 2 * 4 * keys) + 2 * 8 * 10
    assert F.prefill_flops(M, 3, 2) == want


def test_fused_decode_call_two_slots():
    flops, nbytes = F.fused_decode_call(M, [0, 9])
    assert flops == 4 * 2 * 4 * (1 + 10)
    kv_row, q_row = 2 * 1 * 4 * 2, 2 * 4 * 2   # K+V of a token, q of a token
    assert nbytes == kv_row * (1 + 10) + 2 * kv_row + 2 * 2 * q_row


def test_prefill_attention_call():
    flops, nbytes = F.prefill_attn_call(M, 3, 2)
    assert flops == 4 * 2 * 4 * 12
    assert nbytes == 16 * 5 + 2 * 16 * 3


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert F.roofline_s(1000, 50, peak) == pytest.approx(10.0)
    assert F.roofline_s(100, 50, peak) == pytest.approx(5.0)


def test_dims_of_a_configuration():
    from bench import spec
    m = F.Dims.of(spec.load_json(f"{spec.BENCH_DIR}/configs/yi-34b-8l.json"))
    assert (m.layers, m.d, m.heads, m.kv_heads, m.hd, m.ff, m.vocab,
            m.itemsize) == (8, 7168, 56, 8, 128, 20480, 64000, 2)

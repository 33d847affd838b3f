"""Operation and byte counts of bench/roofline.py against hand counts for
a tiny configuration."""
import pytest

from bench import roofline

# d 8, 2 heads of 4, 1 KV head, ff 16, vocab 10, 2 layers, gated
DM = {"layers": 2, "d": 8, "heads": 2, "kv": 1, "dh": 4, "ff": 16,
      "vocab": 10, "norm": "rmsnorm", "gated": True}


def test_params_and_bytes_by_hand():
    # q 8*2*4 + k,v 2*8*1*4 + o 2*4*8 = 64 + 64 + 64; ffn 3*8*16 = 384
    assert roofline.layer_matmul_params(DM) == 576
    assert roofline.kv_bytes_per_token(DM) == 2 * 2 * 1 * 4 * 2
    # layers (576 + 2 norms of 8) + head 8*10, bf16
    assert roofline.weight_bytes(DM) == (2 * (576 + 16) + 80) * 2
    gelu = dict(DM, gated=False, norm="layernorm")
    # ffn 2*8*16 = 256 + biases 16 + 8, 4 norm vectors
    assert roofline.weight_bytes(gelu) == \
        (2 * (192 + 256 + 32 + 24) + 80) * 2


def test_decode_tick_by_hand():
    ops, nbytes = roofline.decode_tick(DM, rows=3, context=20)
    # matmuls 2*3*(2*576 + 80); attention 4*(20+3)*4*2 heads*2 layers
    assert ops == 2 * 3 * (2 * 576 + 80) + 4 * 23 * 4 * 2 * 2
    assert nbytes == roofline.weight_bytes(DM) + 32 * 23 + 3 * 8 * 2


def test_prefill_by_hand():
    ops, nbytes = roofline.prefill(DM, [(5, 0), (3, 16)])
    attn = 4 * 4 * 2 * 2            # per (query, key) pair
    want = (2 * 5 * 2 * 576 + 2 * 80 + attn * 15 +          # 5*6/2
            2 * 3 * 2 * 576 + 2 * 80 + attn * (3 * 16 + 6))
    assert ops == want
    assert nbytes == roofline.weight_bytes(DM) + 32 * (5 + 19) + \
        (5 + 3) * 8 * 2


def test_least_time_takes_the_binding_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline.least_time(1000.0, 50.0, peaks) == pytest.approx(10.0)
    assert roofline.least_time(100.0, 50.0, peaks) == pytest.approx(5.0)

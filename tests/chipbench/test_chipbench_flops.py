"""Operation and byte counts against hand counts at Qwen2-1.5B widths."""
import pytest

from harness import flops, spec

CFG = spec.config("qwen2-1.5b")


def test_dims():
    assert flops.dims(CFG) == {"d": 1536, "L": 28, "H": 12, "K": 2,
                               "hd": 128, "F": 8960, "V": 151936}


def test_layer_matmuls_by_hand():
    # q 1536x1536, k and v 1536x256 each, o 1536x1536, gate/up/down 1536x8960
    macs = 1536 * 1536 + 2 * 1536 * 256 + 1536 * 1536 + 3 * 1536 * 8960
    assert flops.layer_matmul_flops(CFG) == 2 * macs == 93_585_408


def test_prefill_and_decode_by_hand():
    n = 1000
    per_token = 28 * 93_585_408
    pairs = n * (n + 1) // 2
    attn = 4 * 28 * 12 * 128 * pairs
    head = 2 * 1536 * 151936
    assert flops.prefill_flops(CFG, n) == n * per_token + attn + head
    ctx = 4095
    assert flops.decode_flops(CFG, ctx) == \
        per_token + 4 * 28 * 12 * 128 * 4096 + head


@pytest.mark.parametrize("offset,n", [(0, 512), (512, 512), (7168, 512),
                                      (0, 100)])
def test_flash_prefill_cost_by_hand(offset, n):
    f, b = flops.flash_prefill_cost(CFG, offset, n)
    pairs = sum(offset + i + 1 for i in range(n))
    assert f == 4 * 12 * 128 * pairs
    # q and out: n x 12 x 128 bf16 each; K and V: (offset + n) x 2 x 128
    assert b == 2 * (2 * n * 12 * 128 + 2 * (offset + n) * 2 * 128)


def test_least_time_roofline():
    # one 512-token chunk of prefill attention at offset 7168: compute bound
    f, b = flops.flash_prefill_cost(CFG, 7168, 512)
    assert flops.least_time(f, b, 197e12, 819e9) == pytest.approx(f / 197e12)
    # a short chunk over a long context reads more than it computes
    f, b = flops.flash_prefill_cost(CFG, 7168, 1)
    assert flops.least_time(f, b, 197e12, 819e9) == pytest.approx(b / 819e9)

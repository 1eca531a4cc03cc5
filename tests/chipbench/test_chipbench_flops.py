"""Operation and byte counts against hand counts at Qwen2-1.5B widths, and
the windowed attention counts against a count of every query-key pair."""
import pytest

from harness import flops, spec

CFG = spec.config("qwen2-1.5b")
FAM = spec.family(CFG["family"])
LAYER = FAM.attention(CFG)[0]


def test_dims():
    assert FAM.dims(CFG) == {"d": 1536, "L": 28, "H": 12, "K": 2,
                             "hd": 128, "F": 8960, "V": 151936}
    assert FAM.attention(CFG) == [flops.Attn(12, 2, 128, 0)] * 28


def test_layer_matmuls_by_hand():
    # q 1536x1536, k and v 1536x256 each, o 1536x1536, gate/up/down 1536x8960
    macs = 1536 * 1536 + 2 * 1536 * 256 + 1536 * 1536 + 3 * 1536 * 8960
    assert FAM.layer_matmul_flops(CFG) == 2 * macs == 93_585_408
    assert FAM.token_flops(CFG) == 28 * 93_585_408


def test_prefill_and_decode_by_hand():
    n = 1000
    per_token = 28 * 93_585_408
    pairs = n * (n + 1) // 2
    attn = 4 * 28 * 12 * 128 * pairs
    head = 2 * 1536 * 151936
    assert flops.prefill_flops(FAM, CFG, n) == n * per_token + attn + head
    ctx = 4095
    assert flops.decode_flops(FAM, CFG, ctx) == \
        per_token + 4 * 28 * 12 * 128 * 4096 + head


@pytest.mark.parametrize("offset,n", [(0, 512), (512, 512), (7168, 512),
                                      (0, 100)])
def test_flash_prefill_cost_by_hand(offset, n):
    f, b = flops.flash_prefill_cost(LAYER, offset, n)
    pairs = sum(offset + i + 1 for i in range(n))
    assert f == 4 * 12 * 128 * pairs
    # q and out: n x 12 x 128 bf16 each; K and V: (offset + n) x 2 x 128
    assert b == 2 * (2 * n * 12 * 128 + 2 * (offset + n) * 2 * 128)


def test_least_time_roofline():
    # one 512-token chunk of prefill attention at offset 7168: compute bound
    f, b = flops.flash_prefill_cost(LAYER, 7168, 512)
    assert flops.least_time(f, b, 197e12, 819e9) == pytest.approx(f / 197e12)
    # a short chunk over a long context reads more than it computes
    f, b = flops.flash_prefill_cost(LAYER, 7168, 1)
    assert flops.least_time(f, b, 197e12, 819e9) == pytest.approx(b / 819e9)


@pytest.mark.parametrize("offset,n,window", [
    (0, 1, 0), (0, 100, 0), (37, 200, 0), (0, 100, 16), (0, 16, 16),
    (10, 5, 16), (10, 30, 16), (500, 512, 128), (4096, 1, 1024),
    (0, 2048, 4096)])
def test_attention_counts_every_pair(offset, n, window):
    """Query at position p reads keys max(0, p - window + 1)..p (0..p where
    window is 0): the pairs and the distinct keys of n queries."""
    lo = [max(0, p - window + 1) if window else 0
          for p in range(offset, offset + n)]
    assert flops.attn_pairs_prefill(offset, n, window) == \
        sum(p + 1 - l for p, l in zip(range(offset, offset + n), lo))
    assert flops.attn_keys(offset, n, window) == offset + n - min(lo)


class _Mixed:
    """A family of two layers: one windowed, one full."""

    @staticmethod
    def attention(cfg):
        return [flops.Attn(8, 2, 64, 256), flops.Attn(8, 2, 64, 0)]

    @staticmethod
    def token_flops(cfg):
        return 1000

    @staticmethod
    def head_flops(cfg):
        return 10


def test_windowed_layers_count_their_window():
    n, ctx = 1000, 999
    per_pair = 4 * 8 * 64
    want = (n * 1000 + per_pair * (flops.attn_pairs_prefill(0, n, 256)
                                    + n * (n + 1) // 2) + 10)
    assert flops.prefill_flops(_Mixed, {}, n) == want
    assert flops.decode_flops(_Mixed, {}, ctx) == \
        1000 + per_pair * (256 + ctx + 1) + 10

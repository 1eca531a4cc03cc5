"""Trace reduction, on a trace recorded on one v5e chip: one 1024-token
Qwen2-1.5B prefill (two 512-token chunks, 28 layers, operator segments)
then four ragged decode steps of 8 rows (data/prefill_decode.xplane.pb.gz)."""
import pytest

from conftest import BENCH
from harness import trace

DATA = BENCH.parent / "tests" / "chipbench" / "data" / \
    "prefill_decode.xplane.pb.gz"


@pytest.fixture(scope="module")
def dev():
    planes = trace.load(str(DATA))
    assert list(planes) == ["/device:TPU:0"]
    return planes["/device:TPU:0"]


def test_busy_is_the_union_of_op_intervals(dev):
    # independent sweep: +1 at each start, -1 at each end; busy where > 0
    ev = sorted([(s, 1) for _, s, _ in dev.ops] +
                [(e, -1) for _, _, e in dev.ops], key=lambda x: (x[0], -x[1]))
    depth, last, busy = 0, None, 0
    for t, d in ev:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert trace.busy_ns(dev) == busy
    total = sum(e - s for _, s, e in dev.ops)
    assert busy < total                    # nested ops (a while's body)
    span = max(e for _, _, e in dev.ops) - min(s for _, s, _ in dev.ops)
    assert 0 < busy < span


def test_kernel_time_sums_its_events(dev):
    ns, n = trace.kernel_ns(dev, "flash_prefill_attention")
    assert n == 2 * 28                     # two chunks x 28 layers
    want = sum(e - s for name, s, e in dev.ops
               if name.startswith("%flash_prefill_attention"))
    assert ns == want > 0
    ns, n = trace.kernel_ns(dev, "flash_decode_attention")
    assert n == 4 * 28                     # four steps x 28 layers
    assert trace.kernel_ns(dev, "no_such_kernel") == (0, 0)


def test_breakdown(dev):
    top = trace.top_ops(dev)
    assert len(top) == 10
    secs = [s for _, s in top]
    assert secs == sorted(secs, reverse=True)
    # the prefill state's K/V copy is what this trace spends most on
    assert top[0][0] == "copy bf16[28,1,16384,2,128]"
    gaps = trace.idle_gaps(dev)
    assert all(" -> " in k and s > 0 for k, s in gaps)
    span = (max(e for _, _, e in dev.ops) - min(s for _, s, _ in dev.ops))
    idle = span - trace.busy_ns(dev)
    assert sum(s for _, s in trace.idle_gaps(dev, 10**6)) == \
        pytest.approx(idle * 1e-9)


@pytest.mark.parametrize("iv,want", [
    ([], []),
    ([(0, 5), (5, 7)], [(0, 7)]),
    ([(3, 4), (0, 10), (12, 13)], [(0, 10), (12, 13)]),
    ([(0, 2), (1, 3), (4, 6)], [(0, 3), (4, 6)]),
])
def test_union(iv, want):
    assert trace.union(iv) == want


def test_op_and_module_keys():
    assert trace.op_key("%copy.10 = bf16[28,1,8192,2,128]{4,3,2,1,0} "
                        "copy(bf16[28,1,8192,2,128]{4,3} %x)") == \
        "copy bf16[28,1,8192,2,128]"
    assert trace.op_key("%broadcast.clone.1 = bf16[8]{0} broadcast(x)") == \
        "broadcast.clone bf16[8]"
    assert trace.module_key("jit_seg(9554575445060882225)") == "jit_seg"

"""Roofline share of the Pallas prefill attention kernel: the least time
the window's prefill attention needs (per attention layer and 512-token
chunk, the larger of its FLOPs at the bf16 peak and the bytes of its
queries, output and the K/V context it reads at the HBM bandwidth) over the
summed device time of the kernel's events in the trace, in percent."""
import math

from harness import flops
from harness.work import kernel_roofline, prompts


def read(run):
    pk = run.peaks
    layers = run.family.attention(run.cfg)
    chunk = int(run.cell["chunk"])
    least = 0.0
    for n in prompts(run):
        for off in range(0, n, chunk):
            m = min(chunk, n - off)
            # fsum: the chunk's layers summed exactly, then rounded once
            least += math.fsum(
                flops.least_time(*flops.flash_prefill_cost(a, off, m),
                                 pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
                for a in layers)
    return kernel_roofline(run, "flash_prefill_attention", least)

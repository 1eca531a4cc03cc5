"""Roofline share of the Pallas prefill attention kernel: the least time
the window's prefill attention needs (per layer and 512-token chunk, the
larger of its FLOPs at the bf16 peak and the bytes of its queries, output
and real K/V context at the HBM bandwidth) over the summed device time of
the kernel's events in the trace, in percent."""
from harness import flops
from harness.work import kernel_roofline, prompts


def read(run):
    pk = run.peaks
    layers = flops.dims(run.cfg)["L"]
    chunk = int(run.cell["chunk"])
    least = 0.0
    for n in prompts(run):
        for off in range(0, n, chunk):
            f, b = flops.flash_prefill_cost(run.cfg, off, min(chunk, n - off))
            least += layers * flops.least_time(
                f, b, pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
    return kernel_roofline(run, "flash_prefill_attention", least)

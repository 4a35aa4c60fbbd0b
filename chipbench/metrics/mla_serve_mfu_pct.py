"""Useful FLOPs of the traced requests of a latent-attention configuration
(``flops_mla.request_flops``: prefill in the expanded form, decode in the
absorbed form, routed experts at the chip's held share), over the traced
window times the chip's bf16 peak: the cell's share of the whole step."""
from chipbench import flops_mla
from chipbench.peaks import peaks_for


def read(run):
    if run.trace is None or "kv_lora_rank" not in run.config:
        return None
    n = len(run.trace.spans("request"))
    if not n:
        return None
    work = sum(flops_mla.request_flops(run.config, r["batch"], r["prompt"],
                                       r["new"]) for r in run.requests[:n])
    return 100.0 * work / run.trace.window_s \
        / peaks_for(run.device_kind).flops_bf16

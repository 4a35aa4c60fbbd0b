"""Useful FLOPs of the traced requests (prompt and generated tokens, the
routed experts only, causal attention at half), over the traced window
times the chip's peak."""
from chipbench import flops
from chipbench.peaks import peaks_for


def read(run):
    if run.trace is None:
        return None
    n = len(run.trace.spans("request"))
    if not n:
        return None
    work = sum(flops.request_flops(run.config, r["batch"], r["prompt"],
                                   r["new"]) for r in run.requests[:n])
    return 100.0 * work / run.trace.window_s \
        / peaks_for(run.device_kind).flops_bf16

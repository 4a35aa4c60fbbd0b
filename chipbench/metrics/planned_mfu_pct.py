"""Useful FLOPs of one call of the planned program (from its shapes), over
its device time per call in the trace times the chip's peak: the device
time is the busy time inside the traced ``planned_call`` spans, over the
calls they hold."""
from chipbench import flops
from chipbench.peaks import peaks_for


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans("planned_call")
    calls = sum(p["calls"] for p in run.plans[:len(spans)])
    busy = sum(run.trace.busy_s(s, e) for s, e in spans)
    if not calls or busy <= 0:
        return None
    t, c = run.traffic, run.config
    per_call = getattr(flops, f"{t['program']}_flops")(c, t["batch"], t["seq"])
    return 100.0 * per_call * calls / busy / peaks_for(run.device_kind).flops_bf16

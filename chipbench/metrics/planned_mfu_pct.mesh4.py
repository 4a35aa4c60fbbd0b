"""``planned_mfu_pct`` on a host of several chips: useful FLOPs of the
traced calls of the planned program (from its shapes) over the device time
those calls took on all of the host's chips together (each chip's busy time
inside the traced ``planned_call`` spans, summed) times one chip's peak.  A
chosen program that runs on one chip and one sharded over all of them are
both held to the peak of the chip time they used."""
from chipbench import flops
from chipbench.peaks import peaks_for


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans("planned_call")
    calls = sum(p["calls"] for p in run.plans[:len(spans)])
    # busy_s averages over the chips: times their number is the sum
    busy = len(run.trace.devices) * sum(run.trace.busy_s(s, e)
                                        for s, e in spans)
    if not calls or busy <= 0:
        return None
    t, c = run.traffic, run.config
    per_call = getattr(flops, f"{t['program']}_flops")(c, t["batch"], t["seq"])
    return 100.0 * per_call * calls / busy / peaks_for(run.device_kind).flops_bf16

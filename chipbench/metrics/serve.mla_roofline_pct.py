"""The latent attention's share of its roofline in the traced requests: the
least time its work could take on the chip (``flops_mla.mla_least_s``:
projections, scores and weighted values, the prefill at the compute bound,
each decode step at the larger of its compute and memory bounds), over the
device self time of the ``attention`` region in the trace, which the
``serve_mla`` driver records as ``region.attention``."""
from chipbench import flops_mla
from chipbench.peaks import peaks_for


def read(run):
    if run.trace is None or "kv_lora_rank" not in run.config:
        return None
    self_s = sum(s["dur_s"] for s in run.spans
                 if s.get("name") == "region.attention")
    n = len(run.trace.spans("request"))
    if self_s <= 0 or not n:
        return None
    peaks = peaks_for(run.device_kind)
    least = sum(flops_mla.mla_least_s(run.config, r["batch"], r["prompt"],
                                      r["new"], peaks.flops_bf16,
                                      peaks.hbm_bw)
                for r in run.requests[:n])
    return 100.0 * least / self_s

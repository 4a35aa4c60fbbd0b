"""Reduction of a profiler trace recorded with the program's span sink on
(``repro.obs.enable_profiler``), beside ``trace.py``'s reduction of the
harness's annotations:

* the program's spans (``serve.*``, ``store.*``, ``plan.*``, ``ga.*``,
  ``eval.*``) and the harness's annotations, from the host planes;
* the device programs, by module name, from each TPU plane's
  ``XLA Modules`` line (``jit_serve_prefill(<id>)`` reads
  ``jit_serve_prefill``);
* each device operation of the ``XLA Ops`` line with its module, its model
  region and its self time.  The region is the innermost of ``REGIONS`` in
  the operation's ``tf_op`` stat (its HLO ``op_name``, such as
  ``jit(serve_decode)/while/body/closed_call/attention/kv_cache/
  dynamic_update_slice``), read from the event metadata of the raw
  ``.xplane.pb``, which ``ProfileData`` does not expose.  Self time is the
  operation's interval less the operations nested in it, so a ``while`` is
  not counted again on top of its body.

Host and device events share the profiler's clock.  ``serve_breakdown`` and
``plan_breakdown`` compute the serving and planning readings of a traced
run from them.  Run as a script, it runs one cell traced with the sink on
and prints those readings (the harness's own traced runs do not turn the
sink on):

    python -m chipbench.spans --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import bisect
import os
from dataclasses import dataclass, field

from chipbench import trace as tr
from chipbench.peaks import peaks_for

#: name prefixes of the program's spans; the harness's annotations
#: (``trace.ANNOTATIONS``) are read as well
PREFIXES = ("serve.", "store.", "plan.", "ga.", "eval.", "prepare.")
#: the program's model regions (``jax.named_scope``); the innermost one in
#: an operation's ``op_name`` is its region
REGIONS = ("embed", "attention", "kv_cache", "mlp", "moe", "norm", "head")
UNSCOPED = "-"
_DEVICE = "/device:TPU:"


@dataclass
class Op:
    start: int                        # ns, profiler clock
    end: int
    name: str                         # HLO instruction name, e.g. fusion.3
    module: str                       # e.g. jit_serve_decode
    region: str                       # one of REGIONS, or UNSCOPED
    self_ns: int = 0


@dataclass
class SpanTrace:
    devices: dict                     # plane -> merged busy [(s, e)] ns
    spans: list                       # [(name, s, e)] ns, by start
    modules: dict                     # plane -> [(name, s, e)] by start
    ops: dict = field(default_factory=dict)       # plane -> [Op] by start

    def count(self, name: str) -> int:
        return sum(1 for n, _, _ in self.spans if n == name)

    def of(self, name: str) -> list:
        return [(s, e) for n, s, e in self.spans if n == name]

    def _mean(self, per_device: list) -> float:
        return sum(per_device) / max(len(per_device), 1)

    def module_busy_s(self, module: str) -> float:
        """Device seconds of every execution of ``module`` (module events'
        durations), averaged over devices."""
        return self._mean([sum(e - s for n, s, e in mods if n == module)
                           for mods in self.modules.values()]) * 1e-9

    def region_self_s(self, region: str, module: str = None) -> float:
        """Self device seconds of the operations in ``region`` (of
        ``module`` only, where given), averaged over devices."""
        return self._mean([
            sum(o.self_ns for o in ops if o.region == region
                and (module is None or o.module == module))
            for ops in self.ops.values()]) * 1e-9

    def self_s(self, module: str = None) -> float:
        return self._mean([sum(o.self_ns for o in ops
                               if module is None or o.module == module)
                           for ops in self.ops.values()]) * 1e-9

    def idle_in(self, names: tuple, within: list = None) -> float:
        """Seconds the device was idle inside the union of the spans named
        ``names`` (clipped to the intervals ``within``, where given),
        averaged over devices."""
        union = _union([(s, e) for n, s, e in self.spans if n in names])
        if within is not None:
            union = _clip(union, _union(within))
        return self._mean([sum(b - a for a, b in _idle(busy, union))
                           for busy in self.devices.values()]) * 1e-9

    def named_gaps(self, start: int, end: int, top: int = 10) -> list:
        """The ``top`` longest idle gaps inside [start, end], each named by
        the innermost span (program or harness) that holds it."""
        found = [(b - a, a, b) for busy in self.devices.values()
                 for a, b in _idle(busy, [(start, end)])]
        found.sort(reverse=True)
        return [[self.holder((a + b) / 2), ns * 1e-9]
                for ns, a, b in found[:top]]

    def holder(self, t: float) -> str:
        best = None
        for n, s, e in self.spans:
            if s > t:
                break
            if t <= e and (best is None or e - s < best[1]):
                best = (n, e - s)
        return best[0] if best else "none"

    def top_ops(self, top: int = 10) -> list:
        """Operations by self time (summed over executions, averaged over
        devices), each named ``<module>/<region>/<instruction>``."""
        acc: dict = {}
        for ops in self.ops.values():
            for o in ops:
                k = f"{o.module}/{o.region}/{o.name}"
                acc[k] = acc.get(k, 0) + o.self_ns
        n = max(len(self.ops), 1)
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[k, ns * 1e-9 / n] for k, ns in ranked]


def _union(intervals: list) -> list:
    return tr._merge([iv for iv in intervals if iv[1] > iv[0]])


def _clip(a: list, b: list) -> list:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _idle(busy: list, within: list) -> list:
    """Idle intervals: the complement of the merged, sorted ``busy``
    inside each of the disjoint, sorted intervals ``within``."""
    out = []
    for a, b in within:
        i = max(bisect.bisect_right(busy, (a, float("inf"))) - 1, 0)
        t = a
        while i < len(busy) and busy[i][0] < b:
            s, e = busy[i]
            if s > t:
                out.append((t, s))
            t = max(t, e)
            i += 1
        if t < b:
            out.append((t, b))
    return out


# ---------------------------------------------------------------------------
# the event metadata of a raw .xplane.pb (protobuf wire format)
# ---------------------------------------------------------------------------


def _varint(b, i: int) -> tuple:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of a message; length-delimited values are
    zero-copy views, others integers or raw bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def tf_ops(path: str) -> dict:
    """{device plane: {event name: tf_op}} from the event metadata of the
    TPU planes (XSpace.planes=1; XPlane.name=2, event_metadata=4,
    stat_metadata=5; XEventMetadata.name=2, stats=5; XStat.metadata_id=1,
    str_value=5, ref_value=7; XStatMetadata.name=2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pn, pv in _fields(plane):
            if pn == 2:
                name = bytes(pv).decode()
            elif pn in (4, 5):
                value = next((v for k, v in _fields(pv) if k == 2), b"")
                if pn == 4:
                    events.append(value)
                else:
                    sid = sname = None
                    for k, v in _fields(value):
                        if k == 1:
                            sid = v
                        elif k == 2:
                            sname = bytes(v).decode()
                    stat_names[sid] = sname
        if not name.startswith(_DEVICE):
            continue
        want = {i for i, n in stat_names.items() if n == "tf_op"}
        ops = out[name] = {}
        for ev in events:
            ev_name, op = "", None
            for k, v in _fields(ev):
                if k == 2:
                    ev_name = bytes(v).decode()
                elif k == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in want:
                        op = bytes(stat[5]).decode() if 5 in stat \
                            else stat_names.get(stat.get(7))
            if op is not None:
                ops[ev_name] = op
    return out


def region_of(op_name: str) -> str:
    for part in reversed(op_name.split("/")):
        if part in REGIONS:
            return part
    return UNSCOPED


def _instruction(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def _self_times(ops: list) -> None:
    """Each op's interval less the ops nested directly in it."""
    stack: list = []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1].end <= o.start:
            stack.pop()
        while stack and o.end > stack[-1].end:     # overlaps, not nested
            stack.pop()
        o.self_ns = o.end - o.start
        if stack:
            stack[-1].self_ns -= o.end - o.start
        stack.append(o)


def load(path: str) -> SpanTrace:
    """Read one ``.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = tr.find_xplane(path)
    names = tf_ops(path)
    devices, modules, ops, spans = {}, {}, {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(_DEVICE) and \
                plane.name[len(_DEVICE):].isdigit():
            mods, raw = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += [(ev.name.split("(", 1)[0], ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                             for ev in line.events]
                elif line.name == tr._OPS_LINE:
                    raw += [(ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events]
            mods.sort(key=lambda m: m[1])
            starts = [m[1] for m in mods]
            named = names.get(plane.name, {})
            plane_ops = []
            for n, s, e in raw:
                j = bisect.bisect_right(starts, s) - 1
                module = mods[j][0] if j >= 0 and s < mods[j][2] else ""
                plane_ops.append(Op(s, e, _instruction(n), module,
                                    region_of(named.get(n, ""))))
            _self_times(plane_ops)
            devices[plane.name] = tr._merge([(s, e) for _, s, e in raw])
            modules[plane.name] = mods
            ops[plane.name] = sorted(plane_ops, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in tr.ANNOTATIONS \
                            or ev.name.startswith(PREFIXES):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    spans.sort(key=lambda sp: sp[1])
    return SpanTrace(devices=devices, spans=spans, modules=modules, ops=ops)


# ---------------------------------------------------------------------------
# readings of a traced run
# ---------------------------------------------------------------------------

#: the host's side of a decode step: sampling, the token's copy, dispatch
ROUND_TRIP = ("serve.token_to_host", "serve.sample", "serve.decode_step")


def serve_breakdown(t: SpanTrace, config: dict = None,
                    requests: list = (), device_kind: str = "") -> dict:
    """Per decode step: device time of ``jit_serve_decode``, self time in
    ``kv_cache``, and, from the first to the last step of each request, the
    wall time and the device's idle time inside the round-trip spans; per
    prefill: device time of ``jit_serve_prefill``.  With the traced
    requests' shapes (``config``, ``requests``, ``device_kind``): the MoE
    region's roofline share."""
    steps, prefills = t.count("serve.decode_step"), t.count("serve.prefill")
    out = {}
    if steps:
        out["serve.decode_step_ms"] = \
            1e3 * t.module_busy_s("jit_serve_decode") / steps
        loops = _decode_loops(t)
        between = sum(n for _, n in loops)
        if between:
            out["serve.round_trip_ms"] = 1e3 * t.idle_in(
                ROUND_TRIP, [w for w, _ in loops]) / between
            out["serve.step_wall_ms"] = \
                1e3 * sum(e - s for (s, e), _ in loops) * 1e-9 / between
        out["serve.kv_cache_ms"] = \
            1e3 * t.region_self_s("kv_cache", "jit_serve_decode") / steps
        decode = t.self_s("jit_serve_decode")
        if decode > 0:
            out["decode_scoped_share"] = 1 - t.region_self_s(
                UNSCOPED, "jit_serve_decode") / decode
    if prefills:
        out["serve.prefill_ms"] = \
            1e3 * t.module_busy_s("jit_serve_prefill") / prefills
    moe_s = t.region_self_s("moe")
    if config and config.get("num_experts") and requests and moe_s > 0:
        work = sum(routed_expert_flops(config, r["batch"], r["prompt"],
                                       r["new"]) for r in requests)
        out["serve.moe_roofline_pct"] = \
            100.0 * work / moe_s / peaks_for(device_kind).flops_bf16
    out["regions_ms"] = {r: 1e3 * t.region_self_s(r)
                         for r in REGIONS + (UNSCOPED,)}
    return out


def routed_expert_flops(c: dict, batch: int, prompt: int,
                        new_tokens: int) -> int:
    """Useful FLOPs of the MoE layers in one served request: per token the
    router and the gate, up and down matmuls of its ``num_experts_per_tok``
    experts, for the prompt and the ``new_tokens - 1`` decoded tokens."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    per_token = 2 * d * c["num_experts"] \
        + c["num_experts_per_tok"] * 3 * 2 * d * ff
    return batch * (prompt + new_tokens - 1) * c["num_hidden_layers"] \
        * per_token


def _decode_loops(t: SpanTrace) -> list:
    """Per ``serve.generate``: the interval from its first to its last
    ``serve.decode_step`` and the number of steps between them."""
    starts = [a for a, _ in t.of("serve.decode_step")]
    out = []
    for g0, g1 in t.of("serve.generate"):
        s = [a for a in starts if g0 <= a <= g1]
        if len(s) > 1:
            out.append(((s[0], s[-1]), len(s) - 1))
    return out


def union_per_plan(records: list, name: str):
    """Seconds per plan covered by the JSONL span records named ``name``
    (their union, so spans on concurrent threads count once), over the
    plans (``plan.search`` records); None where there are none."""
    plans = sum(1 for r in records if r.get("name") == "plan.search")
    union = _union([(r["t0"], r["t0"] + r["dur_s"]) for r in records
                    if r.get("name") == name])
    if not plans or not union:
        return None
    return sum(e - s for s, e in union) / plans


def _union_s(t: SpanTrace, name: str) -> float:
    return sum(e - s for s, e in _union(t.of(name))) * 1e-9


def plan_breakdown(t: SpanTrace) -> dict:
    """Per traced plan (one ``planned_call`` annotation each): the union
    of the evaluator's ``eval.prepare`` and ``eval.measure`` spans and the
    GA's generations."""
    plans = t.count("planned_call")
    if not plans:
        return {}
    return {f"plan.{k}_s": _union_s(t, n) / plans
            for k, n in (("eval_prepare", "eval.prepare"),
                         ("eval_measure", "eval.measure"),
                         ("ga_generation", "ga.generation"),
                         ("search", "plan.search"))}


def main(argv=None) -> int:
    import argparse
    import json
    import sys
    import time

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from chipbench import common, harness
    from repro.obs import trace as obs_trace

    found = {}

    class SinkProfile(common.Profile):
        def start(self) -> None:
            super().start()
            obs_trace.enable_profiler()

        def stop(self) -> None:
            if self.active:
                obs_trace.disable_profiler()
            super().stop()

        def reduce(self):
            found["spans"] = load(self.dir)
            return super().reduce()

    common.Profile = SinkProfile
    devices = harness.require_chips(1)
    harness.setup_compile_cache()
    run, out = harness.run_cell(args.workload, seed=args.seed,
                                seconds=args.seconds, trace=True,
                                devices=devices, t_start=t_start,
                                log=lambda m: print(f"[spans] {m}",
                                                    flush=True))
    t = found["spans"]
    traced = len(t.of("request"))
    got = {"correct": out["correct"], "metrics": out["metrics"],
           "device": out["device"],
           "idle_gaps": t.named_gaps(*run.trace.window),
           "device_ops": t.top_ops(10)}
    if run.requests:
        got.update(serve_breakdown(t, run.config, run.requests[:traced],
                                   run.device_kind))
        got["request_s"] = [[r["prompt"], r["t1"] - r["t0"], i < traced]
                            for i, r in enumerate(run.requests)]
    else:
        got.update(plan_breakdown(t))
        got["plan_s"] = [[p["plan_s"], i < len(t.of("planned_call"))]
                         for i, p in enumerate(run.plans)]
    print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Tiny stand-ins for the cells' configurations and traffic, and a way to
drive a cell's driver on the CPU without the harness's look for a chip."""
from __future__ import annotations

import copy
import time

from chipbench.harness import Context

DENSE = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "qk_norm": "per_head",
    "program_arch": "qwen3_0_6b", "source": "tiny", "assumed": {},
    "reference": "reference.decoder_logits",
}

MOE = dict(DENSE, num_key_value_heads=4, intermediate_size=32,
           tie_word_embeddings=False, num_experts=8, num_experts_per_tok=2,
           norm_topk_prob=True, program_arch="olmoe_1b_7b",
           assumed={"capacity_factor": 4.0})

SERVE = {"driver": "serve", "batch": 2, "prompt_lengths": [8, 16],
         "new_tokens": 4, "planner": {"population": 2, "generations": 1,
                                      "seed": 0},
         "check_requests": 2, "check_rows": 2, "trace_requests": 1}

PLAN = {"driver": "plan", "program": "attention_block", "batch": 1,
        "seq": 128, "ga": {"population": 2, "generations": 1, "seed": 0},
        "repeats": 1, "calls_per_plan": 2, "trace_plans": 1}

ATTN = dict(DENSE, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32)


def context(config: dict, traffic: dict, limits: dict, *, seed: int = 7,
            seconds: float = 0.5, **kw) -> Context:
    return Context(cell="tiny", config=copy.deepcopy(config),
                   traffic=copy.deepcopy(traffic), seed=seed,
                   seconds=seconds, trace=False,
                   t_start=time.perf_counter(), limits=limits,
                   log=lambda s: None, device_kind="TPU v5 lite", **kw)


def cell_limits(cell: str) -> dict:
    """The limits a cell's runs are held to (``limits/<cell>.json``)."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "limits", f"{cell}.json")) as f:
        return {k: float(v["limit"]) for k, v in json.load(f).items()}


def narrow(cell_config: str, **cut) -> dict:
    """A cell's configuration at its published widths, cut in depth,
    experts held and vocabulary so that the CPU can run it."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", f"{cell_config}.json")) as f:
        c = json.load(f)
    c.update(cut)
    return c


NARROW = {
    "qwen3-0.6b.decode": (
        dict(num_hidden_layers=1, vocab_size=2048),
        dict(SERVE, batch=2, prompt_lengths=[16, 32], new_tokens=8)),
    "olmoe-1b-7b.prefill": (
        dict(num_hidden_layers=1, vocab_size=2048, num_experts=16),
        dict(SERVE, batch=2, prompt_lengths=[16, 32], new_tokens=8)),
}


def narrow_cell(cell: str, **kw) -> Context:
    cut, traffic = NARROW[cell]
    return context(narrow(cell.rsplit(".", 1)[0], **cut), traffic,
                   cell_limits(cell), **kw)

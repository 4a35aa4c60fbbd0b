"""A run with the timed path broken underneath must come out not correct,
under the limits the cells' runs are held to: the harness's look for a chip
is skipped, the rest of the run is driven at widths the cells use (cut in
depth, experts and vocabulary to fit the CPU)."""
import tempfile

import numpy as np
import pytest

import tiny
from chipbench.drivers import plan, serve

SERVING = ["qwen3-0.6b.decode", "olmoe-1b-7b.prefill"]


def _serve(ctx):
    with tempfile.TemporaryDirectory() as d:
        return serve.run(ctx, d)


@pytest.mark.parametrize("cell", SERVING)
def test_sound_serving_run_is_correct(cell):
    run = _serve(tiny.narrow_cell(cell))
    assert run.correct, run.checks


@pytest.mark.parametrize("cell", SERVING)
def test_a_token_altered_where_it_is_produced_is_caught(cell, monkeypatch):
    from repro.runtime.serve import Server

    sample = Server._sample

    def altered(self, logits, key, i):
        tok = sample(self, logits, key, i)
        return (tok + 1) % logits.shape[-1] if i == 2 else tok

    monkeypatch.setattr(Server, "_sample", altered)
    run = _serve(tiny.narrow_cell(cell))
    assert not run.correct, run.checks


@pytest.mark.parametrize("cell", SERVING)
def test_half_the_batch_left_out_is_caught(cell, monkeypatch):
    from repro.runtime.serve import Server

    generate = Server.generate

    def half(self, inputs, *a, **kw):
        out = np.array(generate(self, inputs, *a, **kw))
        out[out.shape[0] // 2:] = 0
        return out

    monkeypatch.setattr(Server, "generate", half)
    run = _serve(tiny.narrow_cell(cell))
    assert not run.correct, run.checks


@pytest.mark.parametrize("cell", SERVING)
def test_a_decode_step_that_returns_its_state_unchanged_is_caught(
        cell, monkeypatch):
    from repro.models import transformer

    step = transformer.decode_step

    def stale(params, cfg, plan, token, state):
        logits, _ = step(params, cfg, plan, token, state)
        return logits, state

    monkeypatch.setattr(transformer, "decode_step", stale)
    run = _serve(tiny.narrow_cell(cell))
    assert not run.correct, run.checks


def _plan(ctx):
    with tempfile.TemporaryDirectory() as d:
        return plan.run(ctx, d)


def _plan_ctx(**traffic):
    return tiny.context(tiny.ATTN, dict(tiny.PLAN, **traffic),
                        tiny.cell_limits("olmoe-1b-7b.plan-attn"),
                        seconds=0.05)


def test_sound_planning_run_is_correct():
    assert _plan(_plan_ctx()).correct


def test_a_planned_answer_altered_is_caught(monkeypatch):
    from repro.core.offload import Offloader

    search = Offloader.search

    def altered(self, *a, **kw):
        res = search(self, *a, **kw)
        fn = res.artifact.fn
        res.artifact.fn = lambda *args: fn(*args) * 1.1
        return res

    monkeypatch.setattr(Offloader, "search", altered)
    run = _plan(_plan_ctx())
    assert not run.correct, run.checks


def test_half_the_planned_batch_left_out_is_caught(monkeypatch):
    from repro.core.offload import Offloader

    search = Offloader.search

    def half(self, *a, **kw):
        res = search(self, *a, **kw)
        fn = res.artifact.fn

        def first_half(*args):
            y = fn(*args)
            h = y.shape[0] // 2
            return y.at[h:].set(y[:h])
        res.artifact.fn = first_half
        return res

    monkeypatch.setattr(Offloader, "search", half)
    run = _plan(_plan_ctx(batch=2))
    assert not run.correct, run.checks

"""The control, the float32 reference computed in fp8 in the program's
place, fails each cell's comparison under the cell's own limit, where the
program passes it (at widths the cells use, cut to fit the CPU).  The limits
themselves were set from chip readings at the cells' own sizes (PERF.md)."""
import tempfile

import pytest

import tiny
from chipbench.drivers import plan, serve


@pytest.mark.parametrize("cell", ["qwen3-0.6b.decode", "olmoe-1b-7b.prefill"])
def test_serving_control_fails_where_the_program_passes(cell):
    ctx = tiny.narrow_cell(cell, control=True)
    with tempfile.TemporaryDirectory() as d:
        run = serve.run(ctx, d)
    (name, (value, limit)), = run.checks.items()
    assert value <= limit < run.control[name]


def test_planning_control_fails_where_the_program_passes():
    ctx = tiny.context(tiny.ATTN, tiny.PLAN,
                       tiny.cell_limits("olmoe-1b-7b.plan-attn"),
                       seconds=0.05, control=True)
    with tempfile.TemporaryDirectory() as d:
        run = plan.run(ctx, d)
    (value, limit), = run.checks.values()
    assert value <= limit < run.control["output_rel_l2"]

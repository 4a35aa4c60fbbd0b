"""Seconds per plan in which the evaluator prepared candidates (lowered
and compiled them): the union of the program's ``eval.prepare`` spans, on
any thread, over the plans, from its own span records."""
from chipbench.spans import union_per_plan


def read(run):
    return union_per_plan(run.spans, "eval.prepare")

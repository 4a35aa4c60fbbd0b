"""Seconds per plan in which the evaluator timed candidates on the chip:
the union of the program's ``eval.measure`` spans over the plans, from its
own span records."""
from chipbench.spans import union_per_plan


def read(run):
    return union_per_plan(run.spans, "eval.measure")

"""Seconds per plan in the program's ``plan.prepare`` span (the planner's
front end: graph, fitness and gene coding), from its own span records."""


def read(run):
    d = [s["dur_s"] for s in run.spans if s.get("name") == "plan.prepare"]
    return sum(d) / len(d) if d else None

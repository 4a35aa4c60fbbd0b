"""Candidates the GA measured per plan (after dedup and caching)."""


def read(run):
    if not run.plans:
        return None
    return sum(p["evaluations"] for p in run.plans) / len(run.plans)

"""Wall time of a plan (prepare and search, ending in the verified
artifact), over all plans of the window."""


def read(run):
    if not run.plans:
        return None
    return sum(p["plan_s"] for p in run.plans) / len(run.plans)

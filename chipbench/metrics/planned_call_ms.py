"""Time of one call of the program each plan chose: every plan's
back-to-back calls, ending in ``block_until_ready``, over their count."""


def read(run):
    calls = sum(p["calls"] for p in run.plans)
    if not calls:
        return None
    return 1e3 * sum(p["call_s"] * p["calls"] for p in run.plans) / calls

"""Tokens generated by every request of the window, over the window: from
the first request's call to the last one's return."""


def read(run):
    if not run.requests:
        return None
    span = run.window[1] - run.window[0]
    return sum(r["batch"] * r["new"] for r in run.requests) / span

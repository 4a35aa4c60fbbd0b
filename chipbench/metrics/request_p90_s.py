"""90th percentile of the wall latency of every request of the window, from
the call to ``Server.generate`` to its return with the tokens on the host."""
import statistics


def read(run):
    lat = [r["t1"] - r["t0"] for r in run.requests]
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=10, method="inclusive")[8]

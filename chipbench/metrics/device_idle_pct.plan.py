"""Share of the traced planning window in which no operation ran on the
device (1 - union of device-op intervals / window), in %."""


def read(run):
    if run.trace is None or not run.plans:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)

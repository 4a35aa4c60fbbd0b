"""Set-up: from process start to the start of the measured window
(imports, weights, planning, warm-up and any compilation)."""


def read(run):
    return run.setup_s

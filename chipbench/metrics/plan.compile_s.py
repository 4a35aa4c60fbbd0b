"""Backend compile seconds per plan, summed from JAX's compile events."""


def read(run):
    if not run.plans:
        return None
    return sum(p["compile_s"] for p in run.plans) / len(run.plans)

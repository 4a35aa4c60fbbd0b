"""Record the small trace that ``test_trace.py`` reads, on a TPU:

    python chipbench/tests/record_trace.py <out_dir>

Three annotated ``request`` calls of a jitted matmul chain inside one
``window`` annotation, with 50 ms of host sleep before each request, so
the trace holds known idle gaps.  Prints the planes and lines it finds."""
import os
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    assert jax.devices()[0].platform == "tpu", "record this on a TPU"
    f = jax.jit(lambda a, b: jnp.tanh(a @ b) @ b)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16) * 0.01
    f(a, b).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            time.sleep(0.05)
            with jax.profiler.TraceAnnotation("request"):
                y = a
                for _ in range(4):
                    y = f(y, b)
                y.block_until_ready()
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    from chipbench.trace import find_xplane
    path = find_xplane(out)
    print("trace", path, os.path.getsize(path))
    for p in ProfileData.from_file(path).planes:
        print("plane", p.name, [(ln.name, len(list(ln.events)))
                                for ln in p.lines])


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    main(sys.argv[1])

"""Record the small serving trace that ``test_spans.py`` reads, on a TPU:

    python chipbench/tests/record_serve_trace.py <out_dir>

Two servers of the program at the reduced size of ``ArchConfig.reduced``
(the qwen3 layout and the OLMoE layout), with the program's profiler sink
on.  Inside one ``window`` annotation, each after 20 ms of host sleep: two
``request`` calls of ``Server.generate`` to the dense server and one to the
MoE server, each a batch of 2 prompts of 16 tokens and 4 greedy tokens.
Prints the trace's path and size."""
import os
import sys
import time

import jax
import jax.numpy as jnp

BATCH, PROMPT, NEW = 2, 16, 4


def servers():
    from repro.configs import get_config
    from repro.models import REFERENCE_PLAN, build_model
    from repro.runtime.serve import ServeConfig, Server

    out = []
    for arch in ("qwen3_0_6b", "olmoe_1b_7b"):
        model = build_model(get_config(arch).reduced())
        params = jax.jit(model.init)(jax.random.key(0))
        out.append(Server(model, params, REFERENCE_PLAN,
                          ServeConfig(max_new_tokens=NEW)))
    return out


def main(out: str) -> None:
    assert jax.devices()[0].platform == "tpu", "record this on a TPU"
    from repro.obs import trace as obs_trace

    dense, moe = servers()
    tokens = {"tokens": jnp.ones((BATCH, PROMPT), jnp.int32)}
    for s in (dense, moe):
        s.generate(tokens)                    # compiles outside the trace
    jax.profiler.start_trace(out)
    obs_trace.enable_profiler()
    with jax.profiler.TraceAnnotation("window"):
        for s in (dense, dense, moe):
            time.sleep(0.02)
            with jax.profiler.TraceAnnotation("request"):
                s.generate(tokens)
    obs_trace.disable_profiler()
    jax.profiler.stop_trace()
    from chipbench.trace import find_xplane
    path = find_xplane(out)
    print("trace", path, os.path.getsize(path))


if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [root, os.path.join(root, "src")]
    main(sys.argv[1])

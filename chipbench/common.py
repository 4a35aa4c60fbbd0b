"""Pieces both drivers use: a compile counter, host annotations and the
profiler session of a traced run."""
from __future__ import annotations

import contextlib
import os
import tempfile

import jax

from chipbench import trace as tr

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def compile_clock():
    """Yields a dict whose ``seconds`` / ``count`` sum the backend compiles
    that ran inside the block, on any thread (a persistent-cache hit
    compiles nothing)."""
    acc = {"seconds": 0.0, "count": 0}

    def listen(event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            acc["seconds"] += duration
            acc["count"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield acc
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def annotate(name: str, on: bool):
    """A profiler annotation round a call into the program, in traced
    runs only."""
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


class Profile:
    """The profiler session of a traced run: ``start()`` opens it with the
    ``window`` annotation, ``stop()`` closes both; ``reduce()`` reads the
    trace back (after the measured window) and deletes it."""

    def __init__(self, tmp: str):
        self.dir = tempfile.mkdtemp(prefix="profile_", dir=tmp)
        self._window = None
        self.active = False

    def start(self) -> None:
        jax.profiler.start_trace(self.dir)
        self._window = jax.profiler.TraceAnnotation("window")
        self._window.__enter__()
        self.active = True

    def stop(self) -> None:
        if self.active:
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False

    def reduce(self) -> tr.Trace:
        out = tr.load(self.dir)
        for base, _, files in os.walk(self.dir, topdown=False):
            for f in files:
                os.remove(os.path.join(base, f))
            os.rmdir(base)
        return out

"""Host spans of the training loop, on the profiler's clock.

``LoopProfile(logdir, (first, stop))`` traces steps ``first`` to
``stop - 1`` with ``jax.profiler`` into ``logdir``.  Inside that window
each step is a ``train.step`` step span (``StepTraceAnnotation``) and
:meth:`LoopProfile.span` opens a named host span under it, so the trace
shows what the host did while the device waited: making and placing the
batch, waiting for the step, the divergence read, logging, a checkpoint
save.  Without a ``logdir``, and outside the window, every span is a
no-op.
"""
from __future__ import annotations

import contextlib

import jax

DEFAULT_STEPS = (1, 3)


def parse_steps(spec: str) -> tuple[int, int]:
    """``"A:B"`` -> ``(A, B)``: the steps A to B - 1, 0 <= A < B."""
    try:
        first, stop = (int(x) for x in spec.split(":"))
    except ValueError:
        raise ValueError(f"steps {spec!r} are not A:B") from None
    if not 0 <= first < stop:
        raise ValueError(f"steps {spec!r} need 0 <= A < B")
    return first, stop


class LoopProfile:
    """A profiler window over a training loop's steps; use as a context
    manager around the loop, so that the trace is written however the
    loop ends."""

    def __init__(self, logdir: str | None,
                 steps: tuple[int, int] = DEFAULT_STEPS):
        self.logdir = logdir
        self.first, self.stop = steps
        self.tracing = False

    def __enter__(self) -> LoopProfile:
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing = False

    @contextlib.contextmanager
    def step(self, step: int):
        """The span of one loop step; starts the trace at the window's
        first step and stops it after its last."""
        inside = self.logdir is not None and self.first <= step < self.stop
        if inside and not self.tracing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self.tracing = True
        try:
            if self.tracing:
                with jax.profiler.StepTraceAnnotation("train.step",
                                                      step_num=step):
                    yield
            else:
                yield
        finally:
            if step + 1 >= self.stop:
                self.close()

    def span(self, name: str):
        """A host span inside the window; a no-op outside it."""
        return jax.profiler.TraceAnnotation(name) if self.tracing \
            else contextlib.nullcontext()

"""Program spans on the profiler's clock.

``span(name, **args)`` marks a stage of the store's work. While a profiler
trace runs (``jax.profiler.trace`` / ``start_trace``, which is also how an
operator takes one) it is a ``jax.profiler.TraceAnnotation``: the span lands
in the trace's host plane, on the thread that ran it, on the same clock as
the device's operations, with ``args`` as the event's stats. With no trace
running it is one shared no-op, and a span costs one check.
``block_if_tracing`` waits for asynchronous device work only while a trace
runs, so a span can time it without costing the untraced path a sync.

Names read ``repro.<layer>.<stage>`` (``repro.repair.gather_wait``,
``repro.launch.device``, ``repro.serve.park``, ...). Nesting on a thread
comes from the trace itself; the args tie a span to its work across
threads: ``bytes``, ``sid``/``block``, ``window``. An arg known only inside
the span is added with ``set_metadata``.
"""
from __future__ import annotations

from jax import block_until_ready
from jax.profiler import TraceAnnotation

# Whether a profiler trace is running: what makes a span real.
tracing = TraceAnnotation.is_enabled


class _Off:
    """The span while no trace runs: does nothing, holds nothing."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **args) -> None:
        pass


OFF = _Off()


def span(name: str, **args):
    """A context manager over one stage: a ``TraceAnnotation`` named
    ``name`` with ``args`` while a trace runs, else :data:`OFF`."""
    if not tracing():
        return OFF
    return TraceAnnotation(name, **args)


def block_if_tracing(x):
    """``x``, waited for while a trace runs, so that a span around the call
    that made it times the work and not only its dispatch. With no trace
    running nothing waits, and the work overlaps whatever follows."""
    return block_until_ready(x) if tracing() else x

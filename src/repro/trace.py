"""Host spans of the serving path, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``: while a profiler trace runs it records a host event with
``args`` as its stats, on the same clock as the device's operations, so an
idle gap of the device can be put down to the host work inside it.  With
no profiler running a span costs about a microsecond, so spans are always
constructed.  Parents follow from nesting on the one host thread.

``traced(name)`` wraps a whole function in a span without arguments.
"""
from __future__ import annotations

import functools

from jax.profiler import TraceAnnotation

PREFIX = "repro."


def span(name: str, **args) -> TraceAnnotation:
    return TraceAnnotation(PREFIX + name, **args)


def traced(name: str):
    full = PREFIX + name

    def wrap(fn):
        @functools.wraps(fn)
        def call(*a, **kw):
            with TraceAnnotation(full):
                return fn(*a, **kw)
        return call
    return wrap

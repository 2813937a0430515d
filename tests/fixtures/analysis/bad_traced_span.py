"""Seeded violations for the `traced-span` rule.

``body`` is traced (passed to ``lax.scan``): a span there would run once at
trace time.  ``host_loop`` opens the same spans around a compiled call on
the host, which is where they belong, and must NOT be flagged.
"""

import jax

from repro.core import tracing


def body(carry, _):
    with tracing.span("repro.body"):  # VIOLATION
        carry = carry * 2
    with jax.profiler.TraceAnnotation("repro.inner"):  # VIOLATION
        carry = carry + 1
    with jax.named_scope("acpd.body"):  # the device-side name: fine
        carry = carry - 1
    return carry, None


def run(x):
    y, _ = jax.lax.scan(body, x, None, length=3)
    return y


def host_loop(x):
    for i in range(3):
        with tracing.span("repro.round", round=i):
            x = jax.jit(run)(x)
    return x

"""Host spans of the serving path, written into the profiler's own trace.

Each span is a ``jax.profiler.TraceAnnotation``: it lands on the same
timeline as the device's operations when a profiler session is running, and
costs about a microsecond when none is.  So the spans are always on; there
is no other recorder.  Keyword metadata (``iteration``, ``tokens``,
``kv_bucket``, ``tp``, ``nano``) becomes the event's stats in the trace.

The names say where each span sits on the packed path:

==================  =========================================================
``sched.plan``      ``GlobalBatchScheduler.plan``: admission and the plan
``sched.pack``      ``GlobalBatchScheduler.pack``: the packed layout
``engine.operands`` ``ServeEngine._launch_packed`` after the pack: numpy
                    operands, slot resets, block tables, queued CoW copies,
                    the token copy to the device
``engine.dispatch`` the jitted packed step, with the copies of the operands
                    in its argument list; metadata ``iteration`` (the
                    launch's ``stats.iterations``), ``tokens`` (launch
                    length), ``kv_bucket``, ``tp`` (devices the step is
                    split over) and ``nano`` (the launch's nano-batch
                    group sizes, ``groups``: under TP each group's
                    row-parallel reductions are collectives of their own)
``engine.retire``   ``ServeEngine._retire_oldest``: the fetch, the payload
                    decode, the commit and ``_finalize``
``engine.fetch``    ``ServeEngine._fetch``: the wait for the payload and its
                    copy to the host; metadata ``iteration`` (the launch it
                    retires)
``sched.commit``    ``GlobalBatchScheduler.commit``: the sampled tokens
                    applied
==================  =========================================================
"""
from __future__ import annotations

import functools

import jax

PLAN = "sched.plan"
PACK = "sched.pack"
COMMIT = "sched.commit"
OPERANDS = "engine.operands"
DISPATCH = "engine.dispatch"
RETIRE = "engine.retire"
FETCH = "engine.fetch"

ALL = (PLAN, PACK, COMMIT, OPERANDS, DISPATCH, RETIRE, FETCH)


def span(name: str, **meta: int | str) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` with integer or string metadata, as a context
    manager."""
    return jax.profiler.TraceAnnotation(name, **meta)


def groups(sizes) -> str:
    """Nano-batch group sizes as one metadata value, ``"128+128"`` (the
    trace's metadata encoding splits values at commas)."""
    return "+".join(str(int(s)) for s in sizes)


def spanned(name: str):
    """Decorator: every call of the function runs inside the span ``name``."""
    return functools.partial(jax.profiler.annotate_function, name=name)

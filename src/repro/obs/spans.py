"""Wall-clock spans and counters of the query path, on the profiler's clock.

The spans are ``jax.profiler.TraceAnnotation``s, so they land on the host
plane of a profiler trace beside the device's own lines and share its
clock: a gap in which the device sits idle can be read off against what
each host thread had open. They are recorded only while a trace runs
(``jax.profiler.trace(dir)``); otherwise opening one is a call that
returns at once. The counters are ``jax.monitoring.record_scalar`` calls,
which go to whatever scalar listeners are registered, and to none by
default.

Spans, outermost first (ids in brackets are the names that
``obs.trace.Tracer`` gives the same query, stage and task on the virtual
clock, so the two clocks' spans of one task share an identifier):

==========================  =============================================
``repro.query`` [query]     ``Coordinator.run_queries``, the whole call,
                            on the calling thread; ``query`` holds the
                            run's unique names, space-separated
``repro.plan`` [query]      building one plan: expansion and validation
``repro.sched.wait``        the event loop blocked on the workers
``repro.task`` [query,      one task attempt on an executor thread; a
stage, task]                scan or join task, whose operators run as a
                            device program, also carries the id
                            ``chip``: the JAX device id of the chip it
                            was placed on (``core.coordinator``)
``repro.store.get``/``put`` store reads and writes
``repro.format.decode``     §3.2 object and segment decode
``repro.format.encode``     §3.2 object and table encode
``repro.ops.stage``         ``device_ops.run``: spec, pad, ``device_put``
``repro.ops.launch``        the program's dispatch (trace and build on a
                            cache miss)
``repro.ops.wait``          the host waiting for the program's sizes
``repro.ops.fetch``         the output's copy to the host
``repro.ops.split``         the output ``Table`` and its partitions
``repro.merge``             the final stage's merge and sort/limit
==========================  =============================================

Inside each task's device program ``jax.named_scope`` names the
operators (``filter``, ``compute``, ``join``, ``aggregate``,
``partition``, ``output``), every radix sort (``radix_sort``, so an
op's name path says whose sort it is: ``join/radix_sort``) and the
aggregate's float64 segment scatters (``aggregate/segment``).

Counters:

===============================  ==========================================
``ROWS``                         once per ``device_ops.run`` call: the true
                                 rows into the task's program (probe and
                                 build sides)
``ROWS_PADDED``                  once per call: the padded rows it
                                 processed, summed over every execution of
                                 it (a join's re-run after an overflow)
``rows_padded_chip(k)``,         once per call of a task the coordinator
``.../rows_padded_chip<k>``      placed (inside ``device_ops.on_chip``):
                                 the same number under the chip ``k`` (JAX
                                 device id, the ``<k>`` of its trace plane
                                 ``/device:TPU:<k>``) that ran it; and 0
                                 for every chip the coordinator holds,
                                 once per ``Coordinator.run_queries`` call,
                                 so a chip given no task reads 0 and is not
                                 absent
``AGG_COLUMNS``                  once per call with a partial aggregate:
                                 the aggregate's float64 output columns
``AGG_SCATTERS``                 with it: the segment scatters its program
                                 emits (one per combiner: add, min, max); a
                                 task without a partial aggregate records
                                 neither
===============================  ==========================================
"""
from __future__ import annotations

import jax

QUERY = "repro.query"
PLAN = "repro.plan"
SCHED_WAIT = "repro.sched.wait"
TASK = "repro.task"
STORE_GET = "repro.store.get"
STORE_PUT = "repro.store.put"
FORMAT_DECODE = "repro.format.decode"
FORMAT_ENCODE = "repro.format.encode"
OPS_STAGE = "repro.ops.stage"
OPS_LAUNCH = "repro.ops.launch"
OPS_WAIT = "repro.ops.wait"
OPS_FETCH = "repro.ops.fetch"
OPS_SPLIT = "repro.ops.split"
MERGE = "repro.merge"

ROWS = "/repro/device_ops/rows"
ROWS_PADDED = "/repro/device_ops/rows_padded"
AGG_COLUMNS = "/repro/device_ops/agg_columns"
AGG_SCATTERS = "/repro/device_ops/agg_scatters"


def rows_padded_chip(k: int) -> str:
    """The counter of the padded rows that chip ``k`` processed."""
    return f"{ROWS_PADDED}_chip{k}"


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """The host span ``name``, with ``ids`` as its arguments; use it as a
    context manager. An id's value must hold no ``,``, ``#`` or ``=``
    (the profiler's encoding of arguments)."""
    return jax.profiler.TraceAnnotation(name, **ids)

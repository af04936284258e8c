"""Reduction of a profiler trace by the program's own spans and scopes.

The query path opens ``repro.*`` host spans (``repro/obs/spans.py``) and
names its device ops by operator scope (``join/radix_sort``, ...). This
module reads both from the ``.xplane.pb`` of a ``--trace 1`` window,
beside ``trace.reduce``, which it leaves as it is:

* **Idle causes.** Every device-idle interval on each chip, between its
  first and last op, is swept exactly (not sampled). At each instant
  every host thread votes for the innermost ``repro.*`` span it has
  open, except a thread blocked in ``repro.sched.wait``, which waits on
  the others; the cause is the name with the most votes, ties going to
  the first in sorted order (as ``trace._category_at`` breaks them), or
  ``sched`` while no ``repro.task`` is open on any thread. Each chip's
  gaps are swept against the same votes, and the causes' seconds sum to
  the chips' total idle time.
* **Host seconds.** ``repro.query`` self time (its duration less what
  ``repro.sched.wait`` covers on the same thread) and the seconds in
  ``repro.ops.stage`` and ``repro.ops.fetch``, summed over threads.
* **Device seconds per scope.** The union of the intervals of each
  chip's ops, by the operator scopes in their op_name (``scopes.py``):
  ``join``, ``join/radix_sort``, ... summed over chips. An op name that
  maps to op_names of different scopes counts under ``ambiguous``.

``RowCounter`` totals the program's counters over a window.
"""
from __future__ import annotations

import bisect
import threading
from collections import defaultdict

from jax.profiler import ProfileData

from chipbench import scopes
from chipbench.trace import (DEVICE_PREFIX, HOST_PLANE, MODULES_LINE,
                             OPS_LINE, union)

PREFIX = "repro."
QUERY, WAIT, TASK = "repro.query", "repro.sched.wait", "repro.task"
TRANSFER = ("repro.ops.stage", "repro.ops.fetch")
SCHED = "sched"                 # idle while no task is open anywhere
OPERATORS = ("filter", "compute", "join", "aggregate", "partition",
             "output")
SORT = "radix_sort"
AMBIGUOUS = "ambiguous"
PROGRAM = "jit__program"
COUNTER_PREFIX = "/repro/"
ROWS = "/repro/device_ops/rows"
ROWS_PADDED = "/repro/device_ops/rows_padded"


class RowCounter:
    """Totals of every ``jax.monitoring`` scalar the program records under
    ``/repro/`` (``rows`` and ``rows_padded`` once per operator task, on
    the executor threads, and whatever counters it adds) while
    registered, each keyed by the last component of its name."""

    def __init__(self):
        self.lock = threading.Lock()
        self.totals: dict[str, float] = {}

    def _on_scalar(self, event: str, value, **kw) -> None:
        if event.startswith(COUNTER_PREFIX):
            key = event.rsplit("/", 1)[-1]
            with self.lock:
                self.totals[key] = self.totals.get(key, 0) + value

    def __enter__(self):
        import jax
        jax.monitoring.register_scalar_listener(self._on_scalar)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_scalar_listener(self._on_scalar)


def scope_of(op_name: str) -> str:
    """``jit(_program)/join/radix_sort/while/body/gather`` -> ``join/
    radix_sort``: the first operator scope in the path, with
    ``/radix_sort`` when the op lies in a sort; ``""`` outside them."""
    parts = op_name.split("/")
    op = next((p for p in parts if p in OPERATORS), "")
    if SORT in parts:
        return f"{op}/{SORT}" if op else SORT
    return op


def innermost(spans) -> list[tuple[float, float, str]]:
    """The (start, end, name) pieces of one thread's timeline, each
    named by the innermost of its nested spans open there."""
    out = []
    stack: list[tuple[float, str]] = []
    t = None
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if t < end:
                out.append((t, end, top))
                t = end
        if stack and t < s:
            out.append((t, s, stack[-1][1]))
        stack.append((e, name))
        t = s
    while stack:
        end, top = stack.pop()
        if t < end:
            out.append((t, end, top))
            t = end
    return out


def _causes(threads: dict) -> list[tuple[float, str]]:
    """(time, cause from then on) at every change of the threads' votes
    or of the number of open tasks, in time order."""
    marks = []
    for spans in threads.values():
        for s, e, name in innermost(spans):
            if name != WAIT:
                marks += [(s, 1, name), (e, -1, name)]
        for s, e, name in spans:
            if name == TASK:
                marks += [(s, 1, None), (e, -1, None)]
    marks.sort(key=lambda m: m[0])
    votes: dict[str, int] = defaultdict(int)
    tasks = 0
    out = []
    i = 0
    while i < len(marks):
        t = marks[i][0]
        while i < len(marks) and marks[i][0] == t:
            _, d, name = marks[i]
            if name is None:
                tasks += d
            else:
                votes[name] += d
            i += 1
        open_votes = {n: v for n, v in votes.items() if v > 0}
        if tasks <= 0 or not open_votes:
            cause = SCHED
        else:
            cause = max(sorted(open_votes), key=open_votes.get)
        out.append((t, cause))
    return out


def idle_causes(gaps, threads: dict) -> dict[str, float]:
    """Seconds of the (start, end) ns ``gaps`` by cause, swept exactly:
    each gap is cut at every change of cause inside it."""
    changes = _causes(threads)
    times = [t for t, _ in changes]
    out: dict[str, float] = defaultdict(float)
    for s, e in gaps:
        k = bisect.bisect_right(times, s) - 1     # the change in force at s
        t = s
        while t < e:
            cause = changes[k][1] if k >= 0 else SCHED
            stop = min(times[k + 1], e) if k + 1 < len(times) else e
            out[cause] += (stop - t) * 1e-9
            t = stop
            k += 1
    return dict(out)


def _self_s(threads: dict) -> float:
    """``repro.query`` seconds less those ``repro.sched.wait`` covers on
    the query's own thread."""
    total = 0
    for spans in threads.values():
        waits = union((s, e) for s, e, n in spans if n == WAIT)
        for s, e, n in spans:
            if n != QUERY:
                continue
            covered = sum(min(e, we) - max(s, ws) for ws, we in waits
                          if ws < e and we > s)
            total += (e - s) - covered
    return total * 1e-9


def reduce(path: str) -> dict:
    """``reduce_bytes`` of the ``.xplane.pb`` at ``path``."""
    with open(path, "rb") as f:
        return reduce_bytes(f.read())


def _device_ops(plane) -> tuple[list, int]:
    """(name, start, end) ns of each op of a device plane, and the device
    ns of its ``jit__program`` runs."""
    ops, program_ns = [], 0
    for line in plane.lines:
        if line.name == OPS_LINE:
            ops = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events]
        elif line.name == MODULES_LINE:
            program_ns += sum(ev.duration_ns for ev in line.events
                              if ev.name.startswith(PROGRAM + "("))
    return ops, program_ns


def _by_scope(ops, names: dict) -> dict[str, list]:
    """One chip's op intervals by the scope of their op_names."""
    out: dict[str, list] = defaultdict(list)
    for name, s, e in ops:
        found = {scope_of(o) for o in names.get(name, ())}
        scope = found.pop() if len(found) == 1 else \
            (AMBIGUOUS if found else "")
        if scope:
            out[scope].append((s, e))
    return out


def _union_s(intervals) -> float:
    return sum(e - s for s, e in union(intervals)) * 1e-9


def reduce_bytes(raw: bytes) -> dict:
    """Idle seconds by cause, host seconds and device seconds by scope of
    a serialized ``XSpace``, the device's summed over every chip with
    ops. Returns ``idle_s``, ``idle_causes`` ({cause: s}), ``queries``
    (``repro.query`` spans), ``sched_self_s``, ``op_transfer_s``,
    ``scope_device_s`` ({scope: s}), ``sort_device_s`` (union over every
    sort scope), ``scoped_device_s`` (union over every scope),
    ``program_s`` (``jit__program`` device seconds) and ``ambiguous``
    (op names with more than one op_name)."""
    pd = ProfileData.from_serialized_xspace(raw)
    chips = []
    program_ns = 0
    threads: dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, ns = _device_ops(plane)
            program_ns += ns
            if ops:
                chips.append((plane.name, ops))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                          ev.name) for ev in line.events
                         if ev.name.startswith(PREFIX)]
                if spans:
                    threads[f"{line.name}/{len(threads)}"] = spans
    if not chips:
        raise ValueError("no device operations in the trace")
    names_of = scopes.op_names(raw, DEVICE_PREFIX)
    gaps = []
    scope_s: dict[str, float] = defaultdict(float)
    sort_s = scoped_s = 0.0
    ambiguous: set[str] = set()
    for plane_name, ops in chips:
        busy = union((s, e) for _, s, e in ops)
        gaps += [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
        names = names_of.get(plane_name, {})
        by_scope = _by_scope(ops, names)
        for k, v in sorted(by_scope.items()):
            scope_s[k] += _union_s(v)
        sort_s += _union_s(iv for k, v in by_scope.items()
                           if k.split("/")[-1] == SORT for iv in v)
        scoped_s += _union_s(iv for v in by_scope.values() for iv in v)
        ambiguous.update(scopes.ambiguous(names))
    return {
        "idle_s": sum(e - s for s, e in gaps) * 1e-9,
        "idle_causes": idle_causes(gaps, threads),
        "queries": sum(n == QUERY for spans in threads.values()
                       for _, _, n in spans),
        "sched_self_s": _self_s(threads),
        "op_transfer_s": sum(e - s for spans in threads.values()
                             for s, e, n in spans if n in TRANSFER) * 1e-9,
        "scope_device_s": dict(sorted(scope_s.items())),
        "sort_device_s": sort_s,
        "scoped_device_s": scoped_s,
        "program_s": program_ns * 1e-9,
        "ambiguous": sorted(ambiguous),
    }

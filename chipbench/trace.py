"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics and the breakdown read.

``jax.profiler.ProfileData`` reads the file. Device planes are those
named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event
per operation run and the ``XLA Modules`` line one per program run
(``jit__program(...)``, ``jit__head(...)``). The host plane
``/host:CPU`` holds the benchmark's own annotations
(``chipbench.<layer>.<function>``, see ``probes.py``) on the lines of the
threads that opened them. Times are in the trace's own nanoseconds,
which host and device planes share.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from jax.profiler import ProfileData

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "chipbench."
# what the host was doing, by the annotation open on the most threads
CATEGORY = {
    "chipbench.format.decode_object": "decode",
    "chipbench.format.deserialize_segment": "decode",
    "chipbench.format.deserialize_table": "decode",
    "chipbench.format.partitions_to_object": "encode",
    "chipbench.format.serialize_table": "encode",
    "chipbench.ops.run": "device_ops.run host side",
}
IDLE_NO_ANNOTATION = "coordinator"


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


_KIND = re.compile(r" ([a-z][a-z\-]*)\(")
_SHAPE = re.compile(r"\b([a-z]+\d+)\[(\d+)\]")


def short_op(hlo: str) -> str:
    """``%fusion.496 = u32[1048576]{...} fusion(...), ...`` ->
    ``%fusion.496 fusion u32[1048576]``: the op, its kind and its
    largest one-dimensional array."""
    head, _, rest = hlo.partition(" = ")
    kind = _KIND.search(rest)
    shapes = [(int(n), f"{t}[{n}]") for t, n in _SHAPE.findall(rest)]
    parts = [head] + ([kind.group(1)] if kind else []) + \
        ([max(shapes)[1]] if shapes else [])
    return " ".join(parts)


def _program_name(event_name: str) -> str:
    """``jit__program(123)`` -> ``jit__program``."""
    return event_name.split("(", 1)[0]


def _category_at(t: float, annotations: dict) -> str:
    """The category open on the most host threads at time ``t``."""
    votes: dict[str, int] = defaultdict(int)
    for spans in annotations.values():
        for s, e, name in spans:
            if s <= t < e:
                votes[CATEGORY.get(name, name)] += 1
    if not votes:
        return IDLE_NO_ANNOTATION
    return max(sorted(votes), key=votes.get)


def reduce(path: str, top: int = 10) -> dict:
    """``reduce_profile`` of the ``.xplane.pb`` at ``path``."""
    return reduce_profile(ProfileData.from_file(path), path, top)


def reduce_profile(pd, label: str = "trace", top: int = 10) -> dict:
    """Busy union, idle gaps and device time per program and op.

    Returns a dict with ``chips`` (device planes found), ``busy_by_chip_s``
    (the union of each plane's op intervals, in plane order), ``busy_s``
    (their mean), ``span_s`` (first op start
    to last op end), ``program_s`` (device seconds per program name),
    ``device_ops`` (the ``top`` ops by device seconds, as [short name,
    s]; a loop's time includes that of the ops in its body) and
    ``idle_gaps`` (the ``top`` longest gaps between ops on the first
    chip, as [what the host was doing, s]).
    """
    busy_by_chip = []
    program_s: dict[str, float] = defaultdict(float)
    op_s: dict[str, float] = defaultdict(float)
    first_busy = None
    annotations: dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            intervals = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        intervals.append((ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
                        op_s[ev.name] += ev.duration_ns * 1e-9
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        program_s[_program_name(ev.name)] += \
                            ev.duration_ns * 1e-9
            merged = union(intervals)
            busy_by_chip.append(merged)
            if first_busy is None:
                first_busy = merged
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in line.events
                         if ev.name.startswith(ANNOTATION_PREFIX)]
                if spans:
                    annotations[f"{line.name}/{len(annotations)}"] = spans
    if not busy_by_chip or not any(busy_by_chip):
        raise ValueError(f"{label}: no device operations in the trace")
    busy = [sum(e - s for s, e in m) * 1e-9 for m in busy_by_chip]
    gaps = [(b[0] - a[1], a[1], b[0])
            for a, b in zip(first_busy, first_busy[1:])]
    gaps.sort(reverse=True)
    idle = [[_category_at((s + e) / 2, annotations), g * 1e-9]
            for g, s, e in gaps[:top]]
    ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    return {
        "chips": len(busy_by_chip),
        "busy_by_chip_s": busy,
        "busy_s": sum(busy) / len(busy),
        "span_s": (first_busy[-1][1] - first_busy[0][0]) * 1e-9,
        "program_s": dict(program_s),
        "device_ops": [[short_op(n), s] for n, s in ops],
        "idle_gaps": idle,
    }

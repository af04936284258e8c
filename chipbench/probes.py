"""Host timers and profiler annotations around the program's layers.

The worker imports the §3.2 format functions by name and calls the
operators through the ``device_ops`` module, so the wrappers go where
``core/worker.py`` looks them up. Each call is timed on the host clock
(summed over the executor threads) and opens a
``jax.profiler.TraceAnnotation`` named ``chipbench.<layer>.<function>``,
which the trace reduction uses to name what the host was doing while the
device sat idle. ``device_ops.run`` also counts the bytes its operands
and output take at their true row counts, for the operators' roofline.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import defaultdict

import jax

from chipbench.stats import table_nbytes

FORMAT_FUNCS = ("decode_object", "deserialize_segment", "deserialize_table",
                "partitions_to_object", "serialize_table")
PREFIX = "chipbench."


def _out_nbytes(out) -> int:
    if isinstance(out, list):
        return sum(table_nbytes(t) for t in out)
    return table_nbytes(out)


class Probes:
    """Install with ``install()``; read ``host_s`` (seconds per function
    name), ``calls`` and ``op_bytes``; ``uninstall()`` restores the
    program's own functions."""

    def __init__(self):
        self.lock = threading.Lock()
        self.host_s: dict[str, float] = defaultdict(float)
        self.calls: dict[str, int] = defaultdict(int)
        self.op_bytes = 0
        self._saved: list[tuple[object, str, object]] = []

    def reset(self) -> None:
        with self.lock:
            self.host_s.clear()
            self.calls.clear()
            self.op_bytes = 0

    def _add(self, name: str, dt: float, nbytes: int = 0) -> None:
        with self.lock:
            self.host_s[name] += dt
            self.calls[name] += 1
            self.op_bytes += nbytes

    def _timed(self, fn, layer: str, name: str, count_bytes: bool):
        label = f"{PREFIX}{layer}.{name}"

        @functools.wraps(fn)
        def wrapper(*args, **kw):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                out = fn(*args, **kw)
            dt = time.perf_counter() - t0
            nbytes = 0
            if count_bytes:
                t, _ops, builds = args[:3]
                nbytes = table_nbytes(t) + _out_nbytes(out) + sum(
                    table_nbytes(b) for b in builds.values())
            self._add(name, dt, nbytes)
            return out
        return wrapper

    def _patch(self, module, name: str, layer: str, count_bytes=False):
        fn = getattr(module, name)
        self._saved.append((module, name, fn))
        setattr(module, name, self._timed(fn, layer, name, count_bytes))

    def install(self) -> "Probes":
        from repro.core import worker
        from repro.relational import device_ops
        for name in FORMAT_FUNCS:
            self._patch(worker, name, "format")
        self._patch(device_ops, "run", "ops", count_bytes=True)
        return self

    def uninstall(self) -> None:
        while self._saved:
            module, name, fn = self._saved.pop()
            setattr(module, name, fn)

"""Chip smoke run: the query engine and the model serving path, once, on a TPU.

    python chip_smoke.py

1. Requires a TPU: when JAX's first device is not a TPU it exits non-zero
   and prints no result (there is no CPU fallback).
2. Turns on the persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``
   if set, else ``<checkout>/.jax_cache``).
3. Query phase: a TPC-H SF 1 engine behind ``Session`` with 64 MiB base
   splits; the six planned queries (q1 q3 q5 q6 q12 q14) are submitted
   twice. Every result must match ``engine.oracle`` (the numpy reference)
   at rtol 1e-9 / atol 1e-6, every task's operator program must have run
   on the TPU, and the second pass must compile nothing.
4. Model phase: ``launch.serve.generate`` on the full smollm-135m config
   (random weights from a seed), batch 4, a 128-token prompt and 16 new
   tokens. Logits must be finite, and the decode path's replay of the
   prompt must reproduce the prefill logits at the last prompt position
   within a bf16 tolerance.
5. The last line of standard output is one JSON object:
   ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Any failure raises, and the script exits non-zero. The phases are plain
functions, which the CPU tests call at a tiny size.
"""
from __future__ import annotations

import json
import pathlib
import sys
import threading
import time
from collections import Counter

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.core.engine import oracle  # noqa: E402
from repro.core.session import Session  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import generate  # noqa: E402
from repro.relational import device_ops  # noqa: E402
from repro.relational.table import DictColumn  # noqa: E402

QUERIES = ("q1", "q3", "q5", "q6", "q12", "q14")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
BF16_TOL = 5e-2          # replay vs prefill logits, relative to max |logit|


def _canon(t) -> dict:
    """Columns as float64, rows sorted by all columns (order-insensitive)."""
    cols = {n: np.asarray(c.codes if isinstance(c, DictColumn) else c,
                          np.float64)
            for n, c in sorted(t.cols.items())}
    if not cols:
        return cols
    order = np.lexsort(tuple(cols.values()))
    return {n: v[order] for n, v in cols.items()}


def _assert_matches(got, want, name: str) -> None:
    g, w = _canon(got), _canon(want)
    if sorted(g) != sorted(w):
        raise AssertionError(f"{name}: columns {sorted(g)} != {sorted(w)}")
    for n in w:
        np.testing.assert_allclose(g[n], w[n], rtol=1e-9, atol=1e-6,
                                   err_msg=f"{name}:{n}")


class _Counters:
    """Operator tasks per device platform and backend compiles, read from
    jax.monitoring events (listeners run on the executor threads)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.platforms: Counter = Counter()
        self.compiles = 0

    def on_event(self, event: str, **kw) -> None:
        if event == device_ops.TASK_EVENT:
            with self.lock:
                self.platforms[kw["platform"]] += 1

    def on_duration(self, event: str, duration_secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            with self.lock:
                self.compiles += 1

    def __enter__(self):
        jax.monitoring.register_event_listener(self.on_event)
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self.on_event)
        jax.monitoring.unregister_event_duration_listener(self.on_duration)


def query_phase(sf: float = 1.0, target_bytes: int = 64 << 20,
                platform: str = "tpu") -> dict:
    """Submit the six queries twice through ``Session``; check each result
    against the oracle, the device every task ran on, and that the second
    pass compiled nothing. Returns the per-pass numbers."""
    print(f"query phase: TPC-H SF {sf} (the paper ran SF 1000; cut for "
          f"the run's time limit), base splits of {target_bytes / 2**20:g} "
          f"MiB (the paper's objects are a few hundred MB), seed 0",
          flush=True)
    t0 = time.perf_counter()
    sess = Session(sf=sf, target_bytes=target_bytes, seed=0,
                   compute_scale=0)
    print(f"  set-up (generate + load): host wall "
          f"{time.perf_counter() - t0:.3f} s; lineitem "
          f"{len(sess.tables['lineitem'])} rows in "
          f"{len(sess.coord.base_splits['lineitem'])} splits", flush=True)
    expected = {}
    stats = {}
    with _Counters() as ctr:
        for pass_no in (1, 2):
            for q in QUERIES:
                c0 = ctr.compiles
                t1 = time.perf_counter()
                res = sess.submit(q)
                wall = time.perf_counter() - t1
                compiles = ctr.compiles - c0
                if q not in expected:
                    expected[q] = oracle(q, sess.tables)
                _assert_matches(res.result, expected[q], q)
                stats[(pass_no, q)] = {"rows": len(res.result),
                                       "host_wall_s": wall,
                                       "compiles": compiles,
                                       "tasks": res.task_count}
                print(f"  pass {pass_no} {q}: {len(res.result)} rows, "
                      f"{res.task_count} tasks, host wall {wall:.3f} s, "
                      f"compiles {compiles}, matches oracle", flush=True)
        platforms = dict(ctr.platforms)
    print(f"  operator tasks by device platform: {platforms}", flush=True)
    if set(platforms) != {platform}:
        raise AssertionError(f"operator tasks ran on {platforms}, "
                             f"expected only {platform!r}")
    second = sum(stats[(2, q)]["compiles"] for q in QUERIES)
    if second:
        raise AssertionError(f"second pass compiled {second} programs")
    return {"stats": stats, "platforms": platforms}


def model_phase(cfg=None, batch: int = 4, prompt_len: int = 128,
                new_tokens: int = 16) -> dict:
    """Serve one batch on ``cfg`` (default: full smollm-135m) and check the
    logits. Returns the replay error and the generated tokens."""
    cfg = cfg or get_config("smollm-135m")
    print(f"model phase: {cfg.name} ({cfg.num_layers} layers, d_model "
          f"{cfg.d_model}), random weights, batch {batch}, prompt "
          f"{prompt_len}, {new_tokens} new tokens", flush=True)
    t0 = time.perf_counter()
    out = generate(cfg, batch=batch, prompt_len=prompt_len,
                   new_tokens=new_tokens)
    wall = time.perf_counter() - t0
    pre = np.asarray(out["prefill_logits"], np.float32)
    rep = np.asarray(out["replay_logits"], np.float32)
    if not (np.isfinite(pre).all() and np.isfinite(rep).all()):
        raise AssertionError("non-finite logits")
    if out["tokens"].shape != (batch, new_tokens):
        raise AssertionError(f"generated {out['tokens'].shape}")
    err = float(np.max(np.abs(rep - pre)))
    scale = float(np.max(np.abs(pre)))
    print(f"  host wall {wall:.3f} s (compiles included); replay vs "
          f"prefill max |diff| {err:.6g}, max |logit| {scale:.6g}, "
          f"tolerance {BF16_TOL} x max |logit|", flush=True)
    if err > BF16_TOL * max(scale, 1.0):
        raise AssertionError(f"decode replay differs from prefill by {err}")
    return {"max_abs_diff": err, "max_abs_logit": scale,
            "tokens": out["tokens"]}


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    query_phase()
    model_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

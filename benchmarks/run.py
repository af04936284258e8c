"""Run every benchmark (one module per paper table/figure) and print the
``name,us_per_call,derived`` CSV. ``--quick`` shrinks sizes for CI;
``--only`` takes a comma-separated module list; ``--json PATH`` also
writes the emitted rows as machine-readable JSON (name -> value ->
derived) so the perf trajectory can be tracked across commits;
``--trace PATH`` traces every coordinator any selected benchmark builds
(``repro.obs.trace.install_global_tracer``) and dumps ONE Chrome
trace_event file viewable at chrome://tracing or ui.perfetto.dev —
tracing is read-only, so the emitted numbers are unchanged (the CI suite
gates run with it on to prove exactly that)."""
from __future__ import annotations

import argparse
import importlib
import json
import time

from benchmarks.common import BENCH_MODULES
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (e.g. "
                         "BENCH_workload.json)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="dump a Chrome trace of every coordinator the "
                         "selected benchmarks build (obs layer)")
    args = ap.parse_args()
    enable_compile_cache()

    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(BENCH_MODULES)
        if unknown:
            raise SystemExit(f"unknown benchmark(s): {sorted(unknown)}")
    trace_handle = None
    if args.trace:
        from repro.obs.trace import install_global_tracer
        trace_handle = install_global_tracer()
    print("name,us_per_call,derived")
    try:
        for name in BENCH_MODULES:
            if only and name not in only:
                continue
            mod = importlib.import_module(f"benchmarks.{name}")
            t0 = time.time()
            try:
                mod.main(quick=args.quick)
                print(f"bench_{name}_wall_s,{time.time()-t0:.2f},ok",
                      flush=True)
            except Exception as e:  # noqa: BLE001 — a failure is a result
                print(f"bench_{name}_wall_s,{time.time()-t0:.2f},"
                      f"FAILED {e!r}", flush=True)
                raise
    finally:
        if trace_handle is not None:
            n = trace_handle.export(args.trace)
            trace_handle.uninstall()
            print(f"# wrote {n} trace events to {args.trace} "
                  "(chrome://tracing / ui.perfetto.dev)", flush=True)
        if args.json:
            from benchmarks.common import RECORDS
            with open(args.json, "w") as f:
                json.dump({name: {"value": value, "derived": derived}
                           for name, value, derived in RECORDS},
                          f, indent=1, sort_keys=True)
            print(f"# wrote {len(RECORDS)} rows to {args.json}", flush=True)


if __name__ == "__main__":
    main()

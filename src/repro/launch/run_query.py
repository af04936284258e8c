"""Query driver: run TPC-H queries end-to-end through the Starling engine.

  PYTHONPATH=src python -m repro.launch.run_query --query q12 --sf 0.01 \\
      [--shuffle multi] [--join-tasks 16] [--no-mitigations]
"""
from __future__ import annotations

import argparse

from repro.core.engine import make_engine, run_query
from repro.core.stragglers import StragglerConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.relational.table import DictColumn
from repro.relational.tpch import QUERIES


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--query", default="q12", choices=sorted(QUERIES))
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--join-tasks", type=int, default=8)
    ap.add_argument("--shuffle", default="single",
                    choices=["single", "multi"])
    ap.add_argument("--no-mitigations", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    policy = StragglerConfig.all_off() if args.no_mitigations else None
    coord, tables = make_engine(sf=args.sf, policy=policy)
    kw = {}
    if args.query == "q12" and args.shuffle == "multi":
        kw["shuffle"] = {"strategy": "multi", "p": 1 / 4, "f": 1 / 4}
    res = run_query(coord, args.query, {"join": args.join_tasks}, **kw)

    print(f"{args.query} @ sf={args.sf}: latency {res.latency_s:.2f}s "
          f"(virtual), cost ${res.cost.total:.5f} "
          f"({res.cost.gets} GETs, {res.cost.puts} PUTs, "
          f"{res.task_count} tasks, {res.backup_count} backups)")
    print("stage windows:", res.stage_times)
    t = res.result
    print("result:")
    names = t.column_names()
    print("  " + " | ".join(names))
    for i in range(min(len(t), 10)):
        row = []
        for n in names:
            c = t[n]
            row.append(c.values[c.codes[i]].decode() if isinstance(
                c, DictColumn) else f"{c[i]:.4g}")
        print("  " + " | ".join(row))


if __name__ == "__main__":
    main()

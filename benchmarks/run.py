"""Benchmark driver: one module per paper table/figure (+ beyond-paper).

Usage:
  PYTHONPATH=src python -m benchmarks.run [--scale tiny|small|paper]
      [--only X] [--warm] [--json-dir DIR]

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.py).

With ``--json-dir`` every module additionally writes a machine-readable
``BENCH_<name>.json`` perf-trajectory record: cold (and, with ``--warm``,
second-run) wall time, backend-compile counts, and the module's policy-grid
size — so PRs can compare benchmark numbers across revisions (the CI
bench-smoke job uploads these as artifacts).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

MODULES = [
    ("power_table", "benchmarks.bench_power_table"),       # Tables 5/6
    ("inactivity", "benchmarks.bench_inactivity"),         # Fig 1
    ("traffic", "benchmarks.bench_traffic_profiles"),      # Figs 6/9/12/15
    ("fixed_pdt", "benchmarks.bench_fixed_pdt"),           # Figs 7/10/13/16
    ("perfbound", "benchmarks.bench_perfbound"),           # Figs 8/11/14/17
    ("decoupled", "benchmarks.bench_decoupled"),           # DESIGN.md §3
    ("kernels", "benchmarks.bench_kernels"),               # kernel parity
    ("topology", "benchmarks.bench_topology"),             # beyond paper
    ("scenario_suite", "benchmarks.bench_scenario_suite"),  # beyond paper
    ("tuner", "benchmarks.bench_tuner"),                   # beyond paper
    ("sharded_sweep", "benchmarks.bench_sharded_sweep"),   # beyond paper
    ("wavefront", "benchmarks.bench_wavefront"),           # DESIGN.md §10
    ("stream", "benchmarks.bench_stream"),                 # DESIGN.md §11
]


def _timed_run(mod, scale):
    from repro.core.instrument import count_compiles
    with count_compiles() as cc:
        t0 = time.time()
        rows = list(mod.run(scale))
        wall = time.time() - t0
    return rows, wall, cc.count


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["tiny", "small", "paper"],
                    default="small")
    ap.add_argument("--only", default=None,
                    help="comma-separated module keys to run")
    ap.add_argument("--warm", action="store_true",
                    help="run each module twice; report the warm pass too "
                         "(plan + compile caches populated)")
    ap.add_argument("--json-dir", default=None, metavar="DIR",
                    help="write BENCH_<name>.json perf records to DIR")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    if args.json_dir:
        os.makedirs(args.json_dir, exist_ok=True)

    print("name,us_per_call,derived")
    failures = []
    for key, modname in MODULES:
        if only and key not in only:
            continue
        t0 = time.time()
        record = {"bench": key, "scale": args.scale, "status": "ok",
                  "cold_wall_s": None, "warm_wall_s": None,
                  "compiles_cold": None, "compiles_warm": None,
                  "policy_count": None, "rows": []}
        try:
            mod = importlib.import_module(modname)
            n_pol = getattr(mod, "n_policies", None)
            if n_pol is not None:
                record["policy_count"] = n_pol(args.scale)
            rows, cold_s, cold_c = _timed_run(mod, args.scale)
            record.update(cold_wall_s=round(cold_s, 3), compiles_cold=cold_c)
            for row in rows:
                print(row.csv(), flush=True)
            record["rows"] = [{"name": r.name, "us_per_call": r.us_per_call,
                              "derived": r.derived} for r in rows]
            if args.warm:
                _, warm_s, warm_c = _timed_run(mod, args.scale)
                record.update(warm_wall_s=round(warm_s, 3),
                              compiles_warm=warm_c)
                print(f"# {key} warm: {warm_s:.1f}s "
                      f"({warm_c} compiles; cold {cold_s:.1f}s, "
                      f"{cold_c} compiles)", flush=True)
        except Exception as e:  # keep the suite going; report at the end
            failures.append((key, repr(e)))
            record.update(status="error", error=repr(e))
            print(f"{key}/ERROR,0.0,{e!r}", flush=True)
        print(f"# {key} done in {time.time()-t0:.1f}s", flush=True)
        if args.json_dir:
            path = os.path.join(args.json_dir, f"BENCH_{key}.json")
            with open(path, "w") as f:
                json.dump(record, f, indent=1, sort_keys=True)
    if failures:
        print(f"# {len(failures)} module(s) failed: {failures}")
        sys.exit(1)
    print("# all benchmark modules passed")


if __name__ == "__main__":
    main()

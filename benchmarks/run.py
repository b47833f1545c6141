"""Benchmark harness: one module per paper table/figure (DESIGN.md §6).
Prints ``name,us_per_call,derived`` CSV rows; ``--json`` additionally
appends each key's rows to ``BENCH_<key>.json`` (a history list, one entry
per run) so the perf trajectory is tracked in-repo.

    PYTHONPATH=src python -m benchmarks.run [--only fig3,fig6,...] [--json]
"""
from __future__ import annotations

import argparse
import datetime
import importlib
import json
import os
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    ("table1", "benchmarks.bench_table1_rtc"),
    ("fig3", "benchmarks.bench_fig3_async_sched"),
    ("fig4", "benchmarks.bench_fig4_pd_online"),
    ("fig6", "benchmarks.bench_fig6_heatmap"),
    ("fig7", "benchmarks.bench_fig7_dist_sched"),
    ("predictor", "benchmarks.bench_predictor"),
    ("fig9", "benchmarks.bench_fig9_scaling"),
    ("fig10", "benchmarks.bench_fig10_teload"),
    ("fig11", "benchmarks.bench_fig11_npufork"),
    ("roofline", "benchmarks.bench_roofline"),
    ("tp_engine", "benchmarks.bench_tp_engine"),
    ("pd_migration", "benchmarks.bench_pd_migration"),
    ("decode_hotloop", "benchmarks.bench_decode_hotloop"),
    ("prefill_batching", "benchmarks.bench_prefill_batching"),
    ("serving_plane", "benchmarks.bench_serving_plane"),
    ("scale_out", "benchmarks.bench_scale_out"),
    ("fault_recovery", "benchmarks.bench_fault_recovery"),
]


def _persist_json(key: str, rows: list, wall_s: float, out_dir: str) -> None:
    path = os.path.join(out_dir, f"BENCH_{key}.json")
    history = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f).get("history", [])
        except (json.JSONDecodeError, OSError):
            history = []
    history.append({
        "ts": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "wall_s": round(wall_s, 3),
        "rows": [{"name": n, "value": v, "derived": d} for n, v, d in rows],
    })
    with open(path, "w") as f:
        json.dump({"key": key, "history": history}, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench keys (e.g. fig3,fig6)")
    ap.add_argument("--json", action="store_true",
                    help="append results to BENCH_<key>.json per bench key")
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_<key>.json (default: cwd)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for key, modname in MODULES:
        if only and key not in only:
            continue
        t0 = time.monotonic()
        try:
            mod = importlib.import_module(modname)
            rows = mod.run()
        except Exception as e:  # noqa: BLE001
            print(f"{key}_ERROR,0,{e!r}")
            failures += 1
            continue
        wall = time.monotonic() - t0
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        print(f"{key}_wall_s,{wall * 1e6:.0f},")
        sys.stdout.flush()
        if args.json:
            _persist_json(key, rows, wall, args.json_dir)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Readings that set a cell's correctness limit and its offered rate, in
one process on the chip (set-up is paid once):

    python bench/calibrate.py --workload qwen3_8b_l4.chat --seconds 20 \\
        --seeds 1,2,3 --control-seeds 1,2,3
    python bench/calibrate.py --workload qwen3_8b_l4.chat --seconds 20 \\
        --seeds 1,2,3 --rates 0.6,0.8,1.0,1.2

For each seed the weights are made anew from it and handed to the running
plane in place of the last seed's, the cell's traffic runs as in
``run.py``, and the served greedy tokens are compared with the reference:
the widest gap of a served token (the program's reading) and, for a
control seed, the widest gap of the token that the control (the reference
in the configuration's ``control`` precision) puts first at the same
positions (the control's reading). With ``--rates`` every seed runs the
open loop once per rate (the knee sweep, over several orders of the same
work). Between runs the plane finishes what the last one left. One
JSON line per run goes to standard output. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run
from spec import Spec

import check
import stats


def backlog(drv, c0, c1) -> dict:
    """TTFT of the window's first and second half: a second half far above
    the first means the queue grew all through the window."""
    mid = (c0.t + c1.t) / 2
    win = [r for r in drv.recs.values() if r["phase"] == "window"]
    halves = [[r["ttft"] for r in win if r["tokens"] is not None
               and (r["due"] < mid) == first] for first in (True, False)]
    return {"ttft_p50_first_half_ms": 1e3 * (stats.percentile(halves[0], 50)
                                             or 0.0),
            "ttft_p50_second_half_ms": 1e3 * (stats.percentile(halves[1], 50)
                                              or 0.0),
            "unfinished": sum(r["tokens"] is None for r in win)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    spec = Spec()
    try:
        run.require_devices(spec.workload(args.workload)["chips"])
    except run.NoDevice as e:
        run.log(f"FAILED: {e}")
        return 2
    run.configure_jax(spec.root)
    cell = run.Cell(spec, args.workload)
    base_rate = cell.cell.get("rate_per_s")
    drive = run.drive_open if cell.mix["loop"] == "open" else \
        run.drive_closed
    je = w = None
    reference = check.Reference(cell.ref, cell.config)
    for seed in seeds:
        # the last seed's weights go before the next are made
        if je is not None:
            je.params = None
            for te in je.engines:
                te.runner.params = None
        w = None
        gc.collect()
        w = cell.weights(seed)
        if je is None:
            je = cell.plane(w, seed)
            run.log(f"warm-up: {cell.warm(je)}")
        else:
            je.params = w
            for te in je.engines:
                te.runner.params = w
        for rate in rates:
            if base_rate is not None:
                cell.cell["rate_per_s"] = rate or base_rate
            while je.has_work():
                je.step()
            drv = run.Driver(je, cell.mix)
            c0, c1, _ = drive(cell, drv, seed, args.seconds, False)
            e2e = run.end_to_end(cell, drv, c0, c1, 0.0)
            finished = [r for r in drv.recs.values()
                        if r["tokens"] is not None]
            ck = cell.cell["check"]
            sample = check.sample(finished, seed, ck["sample_min_tokens"],
                                  ck["sample_max_requests"])
            t = time.monotonic()
            gaps = reference.gaps(
                w, sample, check.padded_len(cell.max_total),
                control=seed in controls)
            out = {"seed": seed, "rate": cell.cell.get("rate_per_s"),
                   "served_gap": gaps["served"],
                   "control_gap": gaps["control"] if seed in controls
                   else None, "positions": gaps["positions"],
                   "sample": len(sample), "reference_s":
                   time.monotonic() - t,
                   "exact": check.exact_failures(finished, cell.vocab,
                                                 len(je.unit_failures)),
                   **{k: v for k, v in e2e.items() if k != "setup_s"}}
            if cell.mix["loop"] == "open":
                out.update(backlog(drv, c0, c1))
            print(json.dumps(out), flush=True)
    je.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

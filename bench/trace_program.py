#!/usr/bin/env python3
"""One traced run of a cell, read with the program's own spans, counters
and request timelines beside the harness's figures:

    python bench/trace_program.py --workload qwen3_8b_l4.chat --seed 7 --seconds 51

It drives the cell exactly as ``run.py --trace 1`` does (build, warm-up,
loop, the traced stretch in the middle of the window) but skips the
reference check, and prints one JSON object as the last line of standard
output:

- ``metrics``: every per-layer metric the cell reports, and the readers of
  the program's own inputs (``PROGRAM_METRICS``), each None where its
  reader finds nothing to read;
- ``idle_gaps``: the longest device idle gaps of the stretch named by the
  harness's and the program's host spans together (innermost span wins),
  beside ``idle_gaps_harness``, named by the harness's alone;
- ``scopes``: device seconds per program and named scope over the
  stretch, and ``op_args``, what the trace gives of one device op;
- ``counters``: the stretch's counter deltas, the harness's outside
  figures (``decode_rows``, ``prefill_tokens``) beside the program's
  (``program_decode_rows``, ``program_prefill_tokens``);
- ``je_step``: JE steps in the stretch, their mean length, and program
  spans per JE step; ``span_cost``: one span's enter and exit with the
  profiler off and on, in microseconds.

Like ``run.py`` it runs only on a TPU (exit code 2 elsewhere).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import program_view  # noqa: E402
import reduce_trace as trace_red  # noqa: E402
import run  # noqa: E402
from spec import Spec  # noqa: E402

PROGRAM_METRICS = ("te_queue_wait_ms", "ttft_prefill_ms",
                   "decode_horizon_mean", "decode_kv_pad_share",
                   "prefill_kv_pad_share", "te_host_ms")
COST_DIR = ".bench_trace_cost"


class Counters(run.Counters):
    """The harness's counters and the program's own work counters."""

    def __init__(self, drv):
        super().__init__(drv)
        self.program = program_view.counters(drv.je.engines)

    def delta(self, later):
        d = super().delta(later)
        d.update({k: v - self.program[k] for k, v in later.program.items()
                  if k in self.program})
        return d


class Driver(run.Driver):
    """The harness's driver, also keeping each completion's timeline."""

    def step(self):
        comps = super().step()
        for c in comps:
            rec = self.recs.get(c.req_id)
            if rec is not None:
                rec["timeline"] = program_view.timeline(c)
        return comps


def span_cost(root: Path, n: int = 20000) -> dict:
    """Seconds per enter and exit of one program span (the best of three
    loops of ``n``), with no profiler running and under one, in us."""
    import jax
    run.import_program()
    from repro.engine.trace import span

    def loop():
        t = time.perf_counter()
        for _ in range(n):
            with span("te.plan"):
                pass
        return (time.perf_counter() - t) / n * 1e6

    off = min(loop() for _ in range(3))
    d = root / COST_DIR
    jax.profiler.start_trace(str(d))
    try:
        on = min(loop() for _ in range(3))
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    return {"off_us": off, "on_us": on}


def main(argv=None, root=None) -> int:
    args = run.parse(argv)
    spec = Spec(root or run.ROOT)
    wl = spec.workload(args.workload)
    try:
        device = run.require_devices(wl["chips"])
    except run.NoDevice as e:
        run.log(f"FAILED: {e}")
        return 2
    run.configure_jax(spec.root)
    cell = run.Cell(spec, args.workload)
    w = cell.weights(args.seed)
    je = cell.plane(w, args.seed)
    cell.warm(je)
    drv = Driver(je, cell.mix)
    drive = run.drive_open if cell.mix["loop"] == "open" else run.drive_closed
    harness_counters = run.Counters
    run.Counters = Counters         # the loop and the tracer snapshot these
    try:
        c0, c1, tracer = drive(cell, drv, args.seed, args.seconds, True)
    finally:
        run.Counters = harness_counters
    e2e = run.end_to_end(cell, drv, c0, c1, c0.t - T_START)

    prog = program_view.load(str(tracer.dir))   # before per_layer drops it
    scoped = program_view.load_scoped(str(tracer.dir))
    ctx, breakdown = run.per_layer(cell, tracer, device)
    ws, we = prog["window"]
    ctx["spans"] = trace_red.clip(prog["spans"], ws, we)
    ctx["timelines"] = [r["timeline"] for r in drv.recs.values()
                        if r["phase"] == "window" and r.get("timeline")]
    names = [m["name"] for m in spec.metrics(args.workload, True)]
    metrics = {n: spec.reader(n).read(ctx)
               for n in names + list(PROGRAM_METRICS)}
    ops = trace_red.clip(prog["ops"], ws, we)
    je_steps = [e - s for n, s, e in ctx["spans"] if n == "je.step"]
    out = {
        "device": dict(device, **ctx["trace"]),
        "end_to_end": e2e,
        "metrics": metrics,
        "idle_gaps": trace_red.idle_gaps(ops, prog["host"], ws, we),
        "idle_gaps_harness": breakdown["idle_gaps"],
        "scopes": program_view.scope_seconds(
            trace_red.clip(scoped["ops"], *scoped["window"]),
            scoped["modules"]),
        "op_args": scoped["op_args"],
        "counters": ctx["counters"],
        "je_step": {"n": len(je_steps),
                    "mean_ms": (sum(je_steps) / len(je_steps) * 1e3
                                if je_steps else None),
                    "spans_per_step": (len(ctx["spans"]) / len(je_steps)
                                       if je_steps else None)},
        "timelines_ms": [[(fd - a) * 1e3, (ft - fd) * 1e3]
                         for a, fd, ft in ctx["timelines"]],
        "span_cost": span_cost(spec.root),
    }
    je.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

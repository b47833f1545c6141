"""What the serving program records about its own work, for the benchmark
to read beside what the harness sees from outside:

- its host spans (``je.*``, ``te.*``, ``distflow.*``; the list is
  ``repro.engine.trace.SPANS``) on the profiler trace's host plane, on the
  clock of the device ops;
- the ``jax.named_scope`` path of each device op (``kv_gather``,
  ``kv_scatter``, ``attention``, ``mlp``, ``lm_head``, ``sample`` in the
  prefill and decode programs): the op's ``tf_op``. A program loaded
  from the persistent compilation cache carries the metadata of the code
  that compiled it first (the cache key leaves metadata out), so the
  scopes show only where this code compiled the program;
- the work counters of each TE (``FlowServe.decode_dispatches`` and the
  rest), summed over the plane's TEs;
- each request's timeline: arrival, first prefill dispatch
  (``Completion.first_dispatch``) and first token.

A program that records none of these (an older one) gives empty lists and
dicts here, never an error, so the per-layer readers of these inputs
return None for it. Intervals are ``(name, start_s, end_s)``, as in
``reduce_trace``.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import reduce_trace as trace_red

SPAN_PREFIXES = ("je.", "te.", "distflow.")
SCOPES = ("kv_gather", "kv_scatter", "attention", "mlp", "lm_head",
          "sample")
COUNTERS = ("decode_dispatches", "decode_kv_live", "decode_kv_slots",
            "prefill_kv_live", "prefill_kv_slots")
# the program's own figures of what the harness counts from outside
# (``Driver._live``), under names of their own beside the harness's
CROSS_CHECKED = {"decode_rows": "program_decode_rows",
                 "prefill_tokens": "program_prefill_tokens"}

Interval = Tuple[str, float, float]


def _newest(profile_dir: str, pattern: str) -> str:
    files = sorted(glob.glob(os.path.join(profile_dir, "**", pattern),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no {pattern} under {profile_dir}")
    return files[-1]


def load(profile_dir: str) -> dict:
    """From the newest ``.xplane.pb`` under ``profile_dir``: ``spans``, the
    program's host spans; ``host``, those and the harness's ``bench.*``
    spans together; ``window``, the ``bench.window`` span's bounds (None
    without one); ``ops`` and ``modules``, the device ops and programs."""
    from jax.profiler import ProfileData
    out = {"spans": [], "host": [], "window": None, "ops": [],
           "modules": []}
    for plane in ProfileData.from_file(_newest(profile_dir,
                                               "*.xplane.pb")).planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            for key, line in (("ops", "XLA Ops"), ("modules", "XLA Modules")):
                for e in (lines[line].events if line in lines else ()):
                    out[key].append((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    iv = (e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    if e.name.startswith(SPAN_PREFIXES):
                        out["spans"].append(iv)
                    if e.name.startswith(SPAN_PREFIXES + ("bench.",)):
                        out["host"].append(iv)
                    if e.name == "bench.window":
                        out["window"] = iv[1:]
    return out


def load_scoped(profile_dir: str) -> dict:
    """The device ops with their scope path, which ``ProfileData`` does not
    give: from the newest ``*.trace.json.gz`` under ``profile_dir`` (the
    profiler writes it beside the ``.xplane.pb``, with each op's metadata
    among its ``args``; it may leave out events the ``.xplane.pb`` holds),
    ``ops``, named by their ``tf_op`` argument (the jit and named-scope
    path) where it holds a scope, else by their own name; ``modules``;
    ``window``, the ``bench.window`` bounds; and ``op_args``, the
    arguments of the first device op, to show what an op carries. Seconds
    on that file's clock."""
    with gzip.open(_newest(profile_dir, "*.trace.json.gz"), "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    out = {"ops": [], "modules": [], "window": None, "op_args": None}
    for e in events:
        if e.get("ph") != "X":
            continue
        iv = (e["name"], e["ts"] * 1e-6, (e["ts"] + e.get("dur", 0)) * 1e-6)
        if e["name"] == "bench.window":
            out["window"] = iv[1:]
        if not procs.get(e["pid"], "").startswith("/device:"):
            continue
        line = threads.get((e["pid"], e.get("tid")))
        if line == "XLA Modules":
            out["modules"].append(iv)
        elif line == "XLA Ops":
            args = e.get("args", {})
            if out["op_args"] is None:
                out["op_args"] = {k: str(v)[:160] for k, v in args.items()}
            path = args.get("tf_op", "")
            out["ops"].append((path if scope_of(path) else e["name"],
                               *iv[1:]))
    return out


def scope_of(path: str) -> Optional[str]:
    """The innermost of ``SCOPES`` among the components of a scope path
    (``jit(run)/jit(main)/attention/kv_gather/gather`` → ``kv_gather``)."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return None


def scope_seconds(ops: List[Interval], modules: List[Interval]
                  ) -> Dict[str, Dict[str, float]]:
    """Device seconds per program (jit name without the hash) and scope;
    ops under no scope count as ``other``."""
    out: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for name, s, e in trace_red.label_ops(ops, modules):
        prog, _, path = name.partition("/")
        out[prog][scope_of(path) or "other"] += e - s
    return {p: dict(v) for p, v in out.items()}


def counters(engines) -> Dict[str, int]:
    """The TEs' work counters, summed; a counter the program lacks is left
    out."""
    out = {}
    for attr, key in [(c, c) for c in COUNTERS] + list(
            CROSS_CHECKED.items()):
        vals = [getattr(te, attr, None) for te in engines]
        if vals and all(v is not None for v in vals):
            out[key] = sum(vals)
    return out


def timeline(completion) -> Optional[Tuple[float, float, float]]:
    """(arrival, first prefill dispatch, first token) of a completion, on
    the monotonic clock; None where the program does not record the
    dispatch."""
    fd = getattr(completion, "first_dispatch", None)
    if fd is None:
        return None
    return (completion.arrival, fd, completion.arrival + completion.ttft)


def te_host_seconds(spans: List[Interval]) -> List[float]:
    """Per ``te.step``: its duration less that of the ``te.*.fetch`` spans
    inside it (the host work the device waits behind, without the waits
    for the device itself)."""
    steps = sorted((s for s in spans if s[0] == "te.step"),
                   key=lambda s: s[1])
    fetches = [s for s in spans
               if s[0].startswith("te.") and s[0].endswith(".fetch")]
    out = []
    for _, s0, e0 in steps:
        inner = sum(e - s for _, s, e in fetches if s >= s0 and e <= e0)
        out.append(e0 - s0 - inner)
    return out

"""Reduction of a profiler trace to device busy time, time per program,
and the longest device idle gaps named by what the host was doing.

An interval is ``(name, start_s, end_s)``. ``load`` reads the newest
``.xplane.pb`` under a profile directory into three lists: device
operations (the "XLA Ops" lines of the device planes), device programs
(the "XLA Modules" lines) and the harness's own host spans (names starting
with ``bench.``). The rest works on such lists, so the tests can hand-build
them.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

Interval = Tuple[str, float, float]

LABEL_LOOKBACK_S = 10.0   # the longest program run an op is looked up in


def load(profile_dir: str) -> Dict[str, List[Interval]]:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    out = {"ops": [], "modules": [], "host": [], "devices": 0}
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            out["devices"] += 1
            for key, line in (("ops", "XLA Ops"), ("modules", "XLA Modules")):
                for e in (lines[line].events if line in lines else ()):
                    out[key].append((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append((e.name, e.start_ns * 1e-9,
                                            (e.start_ns + e.duration_ns)
                                            * 1e-9))
    return out


def union(intervals: List[Interval]) -> List[Tuple[float, float]]:
    """Merged (start, end) spans covered by any interval."""
    spans = []
    for _, s, e in sorted(intervals, key=lambda iv: iv[1]):
        if spans and s <= spans[-1][1]:
            spans[-1][1] = max(spans[-1][1], e)
        else:
            spans.append([s, e])
    return [(s, e) for s, e in spans]


def clip(intervals: List[Interval], start: float, end: float
         ) -> List[Interval]:
    """The parts of the intervals that lie inside [start, end]."""
    return [(n, max(s, start), min(e, end)) for n, s, e in intervals
            if e > start and s < end]


def busy_seconds(ops: List[Interval], n_devices: int = 1) -> float:
    """Seconds in which an operation ran, averaged over the devices (ops of
    all devices in one list count once per device)."""
    return sum(e - s for s, e in union(ops)) / max(1, n_devices)


def program_seconds(modules: List[Interval],
                    patterns: Dict[str, str]) -> Dict[str, float]:
    """Summed device time of the programs whose name matches each group's
    pattern (from the start of the name)."""
    out = {}
    for group, pat in patterns.items():
        rx = re.compile(pat)
        out[group] = sum(e - s for n, s, e in modules if rx.match(n))
    return out


def label_ops(ops: List[Interval], modules: List[Interval]
              ) -> List[Interval]:
    """Ops renamed ``<program>/<instruction>``: the program (its jit name
    without the hash) whose run encloses the op, and the HLO instruction's
    name without its text (``%fusion.12 = f32[...] ...`` → ``%fusion.12``).
    """
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for n, s, e in ops:
        prog = "?"
        # the latest-starting program run that still encloses the op
        for i in range(bisect.bisect_right(starts, s) - 1, -1, -1):
            if mods[i][2] >= e:
                prog = mods[i][0].split("(")[0]
                break
            if s - mods[i][1] > LABEL_LOOKBACK_S:
                break
        out.append((f"{prog}/{n.split(' = ')[0]}", s, e))
    return out


def top_ops(ops: List[Interval], k: int = 10) -> List[list]:
    """The ``k`` operation names with the most device time."""
    tot = defaultdict(float)
    for n, s, e in ops:
        tot[n] += e - s
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(ops: List[Interval], host: List[Interval], window_start: float,
              window_end: float, k: int = 10) -> List[list]:
    """The ``k`` longest spans of the window with no device operation, each
    named by the host span it fell in ("untraced" where none)."""
    gaps, t = [], window_start
    for s, e in union(ops):
        if s > t:
            gaps.append((t, min(s, window_end)))
        t = max(t, e)
    if t < window_end:
        gaps.append((t, window_end))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:k]
    out = []
    for gs, ge in gaps:
        cover = [(min(ge, e) - max(gs, s), e - s, n) for n, s, e in host]
        cover = [x for x in cover if x[0] > 0]
        # the shortest span over half the gap (the innermost of nested
        # spans), else the one that covers most of it
        half = [x for x in cover if x[0] >= (ge - gs) / 2]
        if half:
            name = min(half, key=lambda x: x[1])[2]
        else:
            name = max(cover)[2] if cover else "untraced"
        out.append([name, ge - gs])
    return out

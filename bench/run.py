#!/usr/bin/env python3
"""On-chip benchmark of the serving path: one cell (configuration x
traffic mix) of ``BENCHMARK.json`` per run.

    python bench/run.py --workload qwen3_8b_l4.chat --seed 7 --seconds 10 --trace 0

The run builds the cell's model from its configuration file with weights
random from ``--seed``, brings up the serving plane (the JE,
``ServingJobEngine``, over the cell's topology of FLOWSERVE TEs), warms up
the program buckets the traffic can reach, fills the loop, and then
measures for ``--seconds``: requests enter through ``ServingJobEngine.submit``
with the client's due time as their arrival, and the harness steps the JE.
Afterwards it checks what was served against the plain reference
(``check.py``) and prints, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics read from
a profiler trace of a few seconds inside the window), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``, each number compared
beside its limit. It runs only on a TPU: elsewhere it exits with code 2 and
prints no result.

JAX's compilation cache is ``<checkout>/.jax_cache``; profiles go to
``<checkout>/.bench_trace`` and are deleted once read.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import costs  # noqa: E402
import stats  # noqa: E402
import reduce_trace as trace_red  # noqa: E402
import traffic as gen  # noqa: E402
from spec import Spec  # noqa: E402

CACHE_DIR = ".jax_cache"       # under the checkout (the benchmark root)
TRACE_DIR = ".bench_trace"
TAIL_SECONDS = 90.0     # longest wait after the window for its requests
TRACE_SECONDS = 4.0     # traced stretch inside the window (--trace 1)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class NoDevice(RuntimeError):
    pass


def require_devices(chips: int) -> dict:
    """The accelerator as JAX reports it; raises NoDevice off a TPU or with
    fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise NoDevice(f"no TPU: JAX reports {dev['platform']}; this "
                       f"benchmark never falls back")
    if dev["count"] < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees "
                       f"{dev['count']}")
    return dev


def configure_jax(root: Path) -> None:
    """Every program into the checkout's cache, none evicted, so a second
    run of a cell compiles nothing."""
    import jax
    (root / CACHE_DIR).mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def import_program():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


# ------------------------------------------------------------------ build

class Cell:
    """Everything one workload's files say, plus the program's model."""

    def __init__(self, spec: Spec, name: str):
        import jax
        import jax.numpy as jnp
        import_program()
        from repro.configs.base import get_config
        from repro.models import get_model
        self.spec = spec
        self.wl = spec.workload(name)
        self.name = name
        self.config = spec.config(self.wl["config"])
        self.mix = spec.traffic(self.wl["traffic"])
        self.cell = spec.cell(name)
        self.ref = spec.reference(self.config)
        prog = self.config["program"]
        pcfg = dataclasses.replace(get_config(prog["arch"]),
                                   **prog.get("replace", {}))
        bad = self.ref.program_mismatches(self.config, pcfg)
        if bad:
            raise ValueError(f"program config departs from "
                             f"{self.config['name']}: {bad}")
        self.bundle = get_model(pcfg)
        self.dtype = getattr(jnp, self.config["dtype"])
        # the precision the configuration states, for every program the
        # process traces (the program's matmuls take JAX's default)
        jax.config.update("jax_default_matmul_precision",
                          self.config["matmul_precision"])
        self.vocab = self.config["vocab_size"]
        ps = self.cell["engine"]["page_size"]
        self.max_prompt = self.mix["prompt_tokens"]["max"]
        self.max_total = self.max_prompt + self.mix["output_tokens"]["max"]
        self.prefill_pages = -(-self.max_prompt // ps)
        self.decode_pages = -(-self.max_total // ps)

    def weights(self, seed: int):
        import jax
        shapes = jax.eval_shape(
            lambda k: self.bundle.init_params(k, self.dtype),
            jax.random.PRNGKey(0))
        w = self.ref.init_weights(self.config, shapes, seed, self.dtype)
        return jax.block_until_ready(w)

    def plane(self, w, seed: int):
        import_program()
        from repro.core.heatmap import HeatmapStudy
        from repro.core.serving_plane import ServingJobEngine, TopologySpec
        from repro.engine import EngineConfig
        ecfg = EngineConfig(**self.cell["engine"], dtype=self.dtype,
                            seed=seed % (1 << 31))
        hs = HeatmapStudy(self.bundle.cfg)
        return ServingJobEngine(
            self.bundle, w, TopologySpec.parse(self.cell["topology"]),
            heatmap=hs.combined(), prefill_lens=hs.prefill_lens,
            decode_ratios=hs.decode_ratios, policy=self.cell["policy"],
            ecfg=ecfg)

    def warm(self, je) -> dict:
        """Compile the decode and prefill buckets the traffic can reach,
        and the decode batch's bookkeeping updates."""
        n = {"decode": 0, "prefill": 0, "batch_updates": 0}
        for te in je.engines:
            n["decode"] += te.warmup_decode(max_pages=self.decode_pages)
            n["prefill"] += te.warmup_prefill(max_pages=self.prefill_pages)
            n["batch_updates"] += warm_batch_updates(
                te, te.ecfg.max_decode_batch, self.decode_pages)
        return n


def warm_batch_updates(te, max_batch: int, max_pages: int) -> int:
    """Run the fused decode batch's row updates (rows leaving, joining,
    growing a page, evicted) once for every count of rows that one update
    can change, at every batch and page bucket up to the cell's bounds.
    They are eager device updates whose programs are keyed by that count,
    so without this they compile inside the window the first time a count
    is met. Works on a throwaway copy of the batch state over the TE's
    pool; no page is written. Returns the number of updates run."""
    import_program()
    from repro.engine.hotloop import DecodeHotState, pow2s
    n_runs = 0
    for bb in pow2s(max_batch):
        for pb in pow2s(max_pages):
            hot = DecodeHotState(te.pool)
            grow = 1 if pb > 1 else 0
            rows = [(f"w{i}", list(range(pb if i == 0 else pb - grow)),
                     2, 0, 0.0, 1.0) for i in range(bb)]
            hot.sync(rows)
            for n in range(1, bb + 1):
                # n rows leave and n join in their slots, then grow a page
                fresh = [(f"w{bb}.{n}.{i}", list(range(pb - grow)), 2, 0,
                          0.0, 1.0) for i in range(n)]
                rows = fresh + rows[n:]
                n_runs += hot.sync(rows)
                if grow:
                    rows = [(r[0], list(range(len(r[1]) + 1)), *r[2:])
                            if i < n and len(r[1]) < pb else r
                            for i, r in enumerate(rows)]
                    n_runs += hot.sync(rows)
            hot.evict(rows[0][0])
            n_runs += 1
    return n_runs


# ------------------------------------------------------------------ drive

class Counters:
    """Counters of the plane at one moment, from public engine state."""

    def __init__(self, drv: "Driver"):
        self.t = time.monotonic()
        tes = drv.je.engines
        loads = [te.load_metrics() for te in tes]
        self.je_steps, self.je_wall = drv.je_steps, drv.je_wall
        self.te_wall = sum(te.step_wall for te in tes)
        self.decode_steps = sum(te.decode_steps for te in tes)
        self.compiles = len(drv.built)
        # the budget signal: tokens owed to resident requests
        self.produced = drv.owed_new - sum(x["inflight_decode_tokens"]
                                           for x in loads)
        self.prefilled = drv.owed_prompt - sum(x["queued_prefill_tokens"]
                                               for x in loads)
        self.decode_rows, self.decode_ctx = drv.decode_rows, drv.decode_ctx
        self.prefill_tokens = drv.prefill_tokens
        self.prefill_pos = drv.prefill_pos

    def delta(self, later: "Counters") -> dict:
        return {k: getattr(later, k) - getattr(self, k)
                for k in ("t", "je_steps", "je_wall", "te_wall",
                          "decode_steps", "compiles", "produced",
                          "prefilled", "decode_rows", "decode_ctx",
                          "prefill_tokens", "prefill_pos")}


class Driver:
    """Submits requests through the JE and steps it, keeping a record of
    each request."""

    def __init__(self, je, mix: dict):
        import jax
        from jax import monitoring
        self.je, self.mix = je, mix
        self.recs = {}
        self.je_steps, self.je_wall = 0, 0.0
        self.owed_new = self.owed_prompt = 0
        self.late = []
        self.built = []     # programs compiled or loaded, by name
        # live work of the decode batch and the prefill queue, summed over
        # steps while ``sample_live`` is on (the traced stretch)
        self.sample_live = False
        self.decode_rows = self.decode_ctx = 0
        self.prefill_tokens = self.prefill_pos = 0
        self._ann = jax.profiler.TraceAnnotation

        def on_event(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.built.append(kw.get("fun_name", "?"))
        monitoring.register_event_duration_secs_listener(on_event)

    def submit(self, r: gen.Req, due: float) -> str:
        import_program()
        from repro.core.abstractions import RequestType, UserRequest
        from repro.engine import SamplingParams
        sp = SamplingParams(
            temperature=0.0 if r.greedy else self.mix["temperature"],
            top_p=1.0 if r.greedy else self.mix["top_p"],
            max_new_tokens=r.max_new, stop_on_eos=False)
        with self._ann("bench.submit"):
            now = time.monotonic()
            rid = self.je.submit(
                r.prompt, sampling=sp,
                request=UserRequest(rtype=RequestType.CHAT,
                                    payload={"tokens": r.prompt,
                                             "max_new_tokens": r.max_new},
                                    arrival=due))
        self.late.append(now - due)
        self.owed_new += r.max_new
        self.owed_prompt += len(r.prompt) - 1
        self.recs[rid] = {"prompt": r.prompt, "max_new": r.max_new,
                          "greedy": r.greedy, "due": due, "phase": r.phase,
                          "tokens": None}
        return rid

    def step(self) -> list:
        live = self._live() if self.sample_live else None
        t0 = time.monotonic()
        with self._ann("bench.je_step"):
            comps = self.je.step()
        self.je_wall += time.monotonic() - t0
        self.je_steps += 1
        if live is not None:
            self._count_live(live)
        for c in comps:
            rec = self.recs.get(c.req_id)
            if rec is None:        # left by an earlier driver of the plane
                continue
            rec.update(tokens=list(c.tokens), ttft=c.ttft, finish=c.finish,
                       tpot=c.tpot)
        return comps

    def _live(self) -> list:
        """Per TE before a step: its decode steps so far, the decode batch
        (the scheduler's running set, as its plan slices it) with each
        member's context, and each queued prompt's cached length."""
        out = []
        for te in self.je.engines:
            sch = te.scheduler
            batch = sch.running[:sch.cfg.max_decode_batch]
            out.append((te, te.decode_steps, len(batch),
                        sum(len(s.tokens) for s in batch),
                        [(s, s.n_cached) for s in sch.queued_seqs()]))
        return out

    def _count_live(self, live: list) -> None:
        for te, steps, rows, ctx, queued in live:
            d = te.decode_steps - steps
            self.decode_rows += d * rows
            self.decode_ctx += d * ctx
            for s, before in queued:
                n = max(0, s.n_cached - before)
                self.prefill_tokens += n
                # positions before .. before + n - 1 each attend to as many
                self.prefill_pos += n * (2 * before + n - 1) // 2

    def wait_until(self, t: float) -> None:
        with self._ann("bench.wait"):
            time.sleep(max(0.0, t - time.monotonic()))


class Tracer:
    """Profiles ``TRACE_SECONDS`` from ``start`` on, when asked to."""

    def __init__(self, on: bool, start: float, out_dir: Path = None):
        self.start, self.dir = start, out_dir
        self.state = "idle" if on else "off"
        self.c0 = self.c1 = None
        self._span = None

    def poll(self, drv: Driver) -> None:
        import jax
        now = time.monotonic()
        if self.state == "idle" and now >= self.start:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir))
            self._span = drv._ann("bench.window")
            self._span.__enter__()
            drv.sample_live = True
            self.c0, self.state = Counters(drv), "on"
        elif self.state == "on" and now >= self.c0.t + TRACE_SECONDS:
            self.c1 = Counters(drv)
            drv.sample_live = False
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"


def drive_open(cell: Cell, drv: Driver, seed: int, seconds: float,
               tracer_on: bool):
    """The open loop: lead-in, window, then the tail until every request
    due in the window has finished. Returns (counters at window start,
    counters at window end, tracer)."""
    rate = cell.cell["rate_per_s"]
    reqs = gen.open_loop(cell.mix, rate, seed, seconds, TAIL_SECONDS,
                         cell.vocab)
    t0 = time.monotonic()
    w0 = t0 + cell.mix["lead_seconds"]
    w1 = w0 + seconds
    tracer = Tracer(tracer_on, w0 + max(0.0, (seconds - TRACE_SECONDS) / 2),
                    cell.spec.root / TRACE_DIR)
    c0 = c1 = None
    i = 0
    window_ids = []
    while True:
        now = time.monotonic()
        if c0 is None and now >= w0:
            c0 = Counters(drv)
        if c1 is None and now >= w1:
            c1 = Counters(drv)
        if c0 is not None:
            tracer.poll(drv)
        if c1 is not None and tracer.state in ("off", "done") and all(
                drv.recs[r]["tokens"] is not None for r in window_ids):
            break
        if now >= w1 + TAIL_SECONDS:
            break
        while i < len(reqs) and t0 + reqs[i].due <= now:
            rid = drv.submit(reqs[i], t0 + reqs[i].due)
            if reqs[i].phase == "window":
                window_ids.append(rid)
            i += 1
        if drv.je.has_work():
            drv.step()
        elif i < len(reqs):
            nxt = t0 + reqs[i].due
            if c0 is None:
                nxt = min(nxt, w0)
            elif c1 is None:
                nxt = min(nxt, w1)
            drv.wait_until(nxt)
    return c0, c1, tracer


def drive_closed(cell: Cell, drv: Driver, seed: int, seconds: float,
                 tracer_on: bool):
    """The closed loop: the staggered first requests, each completion
    replaced at once; the window opens after ``fill_completions``."""
    loop = gen.ClosedLoop(cell.mix, seed, cell.vocab)
    now = time.monotonic()
    for r in loop.first:
        drv.submit(r, now)
    done = 0
    c0 = c1 = None
    tracer = Tracer(False, 0.0)
    while c1 is None or tracer.state == "on":
        comps = drv.step()
        now = time.monotonic()
        for _ in comps:
            drv.submit(loop.next(), now)
        done += len(comps)
        if c0 is None and done >= cell.mix["fill_completions"]:
            c0 = Counters(drv)
            tracer = Tracer(tracer_on, c0.t + max(
                0.0, (seconds - TRACE_SECONDS) / 2), cell.spec.root / TRACE_DIR)
        if c0 is not None:
            if c1 is None and now >= c0.t + seconds:
                c1 = Counters(drv)
            tracer.poll(drv)
    # a closed loop's window holds the requests that finished in it
    for r in drv.recs.values():
        r["phase"] = "window" if r["tokens"] is not None and \
            c0.t <= r["finish"] <= c1.t else "other"
    return c0, c1, tracer


# ------------------------------------------------------------------ metrics

def end_to_end(cell: Cell, drv: Driver, c0: Counters, c1: Counters,
               setup_s: float) -> dict:
    recs = list(drv.recs.values())
    limit = cell.cell.get("ttft_limit_s", float("inf"))
    win = [r for r in recs if r["phase"] == "window"]
    ttfts = [r["ttft"] for r in win
             if r["tokens"] is not None and r["ttft"] <= limit]
    tpots = [r["tpot"] for r in recs if r["tokens"] is not None
             and len(r["tokens"]) > 1 and c0.t <= r["finish"] <= c1.t]
    d = c0.delta(c1)
    p90_ttft = stats.percentile(ttfts, 90, len(win) - len(ttfts))
    p90_tpot = stats.percentile(tpots, 90)
    out = {
        "ttft_p90_ms": None if p90_ttft is None else p90_ttft * 1e3,
        "tpot_p90_ms": None if p90_tpot is None else p90_tpot * 1e3,
        "output_tokens_per_s": d["produced"] / d["t"],
        "setup_s": setup_s,
    }
    log(f"window: {d['t']:.3f} s, {len(win)} requests due, {len(ttfts)} "
        f"with a first token within {limit} s, {len(tpots)} finished, "
        f"{d['produced']} tokens, {d['je_steps']} JE steps, "
        f"{d['decode_steps']} decode steps, {d['compiles']} compiles")
    return out


def per_layer(cell: Cell, tracer: Tracer, device: dict) -> tuple:
    """The per-layer metrics' inputs from the traced stretch, and the
    breakdown of its device time."""
    tr = trace_red.load(str(tracer.dir))
    shutil.rmtree(tracer.dir, ignore_errors=True)
    span = [h for h in tr["host"] if h[0] == "bench.window"]
    if not span:
        raise RuntimeError("the trace holds no bench.window span")
    _, ws, we = span[0]
    ops = trace_red.clip(tr["ops"], ws, we)
    mods = trace_red.clip(tr["modules"], ws, we)
    patterns = cell.spec.json_file("programs.json")
    patterns = {k: v for k, v in patterns.items() if k != "note"}
    ctx = {
        "config": cell.config,
        "costs": costs,
        "peaks": cell.spec.peaks(device["kind"]),
        "counters": tracer.c0.delta(tracer.c1),
        "trace": {"busy_s": trace_red.busy_seconds(ops, tr["devices"]),
                  "window_s": we - ws,
                  "program_s": trace_red.program_seconds(mods, patterns)},
    }
    breakdown = {"device_ops": trace_red.top_ops(
                     trace_red.label_ops(ops, tr["modules"])),
                 "idle_gaps": trace_red.idle_gaps(ops, tr["host"], ws, we)}
    log(f"trace: window {we - ws:.4f} s, busy {ctx['trace']['busy_s']:.4f}"
        f" s, programs {ctx['trace']['program_s']}, counters "
        f"{ctx['counters']}, top programs {trace_red.top_ops(mods)}")
    return ctx, breakdown


def units(spec: Spec, name: str, traced: bool) -> dict:
    return {m["name"]: m["unit"] for m in spec.metrics(name, traced)}


# ------------------------------------------------------------------ main

def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root=None) -> int:
    args = parse(argv)
    spec = Spec(root or ROOT)
    wl = spec.workload(args.workload)
    try:
        device = require_devices(wl["chips"])
    except NoDevice as e:
        log(f"FAILED: {e}")
        return 2
    import jax
    configure_jax(spec.root)
    cell = Cell(spec, args.workload)
    log(f"device: {device}; cell {args.workload}; seed {args.seed}")

    t = time.monotonic()
    w = cell.weights(args.seed)
    t_w = time.monotonic() - t
    je = cell.plane(w, args.seed)
    t_e = time.monotonic() - t - t_w
    n_exec = cell.warm(je)
    t_warm = time.monotonic() - t - t_w - t_e
    drv = Driver(je, cell.mix)
    t_fill0 = time.monotonic()
    drive = drive_open if cell.mix["loop"] == "open" else drive_closed
    c0, c1, tracer = drive(cell, drv, args.seed, args.seconds,
                           bool(args.trace))
    setup_s = c0.t - T_START
    log(f"setup: {setup_s:.3f} s = start {t - T_START:.3f} + weights "
        f"{t_w:.3f} + engine {t_e:.3f} + warm-up {t_warm:.3f} (programs "
        f"and updates run: {n_exec}) + loop fill {c0.t - t_fill0:.3f}")
    lat = sorted(drv.late)
    log(f"generator lateness: median {stats.percentile(lat, 50):.6f} s, "
        f"max {lat[-1]:.6f} s over {len(lat)} submits")
    log(f"programs compiled or loaded inside the window: "
        f"{drv.built[c0.compiles:c1.compiles]}")

    e2e = end_to_end(cell, drv, c0, c1, setup_s)
    metrics, breakdown = {}, None
    if args.trace:
        ctx, breakdown = per_layer(cell, tracer, device)
        for name, unit in units(spec, args.workload, True).items():
            v = spec.reader(name).read(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": unit}
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
    else:
        for name, unit in units(spec, args.workload, False).items():
            metrics[name] = {"value": e2e[name], "unit": unit}
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:wl["chips"]])

    # the plane's state goes before the reference runs; the weights stay
    finished = [r for r in drv.recs.values() if r["tokens"] is not None]
    unit_failures = len(je.unit_failures)
    je.close()
    del je, drv.je
    gc.collect()
    t = time.monotonic()
    ck = cell.cell["check"]
    sample = check.sample(finished, args.seed, ck["sample_min_tokens"],
                          ck["sample_max_requests"])
    gaps = check.Reference(cell.ref, cell.config).gaps(
        w, sample, check.padded_len(cell.max_total))
    checks = {"logit_gap": {"value": gaps["served"] if sample else None,
                            "limit": ck["logit_gap"]}}
    for k, v in check.exact_failures(finished, cell.vocab,
                                     unit_failures).items():
        checks[k] = {"value": v, "limit": 0}
    log(f"reference: {len(sample)} greedy requests, {gaps['positions']} "
        f"served tokens, {time.monotonic() - t:.3f} s")
    correct = check.verdict(checks)
    missing = [m for m, v in metrics.items()
               if v["value"] is None or not math.isfinite(v["value"])]
    window = [r for r in drv.recs.values() if r["phase"] == "window"]
    failed = sum(r["tokens"] is None or len(r["tokens"]) != r["max_new"]
                 for r in window)
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    if missing:
        log(f"FAILED: no finite value for {missing}")
        return 1
    print(json.dumps({"correct": correct, "attempted": len(window),
                      "failed": failed, "metrics": metrics,
                      "device": device,
                      **({"breakdown": breakdown} if breakdown else {}),
                      "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serving launcher: wire FLOWSERVE TEs + a model-serving JE + the
autoscaler into a runnable deployment. The model is the arch's smoke
variant (CPU-sized widths) unless ``--published`` asks for its published
widths; ``--layers`` cuts the depth either way.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b \
        --mode colocated --requests 16

    # the live serving plane (DESIGN.md §9): Algorithm 1 over a real fleet
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b \
        --topology pd=1,colo=1 --policy dist_sched --requests 16

    # on a TPU: qwen3-8b at published widths, cut to 4 layers
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b \
        --published --layers 4 --topology pd=1,colo=1 --policy round_robin

A run in which fewer requests complete than were submitted exits non-zero
(``scheduled`` mode: than were placed on a TE with an engine; the plane:
unless a fault plan is attached).
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core.heatmap import HeatmapStudy
from repro.core.predictor import (DecodeLengthPredictor, PredictorConfig,
                                  synth_trace, train_predictor)
from repro.core.scheduling import (DistributedScheduler, SchedRequest,
                                   TEHandle)
from repro.engine import EngineConfig, FlowServe, Request, SamplingParams
from repro.engine.tokenizer import ByteTokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model


def build_model(arch: str, published: bool = False,
                layers: Optional[int] = None, seed: int = 0):
    """(bundle, params) for ``arch``: its smoke variant, or its published
    widths with ``published``; ``layers`` cuts the depth. The float32
    weights are random from ``seed``, made by one jitted program on the
    default device so the stacked layers never exist twice."""
    bundle = get_model(arch, smoke=not published, n_layers=layers)
    params = jax.jit(lambda k: bundle.init_params(k, jnp.float32))(
        jax.random.PRNGKey(seed))
    return bundle, params


def build_te(bundle, params, mode: str, name: str, tp: int = 1,
             horizon: int = 8, fused: bool = True) -> FlowServe:
    ecfg = EngineConfig(mode=mode, tp=tp, n_pages=256, page_size=8, n_slots=8,
                        max_len=256, max_batch_tokens=64, chunk_size=16,
                        max_decode_batch=8, fused_decode=fused,
                        decode_horizon=horizon)
    return FlowServe(bundle, params, ecfg, name=name)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--mode", default="colocated",
                    choices=["colocated", "pd", "scheduled"])
    ap.add_argument("--published", action="store_true",
                    help="build the arch at its published widths (default: "
                         "the CPU-sized smoke variant)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model to this many layers")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--tp", type=int, default=1,
                    help="devices per TE (SPMD tensor parallelism; simulated "
                         "hosts need XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    ap.add_argument("--horizon", type=int, default=8,
                    help="max fused multi-step decode horizon K "
                         "(DESIGN.md §8; 1 disables multi-step)")
    ap.add_argument("--no-fused-decode", action="store_true",
                    help="legacy v1 decode path (per-step host block tables "
                         "+ standalone sampler dispatch)")
    ap.add_argument("--topology", default=None,
                    help="live serving plane (DESIGN.md §9): fleet spec "
                         "'pd=N,colo=N' — N PD-disaggregated 1P+1D pairs "
                         "plus N PD-colocated TEs — or 'pd=NpXd,colo=N' "
                         "for an M:N group whose N prefill TEs feed X "
                         "decode TEs (§4.6; tp/horizon flags apply per "
                         "TE). Overrides --mode.")
    ap.add_argument("--policy", default="dist_sched",
                    choices=["dist_sched", "round_robin"],
                    help="JE placement policy for --topology (Algorithm 1 "
                         "vs the degenerate round-robin baseline)")
    ap.add_argument("--scale-to", type=int, default=0,
                    help="with --topology: mass scale-out to N SERVING TEs "
                         "through the cold-start ladder before serving "
                         "(DESIGN.md §10) — O(log N) fork rounds, "
                         "DRAM-warm remainder, cold fallback")
    ap.add_argument("--fleet-threads", type=int, default=0,
                    help="per-TE executor threads for --topology "
                         "(core/fleet.py): >1 steps fleet units on pinned "
                         "worker threads so engines overlap wall-clock "
                         "work; 0/1 = serial stepping")
    args = ap.parse_args()
    print(f"compile cache: {enable_compile_cache()}")
    if args.tp > 1:
        print(f"TE mesh: 1x{args.tp} over {jax.device_count()} visible devices")

    bundle, params = build_model(args.arch, args.published, args.layers)
    tok = ByteTokenizer()
    sp = SamplingParams(temperature=0.0, max_new_tokens=args.max_new,
                        stop_on_eos=False)
    prompts = [f"request {i}: explain serverless llm serving" for i in range(args.requests)]

    if args.topology:
        from repro.core.scaling import (DrainTrigger, DRAMPageCache,
                                        FastScaler, LoadSpreadTrigger,
                                        WarmPool)
        from repro.core.serving_plane import ServingJobEngine, TopologySpec
        topo = TopologySpec.parse(args.topology)
        if args.tp > 1:
            if topo.tp > 1 and topo.tp != args.tp:
                raise SystemExit(f"conflicting tp: --tp {args.tp} vs "
                                 f"--topology ...,tp={topo.tp}")
            topo.tp = args.tp
        cfg_full = get_config(args.arch)
        hs = HeatmapStudy(cfg_full)
        ecfg = EngineConfig(tp=topo.tp, n_pages=256, page_size=8,
                            max_batch_tokens=64, chunk_size=16,
                            max_decode_batch=8, decode_horizon=args.horizon,
                            fused_decode=not args.no_fused_decode)
        warm = WarmPool()
        je = ServingJobEngine(bundle, params, topo, heatmap=hs.combined(),
                              prefill_lens=hs.prefill_lens,
                              decode_ratios=hs.decode_ratios,
                              policy=args.policy, ecfg=ecfg,
                              scaler=FastScaler(DRAMPageCache(), warm=warm),
                              trigger=LoadSpreadTrigger(),
                              drain_trigger=DrainTrigger(),
                              warm_pool=warm,
                              fleet_threads=args.fleet_threads)
        if args.scale_to > je.n_serving():
            plan = je.scale_to(args.scale_to)
            tiers = " ".join(f"{k}={v}" for k, v in plan["tiers"].items()
                             if v)
            print(f"scale_to({args.scale_to}): {len(plan['rounds'])} rounds "
                  f"in {plan['wall_s']:.2f}s [{tiers}] "
                  f"serving={plan['n_serving']}")
            for r in plan["rounds"]:
                print(f"  round {r['round']}: +{len(r['tes'])} TEs "
                      f"({r['wall_s']:.2f}s) from {r['sources'] or ['-']}")
        t0 = time.monotonic()
        for p in prompts:
            je.submit(tok.encode(p), sampling=sp)
        comps = je.run_to_completion()
        dt = time.monotonic() - t0
        n_tok = sum(len(c.tokens) for c in comps)
        ttft = sum(c.ttft for c in comps) / max(1, len(comps))
        tpot = sum(c.tpot for c in comps) / max(1, len(comps))
        print(f"serving plane [{args.policy}] topology={args.topology} "
              f"fleet_threads={args.fleet_threads}: "
              f"{len(comps)} completions in {dt:.2f}s ({n_tok/dt:.1f} tok/s) "
              f"ttft={ttft*1e3:.0f}ms tpot={tpot*1e3:.1f}ms")
        print(f"  decisions={je.scheduler.decisions} "
              f"scale_events={len(je.scale_events)}")
        for te_id, m in je.fleet_metrics().items():
            extra = (f" {m['n_prefill']}P:{m['n_decode']}D"
                     if m["type"] == "pd_pair" else "")
            print(f"  {te_id}: type={m['type']} state={m['state']}"
                  f"{extra} load={m['load']:.1f}")
        for f in je.unit_failures:
            print(f"  unit failure at step {f['step']}: {f['unit']} "
                  f"{f['type']}: {f['message']}")
        je.close()
        if je.fault_plan is None:
            _require_all(len(comps), len(prompts))
        return

    if args.mode == "colocated":
        te = build_te(bundle, params, "colocated", "te-0", tp=args.tp,
                      horizon=args.horizon, fused=not args.no_fused_decode)
        t0 = time.monotonic()
        for p in prompts:
            te.add_request(Request(prompt_tokens=tok.encode(p), sampling=sp))
        comps = te.run_to_completion()
        dt = time.monotonic() - t0
        print(f"served {len(comps)} requests in {dt:.2f}s "
              f"({sum(len(c.tokens) for c in comps)/dt:.1f} tok/s)")
        for c in comps[:3]:
            print(f"  {c.req_id}: ttft={c.ttft*1e3:.0f}ms tpot={c.tpot*1e3:.1f}ms "
                  f"text={tok.decode(c.tokens)[:40]!r}")
        print("prefix-cache:", te.prefix_cache_stats())
        _require_all(len(comps), len(prompts))
        return

    if args.mode == "pd":
        pe = build_te(bundle, params, "prefill", "te-p0", tp=args.tp)
        de = build_te(bundle, params, "decode", "te-d0", tp=args.tp,
                      horizon=args.horizon, fused=not args.no_fused_decode)
        pe.distflow.link_cluster([de.distflow])
        for p in prompts:
            pe.add_request(Request(prompt_tokens=tok.encode(p), sampling=sp))
        comps = []
        for _ in range(10000):
            if not (pe.has_work() or de.has_work()):
                break
            pe.step()
            for rid in pe.pop_migratable():
                # DistFlow v2: sharded device-resident page runs, resharded
                # in flight when P/D tp differ; import overlaps with decode
                pe.migrate_out(rid, de)
            comps.extend(de.step())
        print(f"PD-disaggregated: {len(comps)} completions; "
              f"KV moved {pe.distflow.bytes_moved()/1e6:.2f} MB")
        _require_all(len(comps), len(prompts))
        return

    # scheduled: JE + Algorithm 1 over 2 colocated + 1 PD pair
    cfg_full = get_config(args.arch)
    hs = HeatmapStudy(cfg_full)
    xs, ys, _ = synth_trace(2000, PredictorConfig())
    pparams, acc = train_predictor(PredictorConfig(), xs, ys)
    pred = DecodeLengthPredictor(PredictorConfig(), pparams)
    tes = [TEHandle("te-c0", "colocated", engine=build_te(bundle, params, "colocated", "te-c0", tp=args.tp)),
           TEHandle("te-c1", "colocated", engine=build_te(bundle, params, "colocated", "te-c1", tp=args.tp)),
           TEHandle("te-pd0", "pd_pair")]
    ds = DistributedScheduler(tes, hs.combined(), hs.prefill_lens,
                              hs.decode_ratios, predictor=pred)
    routed = 0                  # requests placed on a TE with an engine
    for p in prompts:
        toks = tok.encode(p)
        te = ds.dist_sched(SchedRequest(tokens=toks))
        ds.commit(SchedRequest(tokens=toks), te)
        if te.engine is not None:
            te.engine.add_request(Request(prompt_tokens=toks, sampling=sp))
            routed += 1
    done = 0
    for te in tes:
        if te.engine is not None:
            done += len(te.engine.run_to_completion())
    print(f"scheduled mode: {done} completions; decisions={ds.decisions} "
          f"(predictor acc={acc:.3f})")
    _require_all(done, routed)


def _require_all(n_done: int, n_submitted: int) -> None:
    if n_done < n_submitted:
        print(f"FAILED: {n_done} of {n_submitted} requests completed",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

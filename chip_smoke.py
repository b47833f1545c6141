#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: qwen3-8b at its published widths
(d_model 4096, 32 query / 8 KV heads of 128, d_ff 12288, vocab 151936,
qk-norm), cut to 4 layers, float32, random weights from ``--seed``.

    python chip_smoke.py             # one chip: JE + PD pair + colocated TE
    python chip_smoke.py --chips 4   # four chips: tp=2 prefill and decode TEs

One chip. The serving plane (``ServingJobEngine``) runs ``pd=1,colo=1``
with round-robin placement, so requests go both through the PD handoff and
to the colocated TE; its three TEs share chip 0. A warm pass serves
prompts of the same lengths and sampling kinds (other token ids, so no
prefix is reused) to compile exactly the buckets the measured pass uses.
The measured pass serves 8 seeded requests (64-512 prompt tokens, 32 new
tokens, greedy and sampled). It fails unless every request completes with
its full token count, every token is in the vocabulary, the plane recorded
no unit failure, a request went through the PD handoff, and the engine's
prefill logits for one prompt agree with ``bundle.forward`` on the same
weights at ``highest`` matmul precision.

Four chips. A tp=1 reference TE on chip 0 serves the prompts greedily and
records first-token logits; then the plane runs ``pd=1,colo=0,tp=2``: the
prefill TE on chips 0-1, the decode TE on chips 2-3, so the tensor-parallel
collectives and the cross-mesh KV reshard of the PD handoff both run. It
fails unless the two TEs hold disjoint device sets (no TE fell back to
window 0) and both TEs' first-token logits agree with the reference. Greedy
token agreement with the reference is reported as a count.

The script asserts the TPU backend before anything else and never falls
back to the CPU. The numbers it prints are from a smoke run, not a
benchmark; they are printed even when a check fails. Its last line of
standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every check passed. The compile cache is ``JAX_COMPILATION_CACHE_DIR``
when set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen3-8b"
LAYERS = 4
N_REQUESTS = 8
MAX_NEW = 32
PROMPT_LENS = (64, 512)

# Engine vs reference (one chip). The engine runs float32 at the chip's
# default matmul precision, where the MXU takes bfloat16 operands; the
# reference is ``bundle.forward`` at "highest". The bound on the engine's
# error is calibrated on the chip itself: at most REF_FACTOR times the
# error of the same plain forward pass run at the engine's precision. Both
# paths round at the same places in kind and number (the same matmuls, in
# another order and attention layout), so their errors against the
# reference are of one size; REF_FACTOR covers the spread of two such
# maxima over a 152k vocabulary. REF_SLACK is float32 accumulation order
# where nothing rounds to bfloat16 (the CPU). REF_CAP fails a chip whose
# default precision strays further than expected. A wrong position, mask,
# page or layer moves logits by about their RMS, 1.0 in these units:
# deltas are max |delta| / rms(reference).
REF_FACTOR = 3.0
REF_SLACK = 1e-3
REF_CAP = 0.25
# tp=2 vs tp=1 (four chips): both run at the chip's default precision.
# Splitting the heads and d_ff over two chips changes the order of the
# float32 sums and the fusions, so the bfloat16 operand rounding falls on
# other intermediates: the two sides differ by up to about the error each
# has against "highest", which the one-chip check puts near 6e-2 of the
# logits' RMS on a v5e. A wrong reshard or collective moves logits by
# about their RMS, 1.0. (On the CPU, in float32, the delta is < 1e-4.)
TP_TOL = 1e-1


# ---------------------------------------------------------------- workload

def make_prompts(vocab: int, n: int, lens, seed: int):
    """``n`` prompts of token ids drawn over the whole vocabulary, lengths
    uniform in ``lens`` (inclusive)."""
    import numpy as np
    rng = np.random.RandomState(seed)
    lengths = rng.randint(lens[0], lens[1] + 1, n)
    return [[int(t) for t in rng.randint(0, vocab, n_tok)]
            for n_tok in lengths]


def sampling_for(i: int, max_new: int):
    """Even requests greedy, odd ones sampled; no EOS stop, so every request
    owes exactly ``max_new`` tokens."""
    from repro.engine import SamplingParams
    if i % 2 == 0:
        return SamplingParams(temperature=0.0, max_new_tokens=max_new,
                              stop_on_eos=False)
    return SamplingParams(temperature=0.8, top_p=0.95, max_new_tokens=max_new,
                          stop_on_eos=False)


def engine_config():
    """The TE configuration of the chip run: 1024 pages of 16 tokens per TE
    (512 MiB of float32 KV at 4 layers), 128-token prefill steps."""
    from repro.engine import EngineConfig
    return EngineConfig(page_size=16, n_pages=1024, max_batch_tokens=128,
                        chunk_size=64, max_decode_batch=8, decode_horizon=8)


def build_plane(bundle, params, topology: str, ecfg):
    from repro.core.heatmap import HeatmapStudy
    from repro.core.serving_plane import ServingJobEngine, TopologySpec
    hs = HeatmapStudy(bundle.cfg)
    return ServingJobEngine(bundle, params, TopologySpec.parse(topology),
                            heatmap=hs.combined(),
                            prefill_lens=hs.prefill_lens,
                            decode_ratios=hs.decode_ratios,
                            policy="round_robin", ecfg=ecfg)


def serve(je, prompts, sps):
    """Submit every prompt and run the plane to completion. Returns the
    request ids, {req_id: generated tokens}, each request's job, the wall
    seconds from the first submit to the last completion, and failures."""
    t0 = time.monotonic()
    rids, jobs = [], {}
    for p, sp in zip(prompts, sps):
        rid = je.submit(p, sampling=sp)
        rids.append(rid)
        jobs[rid] = je.requests[rid].job
    comps, failures = {}, []
    for c in je.run_to_completion():
        if c.req_id in comps:
            failures.append(f"request {c.req_id} completed twice")
        comps[c.req_id] = c.tokens
    return rids, comps, jobs, time.monotonic() - t0, failures


def check_completions(rids, comps, max_new, vocab):
    """Failures of the served requests' token counts and ids; ``comps`` maps
    request id to generated tokens."""
    out = []
    for rid in rids:
        if rid not in comps:
            out.append(f"{rid} did not complete")
            continue
        toks = comps[rid]
        if len(toks) != max_new:
            out.append(f"{rid}: {len(toks)} tokens, expected {max_new}")
        if not all(0 <= t < vocab for t in toks):
            out.append(f"{rid}: token outside the vocabulary")
    return out


def te_devices(te):
    """Device ids the TE's KV pool lives on."""
    return sorted(d.id for d in te.pool.k.sharding.device_set)


def engine_counters(je):
    return {e.name: {"prefill_dispatches": e.prefill_dispatches,
                     "decode_dispatches": e.host_dispatches,
                     "decode_steps": e.decode_steps,
                     "prefill_compiles": e.prefill_jit_compiles,
                     "decode_compiles": e.jit_compiles}
            for e in je.engines}


def rel_delta(got, ref) -> float:
    """max |got - ref| in units of rms(ref)."""
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / np.sqrt(np.mean(ref ** 2)))


def reference_logits(bundle, params, prompt, precision=None):
    """Next-token logits after ``prompt`` from the plain forward pass at
    matmul ``precision`` (None: the backend's default), real vocabulary
    only."""
    import jax
    import jax.numpy as jnp
    fwd = jax.jit(bundle.forward, static_argnums=0)
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        out = fwd(bundle.cfg, params, jnp.asarray([prompt], jnp.int32))
    return out[0, -1, :bundle.cfg.vocab_size]


# ---------------------------------------------------------------- phases

def one_chip_phase(bundle, params, prompts, warm_prompts, max_new, ecfg,
                   log=print):
    """The default phase: the plane over ``pd=1,colo=1`` on the visible
    device(s). Returns a report whose ``failures`` lists every failed
    check."""
    import numpy as np
    from repro.core.abstractions import TaskKind
    vocab = bundle.cfg.vocab_size
    sps = [sampling_for(i, max_new) for i in range(len(prompts))]
    t0 = time.monotonic()
    je = build_plane(bundle, params, "pd=1,colo=1", ecfg)
    try:
        for e in je.engines:
            log(f"TE {e.name} ({e.ecfg.mode}) on devices {te_devices(e)}")
        wids, warm, _, _, failures = serve(je, warm_prompts, sps)
        failures += [f"warm pass: {f}" for f in
                     check_completions(wids, warm, max_new, vocab)]
        warm_counts = engine_counters(je)
        setup_s = time.monotonic() - t0
        rids, comps, jobs, wall, fails = serve(je, prompts, sps)
        failures += fails + check_completions(rids, comps, max_new, vocab)
        if je.unit_failures:
            failures.append(f"unit failures: {je.unit_failures}")
        handoffs = sum(any(t.kind == TaskKind.DECODE and t.te_id is not None
                           for t in jobs[rid].tasks) for rid in rids)
        if handoffs < 1:
            failures.append("no request went through the PD handoff")
        counts = engine_counters(je)
        # first-token logits of the shortest prompt: the colocated TE's
        # prefill microkernel vs the plain forward pass on the same weights
        probe = min(prompts, key=len)
        colo = next(e for e in je.engines if e.ecfg.mode == "colocated")
        t1 = time.monotonic()
        got = np.asarray(colo.prompt_logits(probe))[:vocab]
        exact = np.asarray(reference_logits(bundle, params, probe, "highest"))
        plain = np.asarray(reference_logits(bundle, params, probe))
        floor = rel_delta(plain, exact)
        bound = REF_FACTOR * floor + REF_SLACK
        delta = rel_delta(got, exact)
        if not np.all(np.isfinite(got)):
            failures.append("engine logits are not finite")
        if bound > REF_CAP:
            failures.append(f"the plain forward at the default precision is "
                            f"{floor:.3e} from the reference: bound "
                            f"{bound:.3e} > cap {REF_CAP}")
        if delta > bound:
            failures.append(f"prefill logits: max |delta| = {delta:.3e} "
                            f"x rms(ref) > bound {bound:.3e}")
        return {
            "setup_s": setup_s, "serve_wall_s": wall,
            "reference_check_s": time.monotonic() - t1,
            "n_requests": len(rids), "pd_handoffs": handoffs,
            "tokens": sum(len(t) for t in comps.values()),
            "probe_prompt_len": len(probe),
            "logit_delta_engine": delta,
            "logit_delta_plain_default_precision": floor,
            "logit_bound": bound,
            "logit_argmax_match": int(np.argmax(got) == np.argmax(exact)),
            "window_fallbacks": je.window_fallbacks,
            "warm_counters": warm_counts, "counters": counts,
            "failures": failures,
        }
    finally:
        je.close()


def four_chip_phase(bundle, params, prompts, max_new, ecfg, log=print):
    """The ``--chips 4`` phase: a tp=1 reference TE on device 0, then the
    plane over ``pd=1,colo=0,tp=2`` on devices 0-3. Returns a report whose
    ``failures`` lists every failed check."""
    import numpy as np
    from repro.engine import FlowServe, Request
    vocab = bundle.cfg.vocab_size
    sp = sampling_for(0, max_new)
    ref = FlowServe(bundle, params, dataclasses.replace(ecfg, tp=1),
                    name="te-ref")
    log(f"reference TE {ref.name} (tp=1) on devices {te_devices(ref)}")
    ref_logits = [np.asarray(ref.prompt_logits(p))[:vocab] for p in prompts]
    ids = [ref.add_request(Request(prompt_tokens=p, sampling=sp))
           for p in prompts]
    done = {c.req_id: c.tokens for c in ref.run_to_completion()}
    failures = check_completions(ids, done, max_new, vocab)
    ref_tokens = [done.get(i, []) for i in ids]
    del ref, done
    gc.collect()

    t0 = time.monotonic()
    je = build_plane(bundle, params, "pd=1,colo=0,tp=2", ecfg)
    try:
        pe, de = je.engines
        for e in je.engines:
            log(f"TE {e.name} ({e.ecfg.mode}, tp={e.ecfg.tp}) on devices "
                f"{te_devices(e)}")
        if je.window_fallbacks:
            failures.append(f"{je.window_fallbacks} TE(s) fell back to "
                            f"window 0")
        if set(te_devices(pe)) & set(te_devices(de)):
            failures.append("the prefill and decode TEs share devices")
        setup_s = time.monotonic() - t0
        rids, comps, _, wall, fails = serve(je, prompts, [sp] * len(prompts))
        failures += fails + check_completions(rids, comps, max_new, vocab)
        if je.unit_failures:
            failures.append(f"unit failures: {je.unit_failures}")
        deltas = {}
        for e in (pe, de):
            deltas[e.name] = max(
                rel_delta(np.asarray(e.prompt_logits(p))[:vocab], r)
                for p, r in zip(prompts, ref_logits))
            if deltas[e.name] > TP_TOL:
                failures.append(f"{e.name}: first-token logits: max |delta| "
                                f"= {deltas[e.name]:.3e} x rms(ref) > "
                                f"{TP_TOL}")
        got = [comps.get(r, []) for r in rids]
        return {
            "setup_s": setup_s, "serve_wall_s": wall,
            "n_requests": len(rids),
            "kv_bytes_moved": pe.distflow.bytes_moved(),
            "logit_delta_vs_tp1": deltas,
            "greedy_tokens_equal": sum(a == b for g, w in zip(got, ref_tokens)
                                       for a, b in zip(g, w)),
            "greedy_tokens_total": sum(len(w) for w in ref_tokens),
            "first_tokens_equal": sum(bool(g and w and g[0] == w[0])
                                      for g, w in zip(got, ref_tokens)),
            "devices": {e.name: te_devices(e) for e in je.engines},
            "counters": engine_counters(je),
            "failures": failures,
        }
    finally:
        je.close()


# ---------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip serving plane (default); 4: only "
                         "the tensor-parallel PD path and its reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device['kind']} x{device['count']} "
          f"(platform {device['platform']})", flush=True)
    if device["platform"] != "tpu":
        print("FAILED: no TPU backend; this smoke run never falls back",
              file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"FAILED: --chips {args.chips} needs {args.chips} devices, "
              f"found {device['count']}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import build_model
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    t0 = time.monotonic()
    bundle, params = build_model(ARCH, published=True, layers=LAYERS,
                                 seed=args.seed)
    jax.block_until_ready(params)
    weights_s = time.monotonic() - t0
    cfg = bundle.cfg
    print(f"model: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads}x{cfg.head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} layers={cfg.n_layers} float32", flush=True)
    prompts = make_prompts(cfg.vocab_size, N_REQUESTS, PROMPT_LENS, args.seed)
    if args.chips == 4:
        rep = four_chip_phase(bundle, params, prompts, MAX_NEW,
                              engine_config())
    else:
        # same lengths and sampling kinds, other token ids: the warm pass
        # compiles the measured pass's buckets and reuses no prefix
        warm = [[(t + 1) % cfg.vocab_size for t in p] for p in prompts]
        rep = one_chip_phase(bundle, params, prompts, warm, MAX_NEW,
                             engine_config())
    rep["weights_s"] = weights_s
    rep["setup_s"] += weights_s
    rep["peak_bytes_in_use"] = {
        d.id: (d.memory_stats() or {}).get("peak_bytes_in_use",
                                           "not reported")
        for d in devs[:args.chips]}
    failures = rep.pop("failures")
    print("smoke run (not a benchmark):")
    for k, v in rep.items():
        print(f"  {k}: {v}")
    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

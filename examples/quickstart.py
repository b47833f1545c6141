"""Quickstart — the end-to-end serving driver (the paper's kind: serving).

Boots one PD-colocated FLOWSERVE TE with a reduced-config model, submits a
batch of chat requests through the request-job-task path, and prints
completions + engine stats.

    PYTHONPATH=src python examples/quickstart.py [--arch qwen3-8b]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.engine import EngineConfig, FlowServe, Request, SamplingParams
from repro.engine.tokenizer import ByteTokenizer
from repro.models import get_model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=32)
    args = ap.parse_args()

    print(f"[quickstart] loading {args.arch} (reduced config, CPU)")
    bundle = get_model(args.arch, smoke=True)
    params = bundle.init_params(jax.random.PRNGKey(0), jnp.float32)
    tok = ByteTokenizer()
    eng = FlowServe(bundle, params, EngineConfig(
        mode="colocated", n_pages=256, page_size=8, n_slots=8, max_len=256,
        max_batch_tokens=64, chunk_size=16, max_decode_batch=8))

    prompts = [
        "what is a serverless llm platform?",
        "explain prefill decode disaggregation",
        "how does a radix prefix cache work?",
        "what is a relational tensor cache?",
        "why pre-warm pods for fast scaling?",
        "what does npu-fork do?",
    ][: args.requests]
    sp = SamplingParams(temperature=0.8, top_p=0.95,
                        max_new_tokens=args.max_new, stop_on_eos=False)

    t0 = time.monotonic()
    ids = {}
    for p in prompts:
        rid = eng.add_request(Request(prompt_tokens=tok.encode(p), sampling=sp))
        ids[rid] = p
    comps = eng.run_to_completion()
    wall = time.monotonic() - t0

    total_tokens = sum(len(c.tokens) for c in comps)
    print(f"[quickstart] {len(comps)} completions, {total_tokens} tokens "
          f"in {wall:.2f}s ({total_tokens / wall:.1f} tok/s)")
    for c in comps:
        print(f"  - {ids[c.req_id][:36]!r:40s} ttft={c.ttft * 1e3:6.0f}ms "
              f"tpot={c.tpot * 1e3:6.1f}ms gen={tok.decode(c.tokens)[:32]!r}")
    print(f"[quickstart] prefix cache: {eng.prefix_cache_stats()}")
    print(f"[quickstart] engine steps: {eng.steps}")


if __name__ == "__main__":
    main()

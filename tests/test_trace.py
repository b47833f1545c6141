"""The serving path's own measurement (engine/trace.py): exact work
counters on a fixed schedule, each request's timeline, and the span list.

The schedule (as in tests/test_hotloop.py): 3 prompts of 12 tokens, page
size 8, token budget 32, chunk 8, decode batch 4, horizon K=4, 10 new
tokens each, greedy.

- Prefill, step 1: 8 tokens of each prompt (positions 0-7, one page each),
  packed into a token bucket of 32 x page bucket 1. Step 2: positions 8-10
  and the extension row at 11 (two pages), bucket 16 x 2. Tokens 24 + 12;
  live context sum over tokens of position + 1 = 3 x 78 = 234. Every
  query scores every entry's gathered run, and the entry bucket is 8
  (``max_prefill_seqs``): slots (32 x 8 x 1 + 16 x 8 x 2) x 8 = 4096.
- Decode: the first token came from prefill, so each request owes 9. The
  horizons are 4 (contexts 13-16), 4 (17-20) and 1 (21), all at batch
  bucket 4 x page bucket 4 (21 tokens need 3 pages): 3 dispatches, 9
  steps, 27 rows; live 3 x (13 + ... + 21) = 459; slots 9 x 4 x 4 x 8 =
  1152. The last horizon is drained with nothing behind it: 1 host sync.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import EngineConfig, FlowServe, Request, SamplingParams
from repro.engine import trace
from repro.models import get_model

SP = SamplingParams(temperature=0.0, max_new_tokens=10, stop_on_eos=False)


@pytest.fixture(scope="module")
def qwen():
    bundle = get_model("qwen3-8b", smoke=True)
    return bundle, bundle.init_params(jax.random.PRNGKey(0), jnp.float32)


def _engine(qwen, **kw):
    bundle, params = qwen
    ecfg = EngineConfig(n_pages=64, page_size=8, max_batch_tokens=32,
                        chunk_size=8, max_decode_batch=4, decode_horizon=4,
                        **kw)
    te = FlowServe(bundle, params, ecfg)
    for i in range(3):
        prompt = [1] + [int(x) for x in
                        np.random.RandomState(i).randint(3, 200, 11)]
        te.add_request(Request(prompt_tokens=prompt, sampling=SP,
                               req_id=f"r{i}"))
    return te


def test_counters_are_exact_on_a_fixed_schedule(qwen):
    te = _engine(qwen)
    comps = te.run_to_completion()
    assert len(comps) == 3 and all(len(c.tokens) == 10 for c in comps)
    got = {k: getattr(te, k) for k in (
        "prefill_dispatches", "prefill_tokens", "prefill_kv_live",
        "prefill_kv_slots", "decode_dispatches", "decode_steps",
        "decode_rows", "decode_kv_live", "decode_kv_slots", "host_syncs")}
    assert got == {"prefill_dispatches": 2, "prefill_tokens": 36,
                   "prefill_kv_live": 234, "prefill_kv_slots": 4096,
                   "decode_dispatches": 3, "decode_steps": 9,
                   "decode_rows": 27, "decode_kv_live": 459,
                   "decode_kv_slots": 1152, "host_syncs": 1}


def test_legacy_paths_count_the_same_work_at_their_own_shapes(qwen):
    """Per-sequence prefill and per-step decode: the same tokens, rows and
    live context; slots at the exact shapes those paths run (a chunk reads
    its sequence's pages, a decode step batch x the longest page run)."""
    te = _engine(qwen, batched_prefill=False, fused_decode=False)
    te.run_to_completion()
    assert (te.prefill_dispatches, te.prefill_tokens) == (6, 33)
    # chunks 0-7 (1 page) and 8-10 (2 pages); the last prompt token is
    # processed by the first decode step
    assert te.prefill_kv_live == 3 * sum(range(1, 12))
    assert te.prefill_kv_slots == 3 * (8 * 1 + 3 * 2) * 8
    # 10 decode steps at contexts 12-21, pages 2 (12-16) then 3 (17-21)
    assert (te.decode_dispatches, te.decode_steps, te.decode_rows) == (
        10, 10, 30)
    assert te.decode_kv_live == 3 * sum(range(12, 22))
    assert te.decode_kv_slots == (5 * 3 * 2 + 5 * 3 * 3) * 8
    assert te.host_syncs == 10


def test_request_timeline_is_ordered(qwen):
    """arrival <= first prefill dispatch <= first token <= finish."""
    te = _engine(qwen)
    for c in te.run_to_completion():
        assert c.arrival <= c.first_dispatch <= c.arrival + c.ttft \
            <= c.finish


def test_request_state_is_released_with_the_request(qwen):
    te = _engine(qwen)
    te.run_to_completion()
    assert te._first_dispatch == {}


def test_span_names_are_unique_and_the_decorator_refuses_unknown_ones():
    assert len(set(trace.SPANS)) == len(trace.SPANS)
    with pytest.raises(ValueError):
        trace.spanned("te.nothing")

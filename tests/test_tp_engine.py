"""SPMD tensor-parallel engine tests (DESIGN.md §5).

A TP>1 FLOWSERVE TE spans a 1×tp ("data","model") mesh of simulated host
devices (tests/conftest.py forces 8). It must reproduce the TP=1 engine:
greedy tokens bit-for-bit end-to-end, and raw decode/prefill logits within
fp32 tolerance. Two sharding regimes are covered:

  * qwen3-8b smoke at tp=2 — heads divide: attention + KV pool shard.
  * granite smoke at tp=4 — KV heads (2) do NOT divide: attention and the
    paged pool replicate, only MoE FFN / vocab shard.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import EngineConfig, FlowServe, Request, SamplingParams
from repro.engine.kv_cache import pages_needed
from repro.engine.model_runner import SequenceState
from repro.engine.sampling import SamplingParams as SParams
from repro.engine.sampling import nucleus_mask, sample, sample_batch
from repro.launch.sharding import attn_shardable
from repro.models import get_model

needs2 = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")
needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >=4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")

SP = SamplingParams(temperature=0.0, max_new_tokens=6, stop_on_eos=False)
PROMPT = [1, 5, 9, 200, 41, 33, 77, 150, 3, 8, 12, 99]


def _mesh_axes(array) -> list:
    """Flat list of mesh-axis names an array's sharding spec mentions."""
    out = []
    for entry in tuple(array.sharding.spec):
        if entry is None:
            continue
        out.extend(entry if isinstance(entry, tuple) else (entry,))
    return out


def _engine(arch, tp, **kw):
    bundle = get_model(arch, smoke=True)
    params = bundle.init_params(jax.random.PRNGKey(0), jnp.float32)
    ecfg = EngineConfig(tp=tp, n_pages=64, page_size=8, n_slots=4, max_len=96,
                        max_batch_tokens=32, chunk_size=8, max_decode_batch=4,
                        **kw)
    return FlowServe(bundle, params, ecfg)


def _raw_logits(arch, tp):
    """(prefill-final, first-decode) logits straight off the PagedRunner."""
    te = _engine(arch, tp, enable_prefix_cache=False)
    seq = SequenceState("s0", tokens=list(PROMPT), n_prompt=len(PROMPT))
    seq.pages = te.pool.alloc(pages_needed(len(PROMPT) + 1, te.pool.page_size))
    pre = np.asarray(te.runner.prefill_chunk(seq, list(PROMPT)))
    seq.tokens.append(17)
    dec = np.asarray(te.runner.decode([seq])[0])
    return pre, dec


def _serve_tokens(arch, tp, n=3, **kw):
    te = _engine(arch, tp, **kw)
    prompts = [[1] + [int(x) for x in np.random.RandomState(i).randint(3, 200, 11)]
               for i in range(n)]
    for i, p in enumerate(prompts):
        te.add_request(Request(prompt_tokens=p, sampling=SP, req_id=f"r{i}"))
    comps = {c.req_id: c.tokens for c in te.run_to_completion()}
    assert len(comps) == n
    return [comps[f"r{i}"] for i in range(n)], te


# ---------------------------------------------------------------------------
# qwen3 smoke (heads divide → attention + KV pool shard)
# ---------------------------------------------------------------------------


@needs2
def test_tp2_decode_logits_match_tp1_qwen3():
    p1, d1 = _raw_logits("qwen3-8b", 1)
    p2, d2 = _raw_logits("qwen3-8b", 2)
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(d1, d2, rtol=1e-4, atol=1e-4)


@needs2
def test_tp2_qwen3_shards_attention_and_pool():
    te = _engine("qwen3-8b", 2)
    assert attn_shardable(te.cfg, 2)
    wq = te.runner.params["blocks"]["attn"]["wq"]
    assert "model" in _mesh_axes(wq)
    assert "model" in _mesh_axes(te.pool.k)


@needs2
def test_tp2_engine_tokens_equal_tp1_qwen3():
    t1, _ = _serve_tokens("qwen3-8b", 1)
    t2, te2 = _serve_tokens("qwen3-8b", 2)
    assert t1 == t2
    # fused decode (DESIGN.md §8): sampling rides inside the decode jit, so
    # ZERO standalone sampler dispatches; the legacy path still pays one
    # batched dispatch per step (and the old per-seq loop paid B)
    assert te2.sampler_dispatches == 0
    t2l, te2l = _serve_tokens("qwen3-8b", 2, fused_decode=False)
    assert t1 == t2l
    assert te2l.sampler_dispatches == te2l.decode_steps


@needs2
@pytest.mark.slow
def test_tp2_engine_tokens_equal_tp1_slotrunner():
    """SlotRunner family (recurrentgemma hybrid): seq-sharded dense caches."""
    t1, _ = _serve_tokens("recurrentgemma-2b", 1, n=2)
    t2, _ = _serve_tokens("recurrentgemma-2b", 2, n=2)
    assert t1 == t2


# ---------------------------------------------------------------------------
# granite smoke at tp=4 (KV heads do not divide → attention replicates,
# only MoE FFN / vocab shard)
# ---------------------------------------------------------------------------


@needs4
def test_tp4_granite_replicates_attention_shards_ffn():
    te = _engine("granite-moe-3b-a800m", 4)
    assert not attn_shardable(te.cfg, 4)      # 2 KV heads % 4 != 0
    wq = te.runner.params["blocks"]["attn"]["wq"]
    w_up = te.runner.params["blocks"]["moe"]["w_up"]
    assert "model" not in _mesh_axes(wq)
    assert "model" in _mesh_axes(w_up)
    assert "model" not in _mesh_axes(te.pool.k)


@needs4
def test_tp4_decode_logits_match_tp1_granite():
    p1, d1 = _raw_logits("granite-moe-3b-a800m", 1)
    p4, d4 = _raw_logits("granite-moe-3b-a800m", 4)
    np.testing.assert_allclose(p1, p4, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(d1, d4, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Batched sampling (TP-independent)
# ---------------------------------------------------------------------------


def test_sample_batch_greedy_matches_per_seq():
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(key, (5, 300)) * 4.0
    want = np.asarray([int(sample(logits[i:i + 1], SParams(temperature=0.0),
                                  jax.random.fold_in(key, i), 256)[0])
                       for i in range(5)])
    got = np.asarray(sample_batch(logits, np.zeros(5), np.ones(5),
                                  jax.random.PRNGKey(0), 256))
    np.testing.assert_array_equal(want, got)


def test_sample_batch_mixed_params_one_dispatch():
    key = jax.random.PRNGKey(7)
    logits = jax.random.normal(key, (4, 300)) * 4.0
    temps = np.asarray([0.0, 0.8, 0.0, 1.5], np.float32)
    top_ps = np.asarray([1.0, 0.9, 0.5, 1.0], np.float32)
    toks = np.asarray(sample_batch(logits, temps, top_ps,
                                   jax.random.PRNGKey(1), 256))
    assert toks.shape == (4,)
    assert (toks >= 0).all() and (toks < 256).all()   # pad vocab masked
    # greedy rows are deterministic regardless of the key
    greedy = np.argmax(np.where(np.arange(300)[None] >= 256, -1e30,
                                np.asarray(logits)), axis=-1)
    assert toks[0] == greedy[0] and toks[2] == greedy[2]
    # same key → same draw; different key may differ
    again = np.asarray(sample_batch(logits, temps, top_ps,
                                    jax.random.PRNGKey(1), 256))
    np.testing.assert_array_equal(toks, again)


@pytest.mark.parametrize("top_p", [0.1, 0.5, 0.9, 0.999])
def test_nucleus_mask_matches_sorted_cumsum(top_p):
    """The sort-free top-p cut keeps exactly the set a descending sort +
    cumsum keeps: the highest logits until their mass reaches top_p, ties
    at the cut included (rows 0-1 carry deliberate ties and negatives)."""
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (6, 500))) * 3.0
    x[0, :50] = 2.5
    x[1] = np.round(x[1])
    s = np.sort(x, axis=-1)[:, ::-1]
    p = np.exp(s - s[:, :1])
    cum = np.cumsum(p / p.sum(-1, keepdims=True), axis=-1)
    cut = s[np.arange(6), np.sum(cum < top_p, axis=-1)]
    want = x >= cut[:, None]
    got = np.asarray(nucleus_mask(jnp.asarray(x, jnp.float32),
                                  jnp.full((6,), top_p, jnp.float32)))
    np.testing.assert_array_equal(got, want)

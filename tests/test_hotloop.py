"""NPU-centric decode hot loop tests (DESIGN.md §8).

The fused decode path — sample-in-step, persistent device-resident batch
metadata, power-of-two bucketed jits, multi-step (lax.scan) horizons with
EOS checked one horizon late — must be bit-identical to the legacy
per-step path on greedy decoding, across multi-step K ∈ {1,4,8}, bucketed
vs exact jits, qwen3 + granite, and TP ∈ {1,2}. Steady-state serving must
cost ZERO host syncs and ZERO jit compiles per step after warmup, and one
host dispatch per K-step horizon.

``host_syncs`` counts decode token fetches with nothing dispatched behind
them (engine/flowserve.py). The schedule ``_serve`` runs: 3 prompts of 12
tokens, budget 32 and chunk 8, prefill in two steps (8 tokens, then 3 and
the extension row that samples the first token), so each request owes 9
decode tokens. The legacy path fetches once per decode step: 9 syncs. The
fused path dispatches horizons of min(K, owed) floored to a power of two
(K=1: 1 x9; K=4: 4, 4, 1; K=8: 8, 1), each fetched behind the next; after
the last one the requests owe 0, so the next step drains it with nothing
behind it: 1 sync. At K=1 the batch's pages are reserved one step ahead,
so the third page (context 17) arrives mid-run, outgrows the block table's
page bucket of 2, and the rebuild first drains the block in flight: 2.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import EngineConfig, FlowServe, Request, SamplingParams
from repro.engine.hotloop import DecodeHotState, pow2_bucket
from repro.engine.kv_cache import PagedKVPool
from repro.kernels import ref as KREF
from repro.models import get_model
from repro.models import layers as L
from repro.models import serving as S
from repro.models import transformer as T

needs2 = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")

SP = SamplingParams(temperature=0.0, max_new_tokens=10, stop_on_eos=False)


@pytest.fixture(scope="module")
def qwen():
    bundle = get_model("qwen3-8b", smoke=True)
    params = bundle.init_params(jax.random.PRNGKey(0), jnp.float32)
    return bundle, params


@pytest.fixture(scope="module")
def granite():
    bundle = get_model("granite-moe-3b-a800m", smoke=True)
    params = bundle.init_params(jax.random.PRNGKey(0), jnp.float32)
    return bundle, params


def _prompts(n, length=11, seed0=0):
    return [[1] + [int(x) for x in
                   np.random.RandomState(seed0 + i).randint(3, 200, length)]
            for i in range(n)]


def _serve(model, sp=SP, n=3, tp=1, **kw):
    bundle, params = model
    ecfg = EngineConfig(tp=tp, n_pages=64, page_size=8, max_batch_tokens=32,
                        chunk_size=8, max_decode_batch=4, **kw)
    te = FlowServe(bundle, params, ecfg)
    for i, p in enumerate(_prompts(n)):
        te.add_request(Request(prompt_tokens=p, sampling=sp, req_id=f"r{i}"))
    comps = {c.req_id: c.tokens for c in te.run_to_completion()}
    assert len(comps) == n
    return [comps[f"r{i}"] for i in range(n)], te


# ---------------------------------------------------------------------------
# Greedy parity: fused+bucketed+multi-step vs the legacy per-step path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k,syncs", [(1, 2), (4, 1), (8, 1)],
                         ids=["1", "4", "8"])
def test_fused_parity_qwen3(qwen, k, syncs):
    want, te0 = _serve(qwen, fused_decode=False)
    got, te = _serve(qwen, fused_decode=True, decode_horizon=k)
    assert got == want
    assert te.sampler_dispatches == 0          # sampling fused into the step
    assert te0.host_syncs == 9                 # v1 blocked every decode step
    assert te.host_syncs == syncs              # only drains block


def test_fused_parity_eos_one_horizon_late(qwen):
    """stop_on_eos with a long budget: any EOS lands mid-horizon and the
    fused path discards post-stop tokens — completions stay identical."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=24, stop_on_eos=True)
    want, _ = _serve(qwen, sp=sp, fused_decode=False)
    got, _ = _serve(qwen, sp=sp, fused_decode=True, decode_horizon=8)
    assert got == want


def test_all_eos_mid_horizon_terminates(qwen, monkeypatch):
    """Worst case of late EOS checking: the ONLY running sequence stops in
    block t while block t+1 is already in flight — running empties, and the
    next plan has no decode batch. The engine must still drain the orphaned
    horizon (not livelock) and match the legacy path exactly."""
    free_run = SamplingParams(temperature=0.0, max_new_tokens=12,
                              stop_on_eos=False)
    want, _ = _serve(qwen, sp=free_run, n=1, fused_decode=False)
    fake_eos = want[0][5]          # a token greedy decoding provably emits
    import repro.engine.flowserve as FS
    monkeypatch.setattr(FS, "EOS_ID", fake_eos)
    sp = SamplingParams(temperature=0.0, max_new_tokens=12, stop_on_eos=True)
    ref, _ = _serve(qwen, sp=sp, n=1, fused_decode=False)
    got, te = _serve(qwen, sp=sp, n=1, fused_decode=True, decode_horizon=4)
    assert got == ref
    assert not te._inflight and not te._pending


def test_fused_parity_granite(granite):
    want, _ = _serve(granite, fused_decode=False)
    got, _ = _serve(granite, fused_decode=True, decode_horizon=4)
    assert got == want


@needs2
def test_fused_parity_qwen3_tp2(qwen):
    want, _ = _serve(qwen, tp=2, fused_decode=False)
    got, te = _serve(qwen, tp=2, fused_decode=True, decode_horizon=4)
    assert got == want
    assert te.host_syncs == 1     # horizons 4, 4, 1; the last one drained


@needs2
@pytest.mark.slow
def test_fused_parity_granite_tp2(granite):
    want, _ = _serve(granite, tp=2, fused_decode=False)
    got, _ = _serve(granite, tp=2, fused_decode=True, decode_horizon=4)
    assert got == want


def test_fused_stochastic_serves_valid_tokens(qwen):
    sp = SamplingParams(temperature=0.9, top_p=0.9, max_new_tokens=8,
                        stop_on_eos=False)
    got, _ = _serve(qwen, sp=sp, fused_decode=True, decode_horizon=4)
    bundle, _ = qwen
    for toks in got:
        assert len(toks) == 8
        assert all(0 <= t < bundle.cfg.vocab_size for t in toks)


# ---------------------------------------------------------------------------
# Steady-state regression: zero syncs, zero recompiles, 1 dispatch / horizon
# ---------------------------------------------------------------------------


def test_steady_state_counters(qwen):
    bundle, params = qwen
    k = 4
    # page_size 64: one page holds any sequence here, so the steady window
    # has NO page-append events — the per-horizon dispatch count is exact
    ecfg = EngineConfig(n_pages=16, page_size=64, max_batch_tokens=32,
                        chunk_size=8, max_decode_batch=4, fused_decode=True,
                        decode_horizon=k)
    te = FlowServe(bundle, params, ecfg)
    sp = SamplingParams(temperature=0.0, max_new_tokens=48, stop_on_eos=False)
    for i, p in enumerate(_prompts(3)):
        te.add_request(Request(prompt_tokens=p, sampling=sp, req_id=f"r{i}"))
    # warm up: run until every sequence is decoding and buckets/jits exist
    for _ in range(50):
        te.step()
        if not (te.scheduler.waiting or te.scheduler.ready
                or te.scheduler.prefilling) and te.decode_steps >= 2 * k:
            break
    # 4 steady steps: each dispatches a K=4 horizon and fetches the one
    # before it, which has the new horizon behind it, so none is a sync
    syncs0, compiles0 = te.host_syncs, te.jit_compiles
    disp0, dsteps0 = te.host_dispatches, te.decode_steps
    for _ in range(4):
        te.step()
    assert te.jit_compiles == compiles0        # bucketed: no recompiles
    assert te.decode_steps - dsteps0 == 4 * k  # multi-step horizons ran
    assert te.host_dispatches - disp0 == 4     # ONE dispatch per horizon
    assert te.host_syncs == syncs0             # async fetch, never blocks


def test_warmup_precompiles_all_buckets(qwen):
    bundle, params = qwen
    # page_size 16 keeps every sequence within 2 pages, so the small warmed
    # grid covers the whole serve trajectory
    ecfg = EngineConfig(n_pages=64, page_size=16, max_batch_tokens=32,
                        chunk_size=8, max_decode_batch=4, fused_decode=True,
                        decode_horizon=2)
    te = FlowServe(bundle, params, ecfg)
    n = te.warmup_decode(max_pages=2)
    assert n == 3 * 2 * 2          # bb in {1,2,4} x pb in {1,2} x K in {1,2}
    compiles0 = te.jit_compiles
    for i, p in enumerate(_prompts(3)):
        te.add_request(Request(prompt_tokens=p, sampling=SP, req_id=f"r{i}"))
    comps = te.run_to_completion()
    assert len(comps) == 3
    assert te.jit_compiles == compiles0    # steady serving never compiled


# ---------------------------------------------------------------------------
# Decode attention reads its own layer of the stacked pool
# ---------------------------------------------------------------------------


def _sliced_pool_decode_body(rt, params, tokens, bt, lengths, k_pool, v_pool):
    """One decode step that hands each layer's pool, sliced out of the
    stacked pool, to the paged-attention oracle: how the decode read KV
    before it gathered token rows. The reference of the test below."""
    cfg = rt.cfg
    ps = rt.pool.page_size
    b = tokens.shape[0]
    x = T.embed(cfg, params, tokens[:, None])
    pos = (lengths - 1)[:, None]
    page = bt[jnp.arange(b), (lengths - 1) // ps]
    slot = (lengths - 1) % ps
    for li in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[li], params["blocks"])
        h = L.apply_norm(x, p["ln1"], cfg.norm)
        q, k_new, v_new = L.attn_qkv(p["attn"], h, cfg.n_heads,
                                     cfg.n_kv_heads, cfg.head_dim, pos,
                                     cfg.rope_theta, cfg.qk_norm,
                                     rotary_dim=cfg.rotary_dim)
        k_pool = k_pool.at[li, page, slot].set(k_new[:, 0])
        v_pool = v_pool.at[li, page, slot].set(v_new[:, 0])
        win = rt._wins[li] if rt._wins[li] < T.GLOBAL_WINDOW else None
        o = KREF.paged_attention_ref(q[:, 0], k_pool[li], v_pool[li], bt,
                                     lengths, softcap=cfg.attn_logit_softcap,
                                     window=win)
        x = x + S._post_attn(cfg, p, L.attn_out(p["attn"], o[:, None]))
        x = x + rt._mlp(p, x)
    return T.unembed(cfg, params, x)[:, 0], k_pool, v_pool


@pytest.fixture(scope="module", params=["qwen3-8b", "gemma2-9b"])
def paged_model(request):
    """A tiny qwen3 (GQA, qk-norm) and a tiny gemma2 (local window of 16
    alternating with global layers, attention softcap)."""
    bundle = get_model(request.param, smoke=True)
    params = bundle.init_params(jax.random.PRNGKey(0), jnp.float32)
    return bundle, params


@pytest.mark.parametrize("pb,lengths", [(1, [8, 5, 1]), (4, [32, 19, 7])],
                         ids=["pb1", "pb4"])
def test_decode_attention_reads_its_own_layer(paged_model, pb, lengths):
    """Every layer of the stacked pool holds its own random KV, and each
    row's run is past the page's or window's end in places: the decode
    step's logits and pools match the sliced-pool reference at float32.
    Rows: three live ones with their own pages, the shorter ones padded
    with the scratch page, and one padding row parked on the scratch page
    (length 1), as the fused batch holds them (batch bucket 4)."""
    bundle, params = paged_model
    ps = 8
    te = FlowServe(bundle, params, EngineConfig(n_pages=16, page_size=ps))
    rt = te.runner
    scratch = te.pool.scratch_page()
    bt = np.full((4, pb), scratch, np.int32)
    for i, n in enumerate(lengths):
        pages = te.pool.alloc(-(-n // ps))
        bt[i, :len(pages)] = pages
    lens = jnp.asarray(lengths + [1], jnp.int32)
    shape = te.pool.k.shape
    k_pool = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    v_pool = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)
    tokens = jnp.asarray([11, 97, 305, 0], jnp.int32)
    args = (params, tokens, jnp.asarray(bt), lens, k_pool, v_pool)
    got = jax.jit(rt.decoder._decode_body)(*args)
    want = jax.jit(functools.partial(_sliced_pool_decode_body, rt))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=0, atol=1e-6)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


# ---------------------------------------------------------------------------
# Device-resident batch state: incremental events, not per-step rebuilds
# ---------------------------------------------------------------------------


def test_hot_state_incremental_events(qwen):
    bundle, _ = qwen
    pool = PagedKVPool(bundle.cfg, 32, 8)
    hot = DecodeHotState(pool)
    # "a" holds 3 pages so the page bucket starts at 4: "b" can later grow
    # 2 -> 3 pages WITHIN the bucket (incremental), not across it (rebuild)
    pages = {"a": pool.alloc(3), "b": pool.alloc(2), "c": pool.alloc(2)}
    rows = [(sid, pages[sid], 5, 7, 0.0, 1.0) for sid in ("a", "b")]
    assert hot.sync(rows) > 0                      # first sync builds rows
    assert hot.bb == 2 and hot.pb == 4
    assert hot.sync(rows) == 0                     # steady state: ZERO work
    # join grows the batch bucket -> rebuild; then steady again
    rows3 = rows + [("c", pages["c"], 5, 9, 0.7, 0.9)]
    assert hot.sync(rows3) > 0
    assert hot.bb == 4
    assert hot.sync(rows3) == 0
    # page append on one row is one incremental scatter, not a rebuild
    pages["b"].extend(pool.alloc(1))
    rebuilds0 = hot.rebuilds
    ev = hot.sync([(sid, pages[sid], 5, 7, 0.0, 1.0) if sid != "c"
                   else ("c", pages["c"], 5, 9, 0.7, 0.9)
                   for sid in ("a", "b", "c")])
    assert ev == 1 and hot.rebuilds == rebuilds0
    # leave deactivates the rows and parks their KV write on the scratch page
    slot_b, slot_c = hot.slot_of["b"], hot.slot_of["c"]
    ev = hot.sync([("a", pages["a"], 5, 7, 0.0, 1.0)])
    assert ev > 0
    active = np.asarray(hot.active)
    bt = np.asarray(hot.bt)
    lengths = np.asarray(hot.lengths)
    for slot in (slot_b, slot_c):
        assert not active[slot]
        assert lengths[slot] == 1
        assert bt[slot, 0] == pool.scratch_page()
    assert active[hot.slot_of["a"]]


def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]


def test_req_id_reuse_joins_fresh(qwen):
    """A finished sequence's hot-state row is evicted at release, so a
    REUSED req id joins fresh instead of aliasing the stale device row
    (whose lengths/block-table still describe the finished request)."""
    bundle, params = qwen
    ecfg = EngineConfig(n_pages=64, page_size=8, max_batch_tokens=32,
                        chunk_size=8, max_decode_batch=4, fused_decode=True,
                        decode_horizon=4)
    te = FlowServe(bundle, params, ecfg)
    sp = SamplingParams(temperature=0.0, max_new_tokens=8, stop_on_eos=False)
    p = _prompts(1)[0]
    te.add_request(Request(prompt_tokens=p, sampling=sp, req_id="dup"))
    first = {c.req_id: c.tokens for c in te.run_to_completion()}["dup"]
    # a lone sequence finishes via the in-loop drain: without the explicit
    # evict its id would linger in slot_of and alias on the next serve
    assert "dup" not in (te._hot.slot_of if te._hot else {})
    te.add_request(Request(prompt_tokens=p, sampling=sp, req_id="dup"))
    second = {c.req_id: c.tokens for c in te.run_to_completion()}["dup"]
    assert second == first


# ---------------------------------------------------------------------------
# Satellite: per-batch sampling-param arrays are cached on the legacy path
# ---------------------------------------------------------------------------


def test_sampling_param_cache_keyed_on_batch(qwen):
    bundle, params = qwen
    ecfg = EngineConfig(n_pages=64, page_size=8, max_batch_tokens=32,
                        chunk_size=8, max_decode_batch=4, fused_decode=False)
    te = FlowServe(bundle, params, ecfg)
    sp = SamplingParams(temperature=0.0, max_new_tokens=6, stop_on_eos=False)
    for i, p in enumerate(_prompts(2)):
        te.add_request(Request(prompt_tokens=p, sampling=sp, req_id=f"r{i}"))
    while te.has_work() and te.decode_steps < 1:
        te.step()
    key0, temps0 = te._sp_cache[0], te._sp_cache[1]
    assert key0 == ("r0", "r1")
    te.step()                          # same batch: the arrays are reused
    assert te._sp_cache[1] is temps0
    te.run_to_completion()             # finishes invalidate via key change
    te.add_request(Request(prompt_tokens=_prompts(1, seed0=9)[0],
                           sampling=sp, req_id="r9"))
    te.run_to_completion()
    assert te._sp_cache[0] == ("r9",)

"""Batched ragged prefill + microkernel runner registry tests (DESIGN.md §12).

The one-dispatch prefill path — flat ragged token stream, per-token
(page, slot, position) indices, one KV scatter per layer across all
sequences, chunk-final logits with first-token sampling fused in — must be
bit-identical to the legacy per-sequence path on greedy decoding, across
ragged prompt mixes, qwen3 + granite (MoE), and TP ∈ {1,2}. Steady-state
serving must cost ONE prefill dispatch per step and ZERO prefill jit
compiles after ``warmup_prefill``. The slot family's riders — pow2-bucketed
masked-tail prefill and fused decode+sample — get the same parity
treatment, and the registry must resolve families from ``ModelConfig``
instead of the engine special-casing runner classes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import EngineConfig, FlowServe, Request, SamplingParams
from repro.engine.runners import (RunnerFamily, families, pick_runner,
                                  register_family, resolve_family)
from repro.engine.runners.base import SequenceState
from repro.models import get_model

needs2 = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")

SP = SamplingParams(temperature=0.0, max_new_tokens=8, stop_on_eos=False)


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


@pytest.fixture(scope="module")
def rwkv():
    bundle = get_model("rwkv6-1.6b", smoke=True)
    params = bundle.init_params(jax.random.PRNGKey(0), jnp.float32)
    return bundle, params


@pytest.fixture(scope="module")
def rgemma():
    bundle = get_model("recurrentgemma-2b", smoke=True)
    params = bundle.init_params(jax.random.PRNGKey(0), jnp.float32)
    return bundle, params


def _prompts(n, length=11, seed0=0):
    return [[1] + [int(x) for x in
                   np.random.RandomState(seed0 + i).randint(3, 200, length)]
            for i in range(n)]

# ragged mix: 1-token prompt (vacuous prefill), tiny, exactly one chunk,
# chunk-boundary+1 (the extension token rides a 1-token final chunk), long
RAGGED = [[7], [5, 6, 9], list(range(3, 11)), list(range(3, 12)),
          [1] + [int(x) for x in np.random.RandomState(3).randint(3, 200, 21)]]


def _serve(model, prompts, sp=SP, tp=1, **kw):
    bundle, params = model
    ecfg = EngineConfig(tp=tp, n_pages=64, page_size=8, max_batch_tokens=32,
                        chunk_size=8, max_decode_batch=4, **kw)
    te = FlowServe(bundle, params, ecfg)
    for i, p in enumerate(prompts):
        te.add_request(Request(prompt_tokens=p, sampling=sp, req_id=f"r{i}"))
    comps = {c.req_id: c.tokens for c in te.run_to_completion()}
    assert len(comps) == len(prompts)
    return [comps[f"r{i}"] for i in range(len(prompts))], te


# ---------------------------------------------------------------------------
# Greedy parity: batched ragged prefill vs the legacy per-sequence path
# ---------------------------------------------------------------------------


def test_batched_parity_qwen3(qwen):
    want, te0 = _serve(qwen, _prompts(4), batched_prefill=False)
    got, te = _serve(qwen, _prompts(4), batched_prefill=True)
    assert got == want
    # the whole point: fewer prefill dispatches for the same tokens
    assert te.prefill_dispatches < te0.prefill_dispatches


def test_batched_parity_ragged_mix(qwen):
    want, _ = _serve(qwen, RAGGED, batched_prefill=False)
    got, _ = _serve(qwen, RAGGED, batched_prefill=True)
    assert got == want


def test_batched_parity_granite(granite):
    want, _ = _serve(granite, RAGGED[:4], batched_prefill=False)
    got, _ = _serve(granite, RAGGED[:4], batched_prefill=True)
    assert got == want


@needs2
def test_batched_parity_qwen3_tp2(qwen):
    want, _ = _serve(qwen, _prompts(3), tp=2, batched_prefill=False)
    got, _ = _serve(qwen, _prompts(3), tp=2, batched_prefill=True)
    assert got == want


@needs2
@pytest.mark.slow
def test_batched_parity_granite_tp2(granite):
    want, _ = _serve(granite, _prompts(3), tp=2, batched_prefill=False)
    got, _ = _serve(granite, _prompts(3), tp=2, batched_prefill=True)
    assert got == want


def test_batched_stochastic_serves_valid_tokens(qwen):
    sp = SamplingParams(temperature=0.9, top_p=0.9, max_new_tokens=6,
                        stop_on_eos=False)
    got, _ = _serve(qwen, _prompts(3), sp=sp, batched_prefill=True)
    bundle, _ = qwen
    for toks in got:
        assert len(toks) == 6
        assert all(0 <= t < bundle.cfg.vocab_size for t in toks)


def test_first_token_sampled_in_dispatch(qwen):
    """A completing prompt leaves its ONE prefill dispatch with the first
    generated token: the engine fetched it through prefill_syncs (never
    the decode-path host_syncs, which §8's tests pin) and the sequence
    satisfies the decode invariant immediately."""
    bundle, params = qwen
    ecfg = EngineConfig(n_pages=64, page_size=8, max_batch_tokens=32,
                        chunk_size=8, max_decode_batch=4, batched_prefill=True)
    te = FlowServe(bundle, params, ecfg)
    te.add_request(Request(prompt_tokens=_prompts(1)[0], sampling=SP,
                           req_id="r0"))
    while not te.scheduler.running:
        te.step()
    seq = te._seqs["r0"]
    assert len(seq.tokens) == seq.n_prompt + 1    # first token appended
    assert seq.n_cached == len(seq.tokens) - 1    # decode invariant holds
    assert te.prefill_syncs >= 1


def test_max_new_tokens_one_finishes_in_prefill(qwen):
    """max_new_tokens=1: the extension row's sampled token IS the whole
    completion — the request finishes without a single decode step."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=1, stop_on_eos=False)
    want, _ = _serve(qwen, _prompts(2), sp=sp, batched_prefill=False)
    got, te = _serve(qwen, _prompts(2), sp=sp, batched_prefill=True)
    assert got == want
    assert te.decode_steps == 0


# ---------------------------------------------------------------------------
# One dispatch vs per-sequence chunks: logits and written KV, per mechanism
# ---------------------------------------------------------------------------

# one step's plan at page size 8: (cached prefix, chunk length, extension
# row). A fresh entry; an entry past the smoke window (16) whose chunk ends
# its prompt, so the last prompt token rides along; a short mid-prompt one.
# 5 + 7 + 2 = 14 tokens in a bucket of 16, 3 entries in a bucket of 4.
PLAN = [(0, 5, False), (16, 6, True), (3, 2, False)]
PS = 8


def _planned_engine(bundle, params):
    """An engine whose pool holds the PLAN's cached prefixes, written by
    the per-sequence path, and the plan's rows ``(pages, start, tokens)``."""
    te = FlowServe(bundle, params, EngineConfig(
        n_pages=32, page_size=PS, max_prefill_seqs=4))
    scratch = te.pool.scratch_page()
    rows = []
    for i, (start, n, ext) in enumerate(PLAN):
        toks = [int(t) for t in np.random.RandomState(10 + i).randint(
            3, 200, start + n + ext)]
        pages = te.pool.alloc(-(-len(toks) // PS))
        if start:
            te.runner.prefill_chunk(SequenceState(
                f"s{i}", toks[:start], start, pages=pages), toks[:start])
        rows.append((pages, start, toks[start:]))
    return te, scratch, rows


@pytest.mark.parametrize("arch", ["qwen3-8b", "gemma2-9b", "mixtral-8x7b"])
def test_ragged_dispatch_matches_per_sequence_chunks(arch):
    """qwen3 (GQA, qk-norm), gemma2 (local/global window, softcap) and
    mixtral (sliding window, MoE): one ragged dispatch of the PLAN, padding
    tokens and a padding entry included, gives each entry the chunk-final
    logits and writes into its pages the KV that a per-sequence
    ``prefill_chunk`` of the same chunk does."""
    bundle = get_model(arch, smoke=True)
    params = bundle.init_params(jax.random.PRNGKey(0), jnp.float32)
    te, scratch, rows = _planned_engine(bundle, params)
    ops = te._pack_ragged([(pg, st, tk, 0.0, 1.0) for pg, st, tk in rows],
                          scratch)
    assert ops[0].shape == (16,) and ops[5].shape == (4, 4)
    got, _, _ = te.runner.prefill_ragged(*ops, jax.random.PRNGKey(0))
    ref, _, ref_rows = _planned_engine(bundle, params)
    for i, (pages, start, toks) in enumerate(ref_rows):
        seq = SequenceState(f"s{i}", toks, start + len(toks), n_cached=start,
                            pages=pages)
        want = ref.runner.prefill_chunk(seq, toks)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        pos = np.arange(start + len(toks))
        page, slot = np.asarray(pages)[pos // PS], pos % PS
        for pool_got, pool_want in ((te.pool.k, ref.pool.k),
                                    (te.pool.v, ref.pool.v)):
            np.testing.assert_allclose(
                np.asarray(pool_got[:, page, slot]),
                np.asarray(pool_want[:, page, slot]), rtol=1e-4, atol=1e-4)


def _avals(jaxpr):
    """Every intermediate's shape in ``jaxpr`` and the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(v.aval.shape)
        for prm in eqn.params.values():
            for sub in (prm if isinstance(prm, (list, tuple)) else (prm,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _avals(sub)


def test_ragged_dispatch_gathers_no_per_token_run(qwen):
    """At the bucket (Tb 128, Pb 8, Sb 4) no intermediate of the traced
    dispatch holds a page run per packed token — (Tb, Pb·ps, Hkv, hd) or
    its unreshaped (Tb, Pb, ps, Hkv, hd) — while the entries' runs side by
    side, (1, Sb·Pb·ps, Hkv, hd), are there: each run is gathered once."""
    bundle, params = qwen
    cfg = bundle.cfg
    te = FlowServe(bundle, params, EngineConfig(n_pages=16, page_size=8))
    tb, pb, sb, ps = 128, 8, 4, 8
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)      # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)    # noqa: E731
    pool = jax.ShapeDtypeStruct(te.pool.k.shape, te.pool.k.dtype)
    fn = te.runner.prefill._ragged_fn(tb, pb, sb)
    jaxpr = jax.make_jaxpr(fn)(
        params, i32(tb), i32(tb), i32(tb), i32(tb), i32(tb), i32(sb, pb),
        i32(sb), f32(sb), f32(sb), jax.random.PRNGKey(0), pool, pool)
    shapes = set(_avals(jaxpr.jaxpr))
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    assert (tb, pb * ps, hkv, hd) not in shapes
    assert (tb, pb, ps, hkv, hd) not in shapes
    assert (1, sb * pb * ps, hkv, hd) in shapes


# ---------------------------------------------------------------------------
# Steady-state regression: 1 prefill dispatch / step, 0 recompiles
# ---------------------------------------------------------------------------


def test_one_prefill_dispatch_per_step(qwen):
    bundle, params = qwen
    ecfg = EngineConfig(n_pages=64, page_size=8, max_batch_tokens=32,
                        chunk_size=8, max_decode_batch=4, max_prefill_seqs=4,
                        batched_prefill=True)
    te = FlowServe(bundle, params, ecfg)
    for i, p in enumerate(RAGGED):
        te.add_request(Request(prompt_tokens=p, sampling=SP, req_id=f"r{i}"))
    while te.has_work():
        d0 = te.prefill_dispatches
        te.step()
        assert te.prefill_dispatches - d0 <= 1   # NEVER more than one


def test_warmup_prefill_precompiles_grid(qwen):
    bundle, params = qwen
    ecfg = EngineConfig(n_pages=64, page_size=8, max_batch_tokens=32,
                        chunk_size=8, max_decode_batch=4, max_prefill_seqs=4,
                        batched_prefill=True)
    te = FlowServe(bundle, params, ecfg)
    n = te.warmup_prefill(max_pages=8)
    # token buckets pow2s(32+4) = {1..64} = 7, page buckets pow2s(8) = 4
    assert n == 7 * 4
    compiles0 = te.prefill_jit_compiles
    for i, p in enumerate(RAGGED):
        te.add_request(Request(prompt_tokens=p, sampling=SP, req_id=f"r{i}"))
    comps = te.run_to_completion()
    assert len(comps) == len(RAGGED)
    assert te.prefill_jit_compiles == compiles0   # serving never compiled


def test_legacy_flag_keeps_per_seq_path(qwen):
    _, te = _serve(qwen, _prompts(3), batched_prefill=False)
    assert te.prefill_syncs == 0          # batched-path counter stays silent
    assert not te.runner.prefill._ragged_fns


# ---------------------------------------------------------------------------
# Slot family riders: bucketed masked-tail prefill + fused decode/sample
# ---------------------------------------------------------------------------


def _serve_slot(model, prompts, bucket, fused, sp=SP):
    bundle, params = model
    ecfg = EngineConfig(n_slots=4, max_len=64, max_batch_tokens=32,
                        chunk_size=8, max_decode_batch=4, fused_decode=fused)
    te = FlowServe(bundle, params, ecfg)
    te.runner.bucket_prefill = bucket
    for i, p in enumerate(prompts):
        te.add_request(Request(prompt_tokens=p, sampling=sp, req_id=f"r{i}"))
    comps = {c.req_id: c.tokens for c in te.run_to_completion()}
    assert len(comps) == len(prompts)
    return [comps[f"r{i}"] for i in range(len(prompts))], te


@pytest.mark.parametrize("model_fx", ["rwkv", "rgemma"])
def test_slot_bucketed_prefill_parity(model_fx, request):
    model = request.getfixturevalue(model_fx)
    want, te0 = _serve_slot(model, RAGGED[:4], bucket=False, fused=False)
    got, te = _serve_slot(model, RAGGED[:4], bucket=True, fused=False)
    assert got == want
    # bucketing shares executables across ragged chunk lengths
    assert te.prefill_jit_compiles < te0.prefill_jit_compiles


@pytest.mark.parametrize("model_fx", ["rwkv", "rgemma"])
def test_slot_fused_sampling_parity(model_fx, request):
    model = request.getfixturevalue(model_fx)
    want, te0 = _serve_slot(model, _prompts(3), bucket=True, fused=False)
    got, te = _serve_slot(model, _prompts(3), bucket=True, fused=True)
    assert got == want
    assert te.sampler_dispatches == 0     # sampling fused into the step
    assert te.host_dispatches < te0.host_dispatches


def test_slot_fused_stochastic_valid(rwkv):
    sp = SamplingParams(temperature=0.8, top_p=0.9, max_new_tokens=5,
                        stop_on_eos=False)
    got, _ = _serve_slot(rwkv, _prompts(2), bucket=True, fused=True, sp=sp)
    bundle, _ = rwkv
    for toks in got:
        assert len(toks) == 5
        assert all(0 <= t < bundle.cfg.vocab_size for t in toks)


# ---------------------------------------------------------------------------
# Runner registry: families resolved from ModelConfig, not engine if-ladders
# ---------------------------------------------------------------------------


def test_registry_resolution(qwen, rwkv):
    assert resolve_family(qwen[0].cfg).name == "paged"
    assert resolve_family(rwkv[0].cfg).name == "slot"
    assert pick_runner(qwen[0].cfg) == "paged"
    assert pick_runner(rwkv[0].cfg) == "slot"
    names = [f.name for f in families()]
    assert names.index("paged") < names.index("slot")   # ordered match


def test_registry_engine_uses_family(qwen, rwkv):
    bundle, params = qwen
    te = FlowServe(bundle, params, EngineConfig(n_pages=16, page_size=8))
    assert te.family.uses_pages and te.pool is not None
    bundle, params = rwkv
    te = FlowServe(bundle, params, EngineConfig(n_slots=2, max_len=32))
    assert not te.family.uses_pages and te.pool is None


def test_registry_custom_family_overrides():
    from repro.engine.runners import SlotRunner
    probe = RunnerFamily(name="probe", runner_cls=SlotRunner,
                         matches=lambda cfg: getattr(cfg, "name", "") == "?",
                         uses_pages=False)
    before = [f.name for f in families()]
    register_family(probe)
    try:
        assert "probe" in [f.name for f in families()]
        # re-registering the same name replaces in place, not duplicates
        register_family(probe)
        assert [f.name for f in families()].count("probe") == 1
    finally:
        import repro.engine.runners.base as B
        B._FAMILIES[:] = [f for f in B._FAMILIES if f.name != "probe"]
    assert [f.name for f in families()] == before

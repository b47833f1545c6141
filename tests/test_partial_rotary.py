"""Partial rotary embedding (``ModelConfig.partial_rotary_factor``).

The rotation against a hand-written numpy one, the full-rotary program
traced as before, and a tiny Nemotron-shaped model served through the paged
TE — ragged prefill, fused decode horizon, a legacy decode step over the
pool they wrote — against the plain reference
``bench/configs/nemotron4_ref.py`` on seeded weights. The same comparison
against a full-rotary reference fails. And a whole run of the benchmark
harness at a tiny size on the CPU, on a Nemotron configuration cut from the
file that ``BENCHMARK.json`` names for ``nemotron4_15b_l4``.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_config
from repro.engine import EngineConfig, FlowServe
from repro.engine.hotloop import DecodeHotState
from repro.engine.runners.base import SequenceState
from repro.models import get_model
from repro.models import layers as L

REPO = Path(__file__).resolve().parents[1]
CONFIGS = REPO / "bench" / "configs"
THETA = 10000.0
# float32 on both sides; the reference sums in another order
LOGIT_TOL = 5e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "nemotron4_ref", CONFIGS / "nemotron4_ref.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _nemotron_file() -> dict:
    """The configuration file ``BENCHMARK.json`` names for Nemotron-4."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry, = [c for c in bench["configs"] if c["name"] == "nemotron4_15b_l4"]
    return json.loads((REPO / entry["file"]).read_text())


def _numpy_rope(x, pos, width):
    """x: (S, H, hd) float64; channel i < width/2 turns with i + width/2."""
    half = width // 2
    inv = THETA ** (-np.arange(half, dtype=np.float64) * 2.0 / width)
    ang = pos[:, None] * inv[None]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:width]
    return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                           x[..., width:]], -1)


@pytest.mark.parametrize("hd", [128, 16])
def test_half_rotary_matches_numpy_and_passes_the_rest_through(hd):
    """Factor 0.5: the first hd/2 channels rotate at frequencies over hd/2
    (64 at head_dim 128, 8 at 16); the others are bit-equal to the input."""
    width = hd // 2
    x = np.random.RandomState(hd).randn(1, 7, 3, hd).astype(np.float32)
    pos = np.array([[0, 1, 2, 5, 17, 100, 300]], np.int32)
    got = np.asarray(L.apply_rope(jnp.asarray(x), jnp.asarray(pos), THETA,
                                  width))
    want = _numpy_rope(x[0].astype(np.float64), pos[0].astype(np.float64),
                       width)
    np.testing.assert_allclose(got[0, ..., :width], want[..., :width],
                               rtol=1e-5, atol=1e-4)
    assert np.array_equal(got[..., width:], x[..., width:])
    # and the rotated half is not left as it was
    assert not np.allclose(got[0, 1:, :, :width], x[0, 1:, :, :width],
                           atol=1e-3)


def test_rotary_width_of_the_registered_models():
    assert get_config("nemotron-4-15b").rotary_dim == 64
    assert get_config("qwen3-8b").rotary_dim == 128
    with pytest.raises(ValueError, match="even"):
        dataclasses.replace(get_config("qwen3-8b"),
                            partial_rotary_factor=0.2).rotary_dim


def _primitives(jaxpr, out=None):
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] = out.get(eqn.primitive.name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, out)
    return out


def _qkv_primitives(factor):
    cfg = dataclasses.replace(smoke_config(get_config("qwen3-8b")),
                              partial_rotary_factor=factor)
    d, hd = cfg.d_model, cfg.head_dim
    p = {"wq": jnp.zeros((d, cfg.n_heads * hd)),
         "wk": jnp.zeros((d, cfg.n_kv_heads * hd)),
         "wv": jnp.zeros((d, cfg.n_kv_heads * hd)),
         "q_norm": jnp.zeros((hd,)), "k_norm": jnp.zeros((hd,))}
    x = jnp.zeros((2, 5, d))
    pos = jnp.zeros((2, 5), jnp.int32)
    jp = jax.make_jaxpr(lambda p, x, pos: L.attn_qkv(
        p, x, cfg.n_heads, cfg.n_kv_heads, hd, pos, cfg.rope_theta,
        cfg.qk_norm, rotary_dim=cfg.rotary_dim))(p, x, pos)
    return _primitives(jp.jaxpr)


def test_full_rotary_traces_no_slice_or_concatenate_beyond_the_rotation():
    """At factor 1.0 q and k each take the two half slices and the one
    concatenation of the rotation, as before partial rotary existed; only a
    partial width adds the split of the head and its rejoining."""
    full = _qkv_primitives(1.0)
    assert full.get("slice", 0) == 4 and full.get("concatenate", 0) == 2
    half = _qkv_primitives(0.5)
    assert half.get("slice", 0) == 8 and half.get("concatenate", 0) == 4
    assert {k: v for k, v in full.items()
            if k not in ("slice", "concatenate")} == \
        {k: v for k, v in half.items() if k not in ("slice", "concatenate")}


# ---------------------------------------------------------------------------
# A tiny Nemotron through the paged TE against the plain reference
# ---------------------------------------------------------------------------

PS = 8
PROMPT = [int(t) for t in np.random.RandomState(5).randint(1, 500, 19)]
STEPS = 6


@pytest.fixture(scope="module")
def tiny():
    """Reference module, its config dict at smoke widths, the program's
    model, and the reference's weights from a seed."""
    ref = _reference()
    pcfg = smoke_config(get_config("nemotron-4-15b"))
    c = _nemotron_file()
    assert c["partial_rotary_factor"] == 0.5
    c.update(hidden_size=pcfg.d_model, intermediate_size=pcfg.d_ff,
             num_attention_heads=pcfg.n_heads,
             num_key_value_heads=pcfg.n_kv_heads, head_dim=pcfg.head_dim,
             num_hidden_layers=pcfg.n_layers, vocab_size=pcfg.vocab_size)
    assert ref.program_mismatches(c, pcfg) == []
    assert ref.rotary_width(c) == pcfg.rotary_dim == 8
    bundle = get_model(pcfg)
    shapes = jax.eval_shape(lambda k: bundle.init_params(k, jnp.float32),
                            jax.random.PRNGKey(0))
    return ref, c, bundle, ref.init_weights(c, shapes, 2**33 + 7)


@pytest.fixture(scope="module")
def served(tiny):
    """One greedy sequence through the paged TE: the ragged prefill's
    chunk-final logits and first token, STEPS tokens of one fused decode
    horizon, then one legacy decode step over the pool both wrote. Returns
    (prefill logits, all tokens, logits of the last step)."""
    _, c, bundle, w = tiny
    te = FlowServe(bundle, w, EngineConfig(n_pages=16, page_size=PS,
                                           max_prefill_seqs=4))
    n = len(PROMPT)
    pages = te.pool.alloc(-(-(n + STEPS + 1) // PS))
    ops = te._pack_ragged([(pages, 0, PROMPT, 0.0, 1.0)],
                          te.pool.scratch_page())
    first_logits, first, _ = te.runner.prefill_ragged(
        *ops, jax.random.PRNGKey(0))
    tokens = PROMPT + [int(first[0])]
    hot = DecodeHotState(te.pool)
    hot.sync([("s", pages, len(tokens), tokens[-1], 0.0, 1.0)])
    block = np.asarray(te.runner.decode_fused(hot, STEPS))   # (STEPS, Bb)
    tokens += [int(t) for t in block[:, 0]]
    seq = SequenceState("s", tokens, n, n_cached=len(tokens) - 1,
                        pages=pages)
    last_logits = te.runner.decode([seq])[0]
    v = c["vocab_size"]
    return (np.asarray(first_logits[0])[:v], tokens,
            np.asarray(last_logits)[:v])


def test_paged_prefill_and_fused_decode_match_the_partial_reference(
        tiny, served):
    ref, c, _, w = tiny
    first, tokens, last = served
    want = np.asarray(ref.logits(c, w, jnp.asarray(tokens)))
    n = len(PROMPT)
    np.testing.assert_allclose(first, want[n - 1], rtol=0, atol=LOGIT_TOL)
    np.testing.assert_allclose(last, want[-1], rtol=0, atol=LOGIT_TOL)
    # every greedy token, from the prefill and from the fused horizon
    assert tokens[n:] == [int(t) for t in want[n - 1:-1].argmax(-1)]


def test_the_same_comparison_fails_against_a_full_rotary_reference(
        tiny, served):
    ref, c, _, w = tiny
    first, tokens, last = served
    full = np.asarray(ref.logits(dict(c, partial_rotary_factor=1.0), w,
                                 jnp.asarray(tokens)))
    assert np.abs(first - full[len(PROMPT) - 1]).max() > 50 * LOGIT_TOL
    assert np.abs(last - full[-1]).max() > 50 * LOGIT_TOL
    # and the reference refuses a program that rotates the whole head
    pcfg = dataclasses.replace(smoke_config(get_config("nemotron-4-15b")),
                               partial_rotary_factor=1.0)
    assert any("partial_rotary_factor" in m
               for m in ref.program_mismatches(c, pcfg))


# ---------------------------------------------------------------------------
# The benchmark harness on a tiny Nemotron cut from the benchmark's file

def _bench_conftest():
    spec = importlib.util.spec_from_file_location(
        "bench_tests_conftest", REPO / "tests" / "bench" / "conftest.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def _restore_jax_config():
    """A harness run sets the configuration's matmul precision and points
    JAX's persistent cache into its root; put them back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_default_matmul_precision")}
    yield
    for k, v in keep.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_harness_run_of_a_tiny_partial_rotary_nemotron_is_correct(
        tmp_path, monkeypatch, capsys, _restore_jax_config):
    """``tinyn.rollout`` of the benchmark's tests, its configuration taken
    from the benchmark's Nemotron file at the same tiny widths: the served
    logits meet the cell's limit against ``nemotron4_ref``."""
    root = _bench_conftest().make_tiny_root(tmp_path / "root")
    tiny_path = root / "bench" / "configs" / "tinyn.json"
    tiny_cfg = json.loads(tiny_path.read_text())
    n = _nemotron_file()
    for k in ("name", "hidden_size", "intermediate_size",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "num_hidden_layers", "vocab_size", "program"):
        n[k] = tiny_cfg[k]
    assert n["reference"] == "nemotron4_ref"
    tiny_path.write_text(json.dumps(n))
    import run
    monkeypatch.setattr(run, "require_devices", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    rc = run.main(["--workload", "tinyn.rollout", "--seed", str(2**33 + 5),
                   "--seconds", "2", "--trace", "0"], root=root)
    out, err = capsys.readouterr()
    assert rc == 0, err[-3000:]
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["checks"]["logit_gap"]["value"] <= 1e-3

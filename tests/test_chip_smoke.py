"""chip_smoke.py's phases at smoke size on the CPU: the one-chip plane on
one device, the four-chip tensor-parallel PD path on four of conftest's
eight host devices, and ``main`` refusing a backend that is not a TPU.

The phases place TEs from ``jax.devices()`` / ``jax.device_count()``; each
test narrows what those report to the devices its phase is meant for."""
import importlib.util
import os
from pathlib import Path

import jax
import pytest

from repro.engine import EngineConfig
from repro.launch import compile_cache
from repro.launch.serve import build_model

_PATH = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                     "chip_smoke.py")
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

MAX_NEW = 6


@pytest.fixture(scope="module")
def smoke_model():
    bundle, params = build_model("qwen3-8b")
    prompts = chip_smoke.make_prompts(bundle.cfg.vocab_size, 4, (9, 40), 0)
    return bundle, params, prompts


def _ecfg():
    return EngineConfig(page_size=8, n_pages=96, max_batch_tokens=32,
                        chunk_size=16, max_decode_batch=4, decode_horizon=4)


def _visible(monkeypatch, n):
    devs = jax.devices()[:n]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(devs))
    monkeypatch.setattr(jax, "device_count", lambda *a, **k: len(devs))


def test_one_chip_phase_on_one_device(smoke_model, monkeypatch):
    bundle, params, prompts = smoke_model
    _visible(monkeypatch, 1)
    warm = [[(t + 1) % bundle.cfg.vocab_size for t in p] for p in prompts]
    rep = chip_smoke.one_chip_phase(bundle, params, prompts, warm, MAX_NEW,
                                    _ecfg(), log=lambda *_: None)
    assert rep["n_requests"] == 4 and rep["pd_handoffs"] >= 1
    assert rep["tokens"] == 4 * MAX_NEW
    assert rep["window_fallbacks"] == 2        # three TEs share device 0
    assert rep["failures"] == []
    # float32 on the CPU on both sides: far inside the chip's bound
    assert rep["logit_delta_engine"] < 1e-4
    assert rep["logit_argmax_match"] == 1
    # the warm pass compiled every bucket the measured pass used
    for name, c in rep["counters"].items():
        w = rep["warm_counters"][name]
        assert c["prefill_compiles"] == w["prefill_compiles"], name
        assert c["decode_compiles"] == w["decode_compiles"], name


def test_four_chip_phase_disjoint_windows(smoke_model, monkeypatch):
    bundle, params, prompts = smoke_model
    _visible(monkeypatch, 4)
    rep = chip_smoke.four_chip_phase(bundle, params, prompts, MAX_NEW,
                                     _ecfg(), log=lambda *_: None)
    assert rep["failures"] == []
    assert rep["devices"] == {"te-pd0-p": [0, 1], "te-pd0-d": [2, 3]}
    assert rep["kv_bytes_moved"] > 0
    assert max(rep["logit_delta_vs_tp1"].values()) < 1e-4
    assert rep["greedy_tokens_equal"] == rep["greedy_tokens_total"] \
        == 4 * MAX_NEW


def test_main_refuses_a_non_tpu_backend(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_compile_cache_location(monkeypatch, tmp_path):
    """The environment's directory wins and nothing else is set; without it
    the cache is the checkout's fixed ``.jax_cache``."""
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.CHECKOUT_CACHE) \
            == jax.config.jax_compilation_cache_dir
        assert compile_cache.CHECKOUT_CACHE.parent == \
            Path(_PATH).resolve().parent
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

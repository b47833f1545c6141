"""Whole runs of the harness at a tiny size on the CPU, with its look for
a chip skipped: the result line, the check that decides ``correct`` with a
token altered where the program produces it, and the control (the
reference in bfloat16) failing the limit. And the refusal off a TPU."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import run
from spec import Spec

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(autouse=True)
def _restore_jax_cache_config():
    """A run points JAX's persistent cache into its checkout; put the
    process's settings back for the tests that follow in this worker."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield
    for k, v in keep.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _run(root, workload, monkeypatch, capsys, seed=2**33 + 5):
    monkeypatch.setattr(run, "require_devices", lambda chips: dict(CPU))
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "2", "--trace", "0"], root=root)
    out, err = capsys.readouterr()
    assert rc == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1]), err


def test_refuses_without_a_tpu(capsys):
    """On the CPU the run exits non-zero and prints no result."""
    rc = run.main(["--workload", "qwen3_8b_l4.chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 2 and out == "" and "no TPU" in err


@pytest.mark.parametrize("workload", ["tiny.chat", "tiny.rollout",
                                      "tinyn.rollout"])
def test_tiny_run_is_correct_and_prints_the_contract_line(
        tiny_root, workload, monkeypatch, capsys):
    res, err = _run(tiny_root, workload, monkeypatch, capsys)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    want = {m["name"] for m in Spec(tiny_root).metrics(workload, False)}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["checks"]["logit_gap"]["value"] <= 1e-3
    # set-up warmed every program the window runs
    assert "programs compiled or loaded inside the window: []" in err
    # the compared numbers are also the last lines of standard error
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert [t.split()[1].rstrip(":") for t in tail] == list(res["checks"])


def test_a_token_altered_where_produced_makes_the_run_incorrect(
        tiny_root, monkeypatch, capsys):
    """The timed path broken underneath: every greedy token the program
    samples (in the prefill dispatch and in the fused decode horizon) is
    replaced by its neighbour in the vocabulary."""
    from repro.engine import sampling
    greedy = sampling.greedy_core

    def altered(logits, vocab_size):
        return (greedy(logits, vocab_size) + 1) % vocab_size

    monkeypatch.setattr(sampling, "greedy_core", altered)
    res, _ = _run(tiny_root, "tiny.chat", monkeypatch, capsys)
    assert res["correct"] is False
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_in_lower_precision_fails_the_limit_the_program_meets(
        tiny_root):
    """The reference in a lower precision put in the program's place: at
    each position of greedy float32 continuations it picks tokens whose
    float32 logit lies below the best by more than the cell's limit, where
    the float32 tokens themselves lie at 0. (The CPU computes float32
    matmuls in full at every precision setting, so here the control is the
    bfloat16 step; on the chip it is the configuration's own.)"""
    spec = Spec(tiny_root)
    c = dict(spec.config("tiny"), vocab_size=4096, hidden_size=128,
             control={"dtype": "bfloat16", "precision": "default"})
    ref = spec.reference(c)
    shapes = {
        "embed": jax.ShapeDtypeStruct((4096, 128), jnp.float32),
        "lm_head": jax.ShapeDtypeStruct((128, 4096), jnp.float32),
        "final_norm": {"scale": jax.ShapeDtypeStruct((128,), jnp.float32)},
        "blocks": {
            "ln1": {"scale": jax.ShapeDtypeStruct((2, 128), jnp.float32)},
            "ln2": {"scale": jax.ShapeDtypeStruct((2, 128), jnp.float32)},
            "attn": {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in {
                "wq": (2, 128, 64), "wk": (2, 128, 32), "wv": (2, 128, 32),
                "wo": (2, 64, 128), "q_norm": (2, 16),
                "k_norm": (2, 16)}.items()},
            "mlp": {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in {
                "w_gate": (2, 128, 128), "w_up": (2, 128, 128),
                "w_down": (2, 128, 128)}.items()}}}
    w = ref.init_weights(c, shapes, seed=3)
    stats = jax.jit(lambda t: ref.logit_stats(
        c, w, t, jnp.zeros((1, 256), jnp.int32)))
    rng = np.random.default_rng(0)
    reqs = []
    for _ in range(3):
        # greedy float32 decoding of 100 tokens after a 32-token prompt
        toks = np.zeros((256,), np.int32)
        toks[:32] = rng.integers(0, 4096, 32)
        for j in range(100):
            toks[32 + j] = int(np.asarray(stats(jnp.asarray(toks))[2])[31 + j])
        reqs.append({"prompt": toks[:32].tolist(),
                     "tokens": toks[32:132].tolist()})
    limit = spec.cell("tiny.chat")["check"]["logit_gap"]
    gaps = check.Reference(ref, c).gaps(w, reqs, 256, control=True)
    assert gaps["positions"] == 300
    assert gaps["served"] <= limit < gaps["control"]

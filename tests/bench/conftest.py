"""Fixtures of the benchmark's tests: the harness's modules on the path,
and a tiny benchmark root (CPU-sized model, mixes and cells) built from
the real files with their sizes cut, so a whole run fits in a test."""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
for _p in (BENCH, REPO / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

TINY_ENGINE = {"page_size": 16, "n_pages": 48, "max_batch_tokens": 12,
               "chunk_size": 12, "max_prefill_seqs": 4,
               "max_decode_batch": 2, "decode_horizon": 2,
               "enable_prefix_cache": False}


def make_tiny_root(root: Path) -> Path:
    """A benchmark root with cells ``tiny.chat`` and ``tiny.rollout`` on a
    two-layer qwen3-shaped model (d_model 64, vocabulary 512) and
    ``tinyn.rollout`` on a two-layer nemotron-shaped one (LayerNorm,
    squared ReLU, 6:1 GQA)."""
    (root / "bench").mkdir(parents=True)
    for name in ("configs", "metrics"):
        shutil.copytree(BENCH / name, root / "bench" / name)
    for name in ("peaks.json", "programs.json"):
        shutil.copy(BENCH / name, root / "bench" / name)
    (root / "src").symlink_to(REPO / "src")
    c = json.loads((BENCH / "configs" / "qwen3_8b_l4.json").read_text())
    c.update(name="tiny", hidden_size=64, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             num_hidden_layers=2, vocab_size=512)
    c["program"] = {"arch": "qwen3-8b", "replace": {
        "n_layers": 2, "d_model": 64, "d_ff": 128, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "vocab_size": 512}}
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(c))
    n = json.loads((BENCH / "configs" / "nemotron4_15b_l4.json").read_text())
    n.update(name="tinyn", hidden_size=96, intermediate_size=384,
             num_attention_heads=6, num_key_value_heads=1, head_dim=16,
             num_hidden_layers=2, vocab_size=500)
    n["program"] = {"arch": "nemotron-4-15b", "replace": {
        "n_layers": 2, "d_model": 96, "d_ff": 384, "n_heads": 6,
        "n_kv_heads": 1, "head_dim": 16, "vocab_size": 500}}
    (root / "bench" / "configs" / "tinyn.json").write_text(json.dumps(n))
    (root / "bench" / "traffic").mkdir()
    chat = json.loads((BENCH / "traffic" / "chat.json").read_text())
    chat["prompt_tokens"].update(median=20, min=8, max=40)
    chat["output_tokens"].update(median=6, min=2, max=12)
    chat["lead_seconds"] = 1
    ro = json.loads((BENCH / "traffic" / "rollout.json").read_text())
    ro.update(concurrency=2, fill_completions=1)
    ro["prompt_tokens"].update(min=8, max=24)
    ro["output_tokens"].update(min=8, max=16)
    for name, mix in (("chat", chat), ("rollout", ro)):
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    (root / "bench" / "cells").mkdir()
    check = {"logit_gap": 1e-3, "sample_min_tokens": 20,
             "sample_max_requests": 4}
    for name, extra in (("tiny.chat", {"rate_per_s": 3.0,
                                       "ttft_limit_s": 30}),
                        ("tiny.rollout", {}), ("tinyn.rollout", {})):
        cell = {"topology": "colo=1", "policy": "dist_sched",
                "engine": TINY_ENGINE, "check": check, **extra}
        (root / "bench" / "cells" / f"{name}.json").write_text(
            json.dumps(cell))
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    b["configs"] = [{"name": n, "source": "test",
                     "file": f"bench/configs/{n}.json", "reduced": [],
                     "why": "test"} for n in ("tiny", "tinyn")]
    b["workloads"] = [{"name": f"{c}.{t}", "config": c, "traffic": t,
                       "chips": 1, "why": "test"}
                      for c, t in (("tiny", "chat"), ("tiny", "rollout"),
                                   ("tinyn", "rollout"))]
    rollouts = ["tiny.rollout", "tinyn.rollout"]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.chat"]
            if m.get("moves", m["name"]) != "ttft_p90_ms":
                m["workloads"] += rollouts
    # the closed loop's own end-to-end metric, and the idle share split by
    # the metric it moves there (read by the shared reader)
    b["end_to_end"].insert(-1, {
        "name": "output_tokens_per_s", "unit": "tokens/s",
        "better": "higher", "bound": 0.25, "source": "host_clock",
        "workloads": rollouts})
    b["per_layer"].append({
        "name": "device_idle_share.rollout", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device",
        "moves": "output_tokens_per_s", "workloads": rollouts})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench") / "root")

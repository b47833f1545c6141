"""The benchmark finds configurations, mixes, cells and metrics by name
from files alone, and ``BENCHMARK.json`` keeps to its format."""
import json
import re
import shutil

import pytest

from conftest import BENCH, REPO
from spec import Spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_new_config_mix_cell_and_metric_are_found_without_edits(tmp_path):
    """Add a configuration, a traffic mix, a cell and a per-layer metric as
    new files plus entries in BENCHMARK.json: the harness finds each by
    name, and no existing file changes."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "bench")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((BENCH / "configs" / "qwen3_8b_l4.json").read_text())
    cfg.update(name="qwen3_8b_l2", num_hidden_layers=2)
    (root / "bench" / "configs" / "qwen3_8b_l2.json").write_text(
        json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix["prompt_tokens"].update(min=2048, max=4096, median=3000)
    (root / "bench" / "traffic" / "longprompt.json").write_text(
        json.dumps(mix))
    (root / "bench" / "cells" / "qwen3_8b_l2.longprompt.json").write_text(
        json.dumps({"rate_per_s": 1.0}))
    (root / "bench" / "metrics" / "queue_wait_ms.py").write_text(
        "def read(ctx):\n    return ctx.get('queue_wait_ms')\n")
    bench["configs"].append({"name": "qwen3_8b_l2", "source": "x",
                             "file": "bench/configs/qwen3_8b_l2.json",
                             "reduced": ["num_hidden_layers"], "why": "x"})
    bench["workloads"].append({"name": "qwen3_8b_l2.longprompt",
                               "config": "qwen3_8b_l2",
                               "traffic": "longprompt", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "queue_wait_ms", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "JE", "moves": "ttft_p90_ms",
                               "workloads": ["qwen3_8b_l2.longprompt"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = Spec(root)
    wl = spec.workload("qwen3_8b_l2.longprompt")
    assert spec.config(wl["config"])["num_hidden_layers"] == 2
    assert spec.traffic(wl["traffic"])["prompt_tokens"]["max"] == 4096
    assert spec.cell(wl["name"]) == {"rate_per_s": 1.0}
    assert spec.reference(spec.config(wl["config"])).logit_stats
    names = [m["name"] for m in spec.metrics(wl["name"], traced=True)]
    assert names == ["queue_wait_ms"]
    assert spec.reader("queue_wait_ms").read({"queue_wait_ms": 3.5}) == 3.5
    assert spec.reader("queue_wait_ms").read({}) is None
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_metrics_per_cell_follow_their_workloads_lists():
    spec = Spec(REPO)
    e2e = {w["name"]: [m["name"] for m in spec.metrics(w["name"], False)]
           for w in spec.bench["workloads"]}
    assert e2e["qwen3_8b_l4.chat"] == ["ttft_p90_ms", "tpot_p90_ms",
                                       "setup_s"]
    for w in spec.bench["workloads"]:
        mine = {m["name"] for m in spec.metrics(w["name"], False)}
        for m in spec.metrics(w["name"], True):
            assert m["moves"] in mine, (w["name"], m["name"])
            spec.reader(m["name"])


def test_benchmark_json_keeps_to_its_format():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    assert all((REPO / p).is_dir() for p in b["paths"])
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        f = json.loads((REPO / c["file"]).read_text())
        assert f["name"] == c["name"] and f["reduced"] == c["reduced"]
        for k, v in f["published"].items():
            assert k in c["reduced"] and f[k] != v
    cells = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert len(w["why"]) <= 200 and w["name"] not in cells
        cells.add(w["name"])
        assert (BENCH / "cells" / f"{w['name']}.json").is_file()
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    metrics = set()
    for kind in ("end_to_end", "per_layer"):
        for m in b[kind]:
            assert NAME.match(m["name"]) and m["name"] not in metrics
            metrics.add(m["name"])
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
            assert set(m.get("workloads", [])) <= cells
            if kind == "end_to_end":
                assert m["source"] in ("host_clock", "device_trace")
                assert 0.01 <= m["bound"] <= 0.25
            else:
                assert Spec(REPO).reader(m["name"]).read
                if m["name"].endswith("_roofline") or "mfu" in m["name"]:
                    assert m["unit"] == "%"
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])


def test_a_split_metric_is_read_by_its_base_reader_unless_it_has_its_own(
        tmp_path):
    """``<m>.<part>`` (one quantity, split by the end-to-end metric it
    moves) falls back to ``<m>.py``; a file of its own takes precedence."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "bench")
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    spec = Spec(root)
    ctx = {"trace": {"busy_s": 3.0, "window_s": 4.0}}
    base = spec.reader("device_idle_share").read(ctx)
    assert base == pytest.approx(25.0)
    assert spec.reader("device_idle_share.rollout").read(ctx) == base
    (root / "bench" / "metrics" / "device_idle_share.rollout.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    assert spec.reader("device_idle_share.rollout").read(ctx) == 7.0
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric.chat")


@pytest.mark.parametrize("name", ["qwen3_8b_l4", "nemotron4_15b_l4"])
def test_config_builds_through_the_paged_family(name):
    """Each configuration file (also one that no cell uses yet) is the
    registered architecture with only the file's cuts applied, runs through
    the normal ``paged`` runner family, and matches its file width for
    width."""
    import dataclasses

    from repro.configs.base import get_config
    from repro.engine.runners import resolve_family
    spec = Spec(REPO)
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    base = get_config(c["program"]["arch"])
    pcfg = dataclasses.replace(base, **c["program"]["replace"])
    assert spec.reference(c).program_mismatches(c, pcfg) == []
    assert resolve_family(pcfg).name == "paged"
    for key in c["reduced"]:
        assert c["published"][key] != c[key]
    changed = {f for f in c["program"]["replace"]
               if getattr(base, f) != getattr(pcfg, f)}
    assert changed <= {"n_layers", "vocab_size"}

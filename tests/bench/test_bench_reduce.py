"""The benchmark's arithmetic on hand-built inputs: the trace reduction,
percentiles with missing samples, the budget signal over a window, the
FLOP and byte functions, and the peaks table."""
import json
import math
import re

import pytest

import costs
import stats
import reduce_trace as trace
from conftest import BENCH, REPO


def test_union_and_busy_merge_overlapping_ops():
    ops = [("a", 0.0, 1.0), ("b", 0.5, 1.5), ("c", 2.0, 2.5),
           ("d", 2.5, 3.0), ("e", 4.0, 4.25)]
    assert trace.union(ops) == [(0.0, 1.5), (2.0, 3.0), (4.0, 4.25)]
    assert trace.busy_seconds(ops) == pytest.approx(2.75)
    # the same ops on two devices: the busy time is their average
    two = ops + [(n, s + 10, e + 10) for n, s, e in ops]
    assert trace.busy_seconds(two, n_devices=2) == pytest.approx(2.75)


def test_program_seconds_by_name_pattern():
    mods = [("jit_run(7)", 0.0, 0.25), ("jit_run", 1.0, 1.5),
            ("jit_horizon(3)", 2.0, 2.125), ("jit_runner", 3.0, 4.0),
            ("jit_other", 5.0, 6.0)]
    got = trace.program_seconds(mods, {"ragged_prefill": r"jit_run\b",
                                       "fused_decode": r"jit_horizon\b"})
    assert got == {"ragged_prefill": pytest.approx(0.75),
                   "fused_decode": pytest.approx(0.125)}


def test_idle_gaps_named_by_innermost_covering_host_span():
    ops = [("x", 1.0, 2.0), ("y", 5.0, 6.0)]
    host = [("bench.je_step", 0.0, 10.0), ("bench.wait", 2.0, 4.5),
            ("bench.submit", 8.0, 8.5)]
    gaps = trace.idle_gaps(ops, host, 0.0, 9.0, k=3)
    assert gaps == [["bench.wait", pytest.approx(3.0)],
                    ["bench.je_step", pytest.approx(3.0)],
                    ["bench.je_step", pytest.approx(1.0)]]
    assert trace.idle_gaps(ops, [], 0.0, 6.0) == [
        ["untraced", pytest.approx(3.0)], ["untraced", pytest.approx(1.0)]]


def test_ops_are_labelled_by_their_enclosing_program():
    mods = [("jit_horizon(123)", 0.0, 2.0), ("jit_run(9)", 3.0, 4.0)]
    ops = [("%fusion.1 = f32[8] fusion(x)", 0.5, 1.0),
           ("%while.2 = (s32[]) while(y)", 3.1, 3.5), ("%copy.3", 2.5, 2.6)]
    assert [n for n, _, _ in trace.label_ops(ops, mods)] == [
        "jit_horizon/%fusion.1", "jit_run/%while.2", "?/%copy.3"]


def test_an_op_inside_a_program_that_encloses_a_shorter_one_keeps_its_name():
    mods = [("jit_horizon(1)", 0.0, 4.0), ("jit_scatter(2)", 1.0, 1.1)]
    ops = [("%while.10", 2.0, 3.0)]
    assert trace.label_ops(ops, mods)[0][0] == "jit_horizon/%while.10"


def test_clip_keeps_the_parts_inside_the_window():
    ivs = [("a", 0.0, 1.0), ("b", 0.5, 2.5), ("c", 1.5, 1.75),
           ("d", 2.75, 4.0), ("e", 3.0, 3.5)]
    assert trace.clip(ivs, 1.0, 3.0) == [("b", 1.0, 2.5), ("c", 1.5, 1.75),
                                         ("d", 2.75, 3.0)]
    # a program run cut by the window's edge counts only its inside part
    got = trace.program_seconds(trace.clip(
        [("jit_horizon(1)", 0.5, 1.5), ("jit_horizon(1)", 2.5, 3.5)],
        1.0, 3.0), {"fused_decode": r"jit_horizon\b"})
    assert got["fused_decode"] == pytest.approx(1.0)


def test_top_ops_sums_by_name():
    ops = [("f", 0, 1), ("g", 1, 3), ("f", 3, 5), ("h", 5, 5.5)]
    assert trace.top_ops(ops, k=2) == [["f", 3], ["g", 2]]


@pytest.mark.parametrize("values,missing,q,want", [
    ([5, 1, 4, 2, 3, 6, 7, 8, 9, 10], 0, 90, 9),
    ([5, 1, 4, 2, 3, 6, 7, 8, 9], 1, 90, 9),
    ([5, 1, 4, 2, 3, 6, 7, 8], 2, 90, math.inf),
    ([3.0], 0, 50, 3.0),
    ([], 0, 90, None),
])
def test_percentile_counts_missing_beyond_every_value(values, missing, q,
                                                      want):
    assert stats.percentile(values, q, missing) == want


def test_spread_is_interquartile_share_of_median():
    v = [10.0, 11.0, 9.0, 10.5, 9.5, 10.0]
    q1, q2, q3 = __import__("statistics").quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / q2)


class _TE:
    def __init__(self, owed_new, queued):
        self.owed_new, self.queued = owed_new, queued
        self.step_wall, self.decode_steps = 0.0, 0
        self.jit_compiles = self.prefill_jit_compiles = 0

    def load_metrics(self):
        return {"inflight_decode_tokens": self.owed_new,
                "queued_prefill_tokens": self.queued}


class _Drv:
    def __init__(self, te):
        self.je = type("JE", (), {"engines": [te]})()
        self.je_steps, self.je_wall = 0, 0.0
        self.owed_new = self.owed_prompt = 0
        self.built = []
        self.decode_rows = self.decode_ctx = 0
        self.prefill_tokens = self.prefill_pos = 0


class _Seq:
    def __init__(self, n_tokens, n_cached=0):
        self.tokens, self.n_cached = [0] * n_tokens, n_cached


class _Sched:
    def __init__(self, running, queued, max_batch):
        self.running, self._queued = running, queued
        self.cfg = type("Cfg", (), {"max_decode_batch": max_batch})()

    def queued_seqs(self):
        return list(self._queued)


def test_live_work_is_weighted_by_the_decode_steps_each_step_ran():
    """Decode rows and their context come from the batch as it stood before
    each step, times the decode steps the step ran; prefill positions from
    each queued prompt's cached length before and after."""
    import run
    te = _TE(0, 0)
    q = _Seq(100, n_cached=10)
    te.scheduler = _Sched([_Seq(50), _Seq(70), _Seq(9)], [q], max_batch=2)
    drv = _Drv(te)
    live = run.Driver._live(drv)
    te.decode_steps = 4        # a fused horizon of 4
    q.n_cached = 14            # 4 prompt tokens at positions 10..13
    run.Driver._count_live(drv, live)
    assert drv.decode_rows == 4 * 2                 # the plan's slice
    assert drv.decode_ctx == 4 * (50 + 70)
    assert drv.prefill_tokens == 4
    assert drv.prefill_pos == 10 + 11 + 12 + 13


@pytest.mark.parametrize("fixed", [False, True])
def test_seeds_share_the_work_and_a_fixed_order_shares_its_timing(fixed):
    """Two seeds get the same multiset of lengths, budgets, sampling kinds
    and gaps; with the mix's ``order_seed`` also in the same order at the
    same due times, and only their token ids differ."""
    import traffic
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    if not fixed:
        mix.pop("order_seed")
    a, b = (traffic.open_loop(mix, 0.4, s, 51, 90, 1000)
            for s in (2**33 + 1, 2**40 + 3))
    shape = [lambda r: len(r.prompt), lambda r: r.max_new,
             lambda r: r.greedy]
    for f in shape:
        assert sorted(map(f, a)) == sorted(map(f, b))
    gaps = [traffic._gaps(mix, 20, 0.4, s, 3) for s in (2**33 + 1, 2**40 + 3)]
    assert sorted(gaps[0]) == pytest.approx(sorted(gaps[1]))
    shape.append(lambda r: round(r.due, 9))
    same_order = all(f(x) == f(y) for f in shape for x, y in zip(a, b))
    assert same_order == fixed
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert a[0].prompt == traffic.open_loop(mix, 0.4, 2**33 + 1, 51, 90,
                                            1000)[0].prompt


def test_tokens_in_window_from_the_budget_signal():
    """Tokens produced between two moments are the budgets submitted less
    what resident requests still owe, including requests that finished or
    joined in between."""
    import run
    te = _TE(0, 0)
    drv = _Drv(te)
    drv.owed_new, drv.owed_prompt = 300, 90       # three requests of 100
    te.owed_new, te.queued = 240, 30              # 60 produced, 60 prefilled
    c0 = run.Counters(drv)
    drv.owed_new, drv.owed_prompt = 400, 120      # a fourth joins
    te.owed_new, te.queued = 150, 0               # one finished meanwhile
    c1 = run.Counters(drv)
    d = c0.delta(c1)
    assert d["produced"] == (400 - 150) - (300 - 240) == 190
    assert d["prefilled"] == 120 - 90 + 30 == 60


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["qwen3_8b_l4", "nemotron4_15b_l4"])
def test_param_count_matches_the_program_at_smoke_size(name):
    """The cost functions' parameter count equals the matrix leaves of the
    program's own model, at smoke widths of the same configuration."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config, smoke_config
    from repro.models import get_model
    c = _config(name)
    pcfg = dataclasses.replace(get_config(c["program"]["arch"]),
                               **c["program"]["replace"])
    small = smoke_config(pcfg)
    cs = dict(c, hidden_size=small.d_model, intermediate_size=small.d_ff,
              num_attention_heads=small.n_heads,
              num_key_value_heads=small.n_kv_heads, head_dim=small.head_dim,
              num_hidden_layers=small.n_layers, vocab_size=small.vocab_size)
    shapes = jax.eval_shape(
        lambda k: get_model(small).init_params(k, jnp.float32),
        jax.random.PRNGKey(0))
    matrices = ("embed", "lm_head", "wq", "wk", "wv", "wo", "w_up",
                "w_down", "w_gate")
    n = sum(math.prod(s.shape)
            for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0]
            if p[-1].key in matrices)
    pad_rows = 2 * (small.padded_vocab - small.vocab_size) * small.d_model
    assert costs.param_count(cs) + pad_rows == n == small.param_count()


@pytest.mark.parametrize("name,layer,kv", [
    ("qwen3_8b_l4", 4096 * 6144 + 4096 * 4096 + 3 * 4096 * 12288, 32768),
    ("nemotron4_15b_l4", 6144 * 8192 + 6144 * 6144 + 2 * 6144 * 24576,
     32768),
])
def test_costs_at_published_widths(name, layer, kv):
    c = _config(name)
    assert costs.layer_params(c) == layer
    assert costs.kv_bytes_per_token(c) == kv
    w = (4 * layer + c["hidden_size"] * c["vocab_size"]) * 4
    assert costs.decode_step_bytes(c, 8, 1000) == w + 8 * 1000 * kv
    f = costs.token_flops(c, 100, True)
    assert f == 2 * 4 * layer + 2 * c["hidden_size"] * c["vocab_size"] \
        + 4 * 4 * c["num_attention_heads"] * 128 * 100
    assert costs.token_flops(c, 0, False) == 2 * 4 * layer


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    from spec import Spec
    spec = Spec(REPO)
    assert "TPU v5e" in spec.json_file("peaks.json")["source"]
    assert spec.peaks("TPU v5 lite") == {"bf16_flops_per_s": 197e12,
                                         "hbm_bytes_per_s": 819e9,
                                         "hbm_bytes": 16e9}
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v9 imaginary")


def test_program_name_patterns_compile():
    pats = json.loads((BENCH / "programs.json").read_text())
    for k, v in pats.items():
        if k != "note":
            re.compile(v)
    assert REPO.joinpath("BENCHMARK.json").is_file()

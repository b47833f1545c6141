"""The program's own measurement as the benchmark reads it
(``bench/program_view.py``, ``bench/trace_program.py`` and the readers of
its inputs): a serving plane's spans on a real CPU profiler trace, the
arithmetic on hand-built spans, ops and contexts, and a tiny traced run
whose in-program counters are set against the harness's outside figures."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import program_view as pv
import reduce_trace as trace_red
import run
import trace_program
from spec import Spec
from test_bench_run import CPU, _restore_jax_cache_config  # noqa: F401

PROGRAM_METRICS = trace_program.PROGRAM_METRICS


# ------------------------------------------------------------ real trace

@pytest.fixture(scope="module")
def traced_plane(tmp_path_factory):
    """A JE over a PD pair and a colocated TE (round robin, so both serve)
    stepped to completion under the profiler."""
    from repro.core.serving_plane import ServingJobEngine, TopologySpec
    from repro.engine import EngineConfig, SamplingParams
    from repro.models import get_model
    bundle = get_model("qwen3-8b", smoke=True)
    params = bundle.init_params(jax.random.PRNGKey(0), jnp.float32)
    je = ServingJobEngine(
        bundle, params, TopologySpec.parse("pd=1,colo=1"),
        heatmap=np.ones((2, 2)), prefill_lens=[16, 64],
        decode_ratios=[0.25, 1.0], policy="round_robin",
        ecfg=EngineConfig(n_pages=64, page_size=8, max_batch_tokens=32,
                          chunk_size=8, max_decode_batch=4))
    sp = SamplingParams(temperature=0.0, max_new_tokens=6, stop_on_eos=False)
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        for i in range(4):
            je.submit([1] + [int(x) for x in np.random.RandomState(i)
                             .randint(3, 200, 14)], sampling=sp)
        comps = je.run_to_completion()
    yield pv.load(str(d)), comps, je
    je.close()


def _inside(iv, outer):
    return any(s <= iv[1] and iv[2] <= e for _, s, e in outer)


def test_the_trace_holds_every_span_the_program_lists(traced_plane):
    from repro.engine.trace import SPANS
    prog, comps, _ = traced_plane
    assert len(comps) == 4
    assert {n for n, _, _ in prog["spans"]} == set(SPANS)


def test_te_spans_nest_in_te_step_and_te_step_in_je_step(traced_plane):
    spans = traced_plane[0]["spans"]
    by = {n: [iv for iv in spans if iv[0] == n] for n, _, _ in spans}
    for name in ("te.plan", "te.prefill", "te.prefill.fetch",
                 "te.decode.sync", "te.decode.dispatch", "te.decode.fetch"):
        assert all(_inside(iv, by["te.step"]) for iv in by[name]), name
    assert all(_inside(iv, by["te.prefill"]) for iv in by["te.prefill.fetch"])
    for name in ("te.step", "distflow.transfer"):
        assert all(_inside(iv, by["je.step"]) for iv in by[name]), name


def test_timelines_hold_through_the_pd_handoff(traced_plane):
    """Every request's first prefill dispatch lies after its arrival and
    before its first token, also where the KV moved to the decode TE."""
    _, comps, je = traced_plane
    assert sum(len(te.distflow.log) for te in je.engines) > 0
    for c in comps:
        a, fd, ft = pv.timeline(c)
        assert a < fd <= ft <= c.finish


# ------------------------------------------------------------ hand-built

def test_scope_is_the_innermost_known_path_component():
    assert pv.scope_of("jit(horizon)/jit(main)/while/body/attention/"
                       "kv_gather/gather") == "kv_gather"
    assert pv.scope_of("jit(run)/jit(main)/mlp/dot_general") == "mlp"
    assert pv.scope_of("jit(run)/jit(main)/add") is None


def test_device_time_by_program_and_scope():
    mods = [("jit_run(1)", 0.0, 4.0), ("jit_horizon(2)", 5.0, 7.0)]
    ops = [("jit(run)/attention/kv_gather/gather", 0.0, 2.0),
           ("jit(run)/mlp/dot_general", 2.0, 3.0),
           ("%fusion.3", 3.0, 3.5),
           ("jit(horizon)/while/body/sample/argmax", 5.0, 5.25)]
    assert pv.scope_seconds(ops, mods) == {
        "jit_run": {"kv_gather": 2.0, "mlp": 1.0, "other": 0.5},
        "jit_horizon": {"sample": 0.25}}


def test_scoped_ops_come_from_the_trace_json_arguments(tmp_path):
    """The profiler's ``*.trace.json.gz`` carries each device op's metadata
    among its arguments; ops are named by their ``tf_op`` path where it
    holds a scope."""
    import gzip
    m = [{"ph": "M", "pid": 1, "name": "process_name",
          "args": {"name": "/device:TPU:0"}},
         {"ph": "M", "pid": 2, "name": "process_name",
          "args": {"name": "/host:CPU"}},
         {"ph": "M", "pid": 1, "tid": 3, "name": "thread_name",
          "args": {"name": "XLA Ops"}},
         {"ph": "M", "pid": 1, "tid": 4, "name": "thread_name",
          "args": {"name": "XLA Modules"}}]
    x = [{"ph": "X", "pid": 2, "tid": 9, "ts": 0.0, "dur": 5e6,
          "name": "bench.window"},
         {"ph": "X", "pid": 1, "tid": 4, "ts": 1e6, "dur": 3e6,
          "name": "jit_run(7)"},
         {"ph": "X", "pid": 1, "tid": 3, "ts": 1e6, "dur": 2e6,
          "name": "fusion.1", "args": {"long_name": "fusion.1 = f32[8]",
                                       "tf_op": "jit(run)/kv_gather/gather"}},
         {"ph": "X", "pid": 1, "tid": 3, "ts": 3e6, "dur": 1e6,
          "name": "copy.2", "args": {"long_name": "copy.2"}}]
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    with gzip.open(d / "h.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": m + x}, f)
    got = pv.load_scoped(str(tmp_path))
    assert got["window"] == (0.0, 5.0)
    assert got["ops"] == [("jit(run)/kv_gather/gather", 1.0, 3.0),
                          ("copy.2", 3.0, 4.0)]
    assert pv.scope_seconds(got["ops"], got["modules"]) == {
        "jit_run": {"kv_gather": 2.0, "other": 1.0}}


def test_te_host_time_leaves_out_the_fetches_inside_each_step():
    spans = [("te.step", 0.0, 10.0), ("te.prefill", 1.0, 6.0),
             ("te.prefill.fetch", 2.0, 5.0), ("te.decode.fetch", 7.0, 8.0),
             ("te.step", 20.0, 22.0), ("te.decode.fetch", 30.0, 31.0)]
    assert pv.te_host_seconds(spans) == [6.0, 2.0]
    assert pv.te_host_seconds([]) == []


def test_idle_gaps_are_named_by_the_innermost_program_span():
    ops = [("x", 0.0, 1.0), ("y", 4.0, 5.0), ("z", 9.0, 10.0)]
    host = [("bench.je_step", 0.0, 10.0), ("je.step", 0.5, 9.5),
            ("te.step", 0.6, 9.4), ("te.decode.fetch", 1.0, 3.9),
            ("te.plan", 5.0, 5.2)]
    assert trace_red.idle_gaps(ops, host, 0.0, 10.0, k=2) == [
        ["te.step", pytest.approx(4.0)],
        ["te.decode.fetch", pytest.approx(3.0)]]


def test_counters_and_timeline_of_a_program_without_them():
    class Old:
        decode_steps = 3
        arrival, ttft = 1.0, 0.5
    assert pv.counters([Old(), Old()]) == {}
    assert pv.timeline(Old()) is None


CTX = {"counters": {"decode_steps": 64, "decode_dispatches": 16,
                    "decode_kv_live": 750, "decode_kv_slots": 1000,
                    "prefill_kv_live": 100, "prefill_kv_slots": 400},
       "spans": [("te.step", 0.0, 0.010), ("te.decode.fetch", 0.001, 0.005),
                 ("te.step", 0.020, 0.022)],
       "timelines": [(0.0, 0.1 * i, 0.1 * i + 0.01 * i)
                     for i in range(1, 11)]}


@pytest.mark.parametrize("name,want", [
    ("te_queue_wait_ms", 900.0), ("ttft_prefill_ms", 90.0),
    ("decode_horizon_mean", 4.0), ("decode_kv_pad_share", 25.0),
    ("prefill_kv_pad_share", 75.0), ("te_host_ms", 4.0)])
def test_program_readers_on_a_hand_built_context(name, want):
    assert Spec().reader(name).read(CTX) == pytest.approx(want)


@pytest.mark.parametrize("name", PROGRAM_METRICS)
def test_program_readers_give_none_with_nothing_to_read(name):
    """An older program (no counters, spans or timelines) and a stretch in
    which nothing of the kind ran."""
    reader = Spec().reader(name)
    assert reader.read({"counters": {"decode_steps": 5}}) is None
    assert reader.read({"counters": {
        k: 0 for k in CTX["counters"]}, "spans": [], "timelines": []}) is None


# ------------------------------------------------------------ tiny run

def test_tiny_traced_run_reads_the_program_and_agrees_with_the_harness(
        tiny_root, monkeypatch, capsys):
    """``trace_program`` on a tiny cell on the CPU: every reader of the
    program's inputs gives a finite value, and the program's own prefill
    tokens equal the harness's (``Driver._live``), which exceed the budget
    signal's by the extension rows that sample first tokens. The harness
    counts a decode row for every member of the running set before the JE
    step, the program each row it dispatched: they part where a row
    finishes at the head of a step, which the harness counts and no decode
    reads, so the program's count is never the larger."""
    # the CPU under a chip's name, so the per-layer readers find peaks
    kind = next(iter(json.loads((tiny_root / "bench" / "peaks.json")
                                .read_text())["kinds"]))
    monkeypatch.setattr(run, "require_devices",
                        lambda chips: dict(CPU, kind=kind))
    rc = trace_program.main(["--workload", "tiny.chat", "--seed",
                             str(2**33 + 7), "--seconds", "2"],
                            root=tiny_root)
    out, err = capsys.readouterr()
    assert rc == 0, err[-3000:]
    res = json.loads(out.strip().splitlines()[-1])
    for name in PROGRAM_METRICS:
        v = res["metrics"][name]
        assert v is not None and math.isfinite(v), name
    c = res["counters"]
    assert c["program_prefill_tokens"] == c["prefill_tokens"] > 0
    assert c["program_prefill_tokens"] >= c["prefilled"] > 0
    assert 0 < c["program_decode_rows"] <= c["decode_rows"]
    assert res["je_step"]["n"] > 0 and res["span_cost"]["off_us"] > 0

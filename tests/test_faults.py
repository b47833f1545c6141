"""Fault-injection + recovery tests (DESIGN.md §11, core/faults.py).

Fast subset (pure python / single cheap construct): FaultPlan
determinism and budgets, the FAILED lifecycle edges, cluster
fail/reboot, WarmPool entry integrity, the reserved-window leak
regression (thread hammer), and admission shedding plumbing.

Live-engine cases (ALSO marked slow+fleet): seeded TE kill mid-burst
with full recovery + greedy-token parity, mid-migration source crash
(at-most-once dedupe), transient transfer retry with backoff, fork
retry with an alternative source, drain-cancel racing a failure, and
``Scheduler.remove`` on a mid-migration sequence.
"""
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.abstractions import RequestType, Status, UserRequest
from repro.core.faults import (AdmissionRejected, FaultPlan, FaultSpec,
                               ForkFault, TEFailureError, TransferFault,
                               backoff_s)
from repro.core.fleet import (FleetExecutor, LifecycleError, TEState,
                              advance)
from repro.core.cluster import TaskExecutor
from repro.core.scaling import WarmPool, WarmPoolMismatchError
from repro.core.scheduling import TEHandle
from repro.core.serving_plane import ServingJobEngine, TopologySpec
from repro.engine import EngineConfig, FlowServe, Request, SamplingParams
from repro.models import get_model

pytestmark = pytest.mark.faults

SP = SamplingParams(temperature=0.0, max_new_tokens=10, stop_on_eos=False)
LENS, RATIOS = [16, 64], [0.25, 1.0]
COLO_HEAT = -np.ones((2, 2))
PD_HEAT = np.ones((2, 2))


def _ecfg(**kw):
    base = dict(n_pages=64, page_size=8, max_batch_tokens=32,
                chunk_size=8, max_decode_batch=4)
    base.update(kw)
    return EngineConfig(**base)


def _plane(bundle, params, topo, heat=COLO_HEAT, **kw):
    return ServingJobEngine(bundle, params, topo, heatmap=heat,
                            prefill_lens=LENS, decode_ratios=RATIOS,
                            ecfg=_ecfg(), **kw)


def _prompts(n, length=14, seed0=0):
    return [[1] + [int(x) for x in
                   np.random.RandomState(seed0 + i).randint(3, 200, length)]
            for i in range(n)]


@pytest.fixture(scope="module")
def qwen():
    bundle = get_model("qwen3-8b", smoke=True)
    params = bundle.init_params(jax.random.PRNGKey(0), jnp.float32)
    return bundle, params


def _reference_tokens(bundle, params, prompts, sp=SP):
    ref = FlowServe(bundle, params, _ecfg(), name="fref")
    ids = [ref.add_request(Request(prompt_tokens=p, sampling=sp))
           for p in prompts]
    comps = {c.req_id: c.tokens for c in ref.run_to_completion()}
    return [comps[i] for i in ids]


def _fake_engine(name, steps=0, queued=False):
    sched = types.SimpleNamespace(
        queued_seqs=lambda: ([object()] if queued else []))
    return types.SimpleNamespace(name=name, steps=steps, scheduler=sched,
                                 fault_plan=None,
                                 distflow=types.SimpleNamespace(
                                     fault_hook=None))


# ---------------------------------------------------------------------------
# Fast: FaultPlan determinism + budgets
# ---------------------------------------------------------------------------


def test_fault_plan_seeded_and_deterministic():
    names = [f"te-{i}" for i in range(8)]
    picks_a = [FaultPlan(seed=s).choose_victim(names) for s in range(20)]
    picks_b = [FaultPlan(seed=s).choose_victim(names) for s in range(20)]
    assert picks_a == picks_b             # same seed -> same victim
    assert len(set(picks_a)) > 1          # different seeds spread victims
    # victim choice ignores caller-side ordering
    assert FaultPlan(seed=3).choose_victim(names) \
        == FaultPlan(seed=3).choose_victim(list(reversed(names)))
    with pytest.raises(ValueError):
        FaultPlan(specs=[FaultSpec("meteor_strike")])


def test_fault_plan_crash_at_step_and_count_budget():
    fp = FaultPlan(specs=[FaultSpec("te_crash", te="te-1", at_step=3)])
    # wrong TE never fires; right TE fires only once step >= at_step
    fp.on_step(_fake_engine("te-0", steps=5))
    fp.on_step(_fake_engine("te-1", steps=2))
    with pytest.raises(TEFailureError) as ei:
        fp.on_step(_fake_engine("te-1", steps=3))
    assert ei.value.te == "te-1"
    # budget consumed: the same TE steps on afterwards
    fp.on_step(_fake_engine("te-1", steps=4))
    assert fp.fired("te_crash") == 1
    assert fp.injected[0]["te"] == "te-1" and fp.injected[0]["step"] == 3


def test_fault_plan_phase_scoping_and_prefix_match():
    # a PREFILL-phase crash only fires while the engine holds queued work
    fp = FaultPlan(specs=[FaultSpec("te_crash", te="te-pd0",
                                    phase="prefill")])
    fp.on_step(_fake_engine("te-pd0-p", queued=False))   # decode-only: no
    with pytest.raises(TEFailureError):                  # prefix match +
        fp.on_step(_fake_engine("te-pd0-p", queued=True))  # queued work
    # migration/fork phases never fire from on_step
    fp2 = FaultPlan(specs=[FaultSpec("te_crash", te="a", phase="migration"),
                           FaultSpec("te_crash", te="a", phase="fork")])
    fp2.on_step(_fake_engine("a", queued=True))
    with pytest.raises(TEFailureError):
        fp2.on_migration(_fake_engine("a"), "b")
    with pytest.raises(TEFailureError):
        fp2.on_fork(_fake_engine("a"))


def test_fault_plan_transient_kinds_and_straggler():
    fp = FaultPlan(specs=[FaultSpec("xfer_fail", count=2),
                          FaultSpec("fork_fail", te="src"),
                          FaultSpec("straggler", te="slow", delay_s=0.02)])
    for _ in range(2):
        with pytest.raises(TransferFault):
            fp.xfer_hook("x", "y", 1024)
    fp.xfer_hook("x", "y", 1024)          # budget of 2 exhausted
    with pytest.raises(ForkFault):
        fp.on_fork(_fake_engine("src"))
    t0 = time.monotonic()
    fp.on_step(_fake_engine("slow"))      # stalls but does not die
    assert time.monotonic() - t0 >= 0.02
    assert fp.fired() == 4


def test_backoff_is_capped_exponential():
    delays = [backoff_s(i) for i in range(8)]
    assert delays[:4] == [0.005, 0.01, 0.02, 0.04]
    assert all(d == 0.1 for d in delays[5:])      # capped
    assert delays == sorted(delays)


# ---------------------------------------------------------------------------
# Fast: FAILED lifecycle edges
# ---------------------------------------------------------------------------


def test_failed_state_legal_and_illegal_transitions():
    # legal: fail from WARMING/SERVING/DRAINING; leave via reboot or release
    for frm in (TEState.WARMING, TEState.SERVING, TEState.DRAINING):
        assert advance(frm, TEState.FAILED) is TEState.FAILED
    assert advance(TEState.FAILED, TEState.WARMING) is TEState.WARMING
    assert advance(TEState.FAILED, TEState.RELEASED) is TEState.RELEASED
    # every other FAILED edge raises
    for frm in (TEState.PROVISIONING, TEState.RELEASED, TEState.FAILED):
        with pytest.raises(LifecycleError):
            advance(frm, TEState.FAILED)
    for to in (TEState.SERVING, TEState.DRAINING, TEState.PROVISIONING):
        with pytest.raises(LifecycleError):
            advance(TEState.FAILED, to)


def test_cluster_te_fail_and_reboot_walk():
    te = TaskExecutor("te-0", "colocated")
    assert te.state is TEState.SERVING
    te.fail()
    assert not te.healthy and te.state is TEState.FAILED
    te.reboot()                           # FAILED -> WARMING -> SERVING
    assert te.healthy and te.state is TEState.SERVING
    # failing a DRAINING TE quarantines it too
    te.transition(TEState.DRAINING)
    te.fail()
    assert te.state is TEState.FAILED
    te.transition(TEState.RELEASED)       # replace instead of reboot
    te.fail()                             # RELEASED stays released
    assert te.state is TEState.RELEASED


def test_tehandle_failed_stops_admitting():
    h = TEHandle("t", "colocated", state=TEState.SERVING)
    assert h.admitting
    h.transition(TEState.FAILED)
    assert not h.admitting


# ---------------------------------------------------------------------------
# Fast: WarmPool entry integrity
# ---------------------------------------------------------------------------


def test_warm_pool_hit_miss_and_tag_mismatch():
    pool = WarmPool(capacity_bytes=2000)
    params = {"w": np.zeros((8, 8), np.float32)}           # 256 B
    assert pool.put("qwen", params, host_copy=False, tag="qwen-8b")
    assert pool.get("llama") is None                       # miss
    assert pool.get("qwen", tag="qwen-8b") is params       # tagged hit
    assert pool.get("qwen") is params                      # untagged hit
    with pytest.raises(WarmPoolMismatchError):
        pool.get("qwen", tag="llama-70b")                  # wrong asset
    with pytest.raises(WarmPoolMismatchError):
        pool.put("qwen", params, host_copy=False, tag="llama-70b")
    assert pool.stats()["hits"] == 2 and pool.stats()["misses"] == 1
    # eviction clears the tag with the entry
    big = {"w": np.zeros((450,), np.float32)}              # 1800 B
    assert pool.put("other", big, host_copy=False)
    assert "qwen" not in pool.tags and not pool.hit("qwen")


def test_from_warm_rejects_mismatched_asset(qwen):
    bundle, params = qwen
    bogus = {"not_the_model": np.zeros((4, 4), np.float32)}
    with pytest.raises(WarmPoolMismatchError, match="does not match"):
        FlowServe.from_warm(bundle, bogus, _ecfg(), name="te-bad")
    # the real params still come up fine
    te = FlowServe.from_warm(bundle, jax.tree.map(np.asarray, params),
                             _ecfg(), name="te-good")
    assert te.fork_ready


# ---------------------------------------------------------------------------
# Fast: reserved-window leak regression (thread hammer)
# ---------------------------------------------------------------------------


def _window_plane():
    """A plane skeleton exposing ONLY the window allocator (no engines)."""
    je = ServingJobEngine.__new__(ServingJobEngine)
    je.topology = TopologySpec(colo=1, tp=1)
    je._offset_cursor = 0
    je._free_windows = []
    je._window_of = {}
    je._window_lock = threading.Lock()
    je._reserved_windows = set()
    je.window_fallbacks = 0
    return je


def test_window_abort_releases_reservation():
    je = _window_plane()
    off, owned = je._alloc_window()
    assert owned and off in je._reserved_windows
    je._abort_window(off, owned)          # the fork raised: no leak
    assert off not in je._reserved_windows
    off2, owned2 = je._alloc_window()
    assert owned2 and off2 == off         # the window is reusable
    je._commit_window("te-x", off2, owned2)
    assert je._window_of["te-x"] == off2
    # committing an UNOWNED fallback window must not clobber a live
    # reservation of offset 0
    je2 = _window_plane()
    off0, owned0 = je2._alloc_window()
    assert off0 == 0 and owned0
    je2._commit_window("te-fallback", 0, False)
    assert 0 in je2._reserved_windows     # the real claim survives
    je2._commit_window("te-real", off0, owned0)
    assert je2._window_of["te-real"] == 0


def test_window_leak_thread_hammer():
    """Concurrent forks that abort mid-bring-up must never shrink the
    fleet: after the hammer, every window is either committed or free and
    nothing stays reserved."""
    je = _window_plane()
    n_threads, iters = 8, 40
    errors = []

    def hammer(tid):
        rng = np.random.RandomState(tid)
        try:
            for i in range(iters):
                off, owned = je._alloc_window()
                if rng.rand() < 0.5:      # fork "raised" mid-bring-up
                    je._abort_window(off, owned)
                else:
                    name = f"te-{tid}-{i}"
                    je._commit_window(name, off, owned)
                    if owned:             # release it again (scale-in)
                        with je._window_lock:
                            je._free_windows.append(
                                je._window_of.pop(name))
        except Exception as exc:          # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert je._reserved_windows == set()  # nothing leaked
    assert not je._window_of              # everything released again
    # every window the cursor ever handed out is recoverable
    recovered = set()
    while True:
        off, owned = je._alloc_window()
        if not owned or off in recovered:
            break
        recovered.add(off)
    assert len(recovered) >= min(je._offset_cursor, 1)


# ---------------------------------------------------------------------------
# Fast: admission shedding plumbing
# ---------------------------------------------------------------------------


def test_admission_check_sheds_on_bounded_queue():
    je = ServingJobEngine.__new__(ServingJobEngine)
    je.admission_limit = 2
    je.steps = 0
    je.jobs, je.rejections, je._parked = {}, [], []
    eng = types.SimpleNamespace(load_metrics=lambda: {"n_queued": 3})
    h = TEHandle("te-0", "colocated", state=TEState.SERVING)
    h.engine = eng
    je._handles = [h]
    req = UserRequest(rtype=RequestType.CHAT,
                      payload={"tokens": [1, 2, 3], "max_new_tokens": 4})
    with pytest.raises(AdmissionRejected) as ei:
        je._check_admission(req)          # 3 queued >= 2 * 1 serving
    assert ei.value.req_id == req.req_id
    assert je.rejections[0]["cap"] == 2
    job = next(iter(je.jobs.values()))
    assert job.status is Status.REJECTED
    # capacity recovered (or queue drained): admission reopens
    eng2 = types.SimpleNamespace(load_metrics=lambda: {"n_queued": 0})
    h.engine = eng2
    je._check_admission(req)              # no raise
    # limit=None disables shedding entirely
    je.admission_limit = None
    h.engine = eng
    je._check_admission(req)


# ---------------------------------------------------------------------------
# Slow: live kill -> recovery with greedy-token parity
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.fleet
@pytest.mark.parametrize("threads", [0, 4])
def test_live_te_kill_recovers_all_requests_with_parity(qwen, threads):
    """Seeded kill of 1-of-3 TEs mid-burst: the plane completes 100% of
    requests exactly once (restarts counted), and every completion —
    including the restarted ones, which re-run from the prompt at
    temperature 0 — matches the no-fault reference tokens."""
    bundle, params = qwen
    prompts = _prompts(9)
    expect = _reference_tokens(bundle, params, prompts)

    fp = FaultPlan(seed=11)
    victim = fp.choose_victim([f"te-colo{i}" for i in range(3)])
    fp.add(FaultSpec("te_crash", te=victim, at_step=2))
    je = _plane(bundle, params, TopologySpec(colo=3),
                policy="round_robin", fault_plan=fp,
                fleet_threads=threads)
    try:
        rids = [je.submit(p, SP) for p in prompts]
        comps = je.run_to_completion()
        assert fp.fired("te_crash") == 1
        got = {}
        for c in comps:
            assert c.req_id not in got, "duplicated completion"
            got[c.req_id] = c.tokens
        assert sorted(got) == sorted(rids)          # none lost
        for rid, want in zip(rids, expect):
            assert got[rid] == want                 # greedy parity for ALL
        # containment surfaced in the plane's books
        ev = [e for e in je.scale_events if e["kind"] == "te_failure"]
        assert len(ev) == 1 and ev[0]["te_id"] == victim
        restarts = je.restart_counts()
        assert ev[0]["n_restarted"] == len(restarts) > 0
        assert all(r["reason"] == "te_failure" for r in je.resubmits)
        assert victim not in [h.te_id for h in je.handles]
        assert je.n_serving() == 2
        # repair: scale_to refills the lost capacity from survivors
        plan = je.scale_to(3)
        assert je.n_serving() == 3 and plan["tiers"]["fork"] >= 1
    finally:
        je.close()


@pytest.mark.slow
@pytest.mark.fleet
def test_injected_crash_is_recorded(qwen):
    """Recovery hides a failure from the requests, never from the plane's
    books: the crash is kept with its unit, type, message and traceback."""
    bundle, params = qwen
    fp = FaultPlan(specs=[FaultSpec("te_crash", te="te-colo1", at_step=0)])
    je = _plane(bundle, params, TopologySpec(colo=2), policy="round_robin",
                fault_plan=fp)
    try:
        rids = [je.submit(p, SP) for p in _prompts(2)]
        assert {c.req_id for c in je.run_to_completion()} == set(rids)
        assert fp.fired("te_crash") == 1
        [rec] = je.unit_failures
        assert rec["unit"] == "te-colo1"
        assert rec["type"] == "TEFailureError"
        assert "te-colo1" in rec["message"]
        assert "TEFailureError" in rec["traceback"]
    finally:
        je.close()


@pytest.mark.slow
@pytest.mark.fleet
def test_mid_migration_source_crash_dedupes(qwen):
    """The source dies AFTER the destination imported (mid-migration):
    recovery must produce exactly one live copy — the voided import
    restarts once, never both endpoints."""
    bundle, params = qwen
    prompts = _prompts(4)
    # long decode budget: the fused hot loop emits up to decode_horizon
    # tokens per step, so a 10-token run would finish before the drain
    # pump gets a chance to migrate anything off the victim
    sp = SamplingParams(temperature=0.0, max_new_tokens=40,
                        stop_on_eos=False)
    expect = _reference_tokens(bundle, params, prompts, sp=sp)
    fp = FaultPlan(specs=[FaultSpec("te_crash", te="te-colo0",
                                    phase="migration")])
    je = _plane(bundle, params, TopologySpec(colo=2),
                policy="round_robin", fault_plan=fp)
    try:
        rids = [je.submit(p, sp) for p in prompts]
        for _ in range(3):
            je.step()
        je.drain("te-colo0")              # forces migrations off colo0
        je.run_to_completion()
        comps = {c.req_id: c.tokens for c in je.completions}
        assert fp.fired("te_crash") == 1
        assert sorted(comps) == sorted(rids)
        assert len(je.completions) == len(rids)   # exactly once, no dup
        for rid, want in zip(rids, expect):
            assert comps[rid] == want
        ev = [e for e in je.scale_events if e["kind"] == "te_failure"]
        assert len(ev) == 1 and ev[0]["te_id"] == "te-colo0"
    finally:
        je.close()


@pytest.mark.slow
@pytest.mark.fleet
def test_transient_transfer_fault_retries_with_backoff(qwen):
    """A transient wire failure on the PD handoff voids nothing: both
    endpoints restore state and the pump retries with capped backoff
    until the KV lands — every request still completes."""
    bundle, params = qwen
    prompts = _prompts(3)
    fp = FaultPlan(specs=[FaultSpec("xfer_fail", te="te-pd0-p", count=2)])
    je = _plane(bundle, params, TopologySpec(pd=1, colo=0), heat=PD_HEAT,
                fault_plan=fp)
    try:
        rids = [je.submit(p, SP) for p in prompts]
        comps = {c.req_id for c in je.run_to_completion()}
        assert comps == set(rids)
        assert fp.fired("xfer_fail") == 2
        assert je.xfer_retries == 2       # each fault parked + retried
        assert je._xfer_retry == {}       # all backoffs resolved
    finally:
        je.close()


@pytest.mark.slow
@pytest.mark.fleet
def test_fork_retries_transient_fault_and_alternative_source(qwen):
    bundle, params = qwen
    # transient ForkFault: the same scale-out retries and succeeds
    fp = FaultPlan(specs=[FaultSpec("fork_fail", count=1)])
    je = _plane(bundle, params, TopologySpec(colo=2), fault_plan=fp)
    try:
        je._scale_out()
        assert fp.fired("fork_fail") == 1
        assert je.n_serving() == 3        # retry from the next source won
        assert je._reserved_windows == set()
    finally:
        je.close()
    # fork SOURCE dies mid-fork: quarantined, alternative source finishes
    fp2 = FaultPlan(specs=[FaultSpec("te_crash", te="te-colo0",
                                     phase="fork")])
    je2 = _plane(bundle, params, TopologySpec(colo=2), fault_plan=fp2)
    try:
        je2._scale_out()
        assert fp2.fired("te_crash") == 1
        names = [h.te_id for h in je2.handles]
        assert "te-colo0" not in names    # the dead source left the fleet
        assert je2.n_serving() == 2       # lost 1, forked 1
        assert any(e["kind"] == "te_failure" for e in je2.scale_events)
        assert je2._reserved_windows == set()
    finally:
        je2.close()


@pytest.mark.slow
@pytest.mark.fleet
def test_drain_cancel_races_concurrent_failure(qwen):
    """Drain-cancel on TE A in the same step window as TE B failing: B is
    quarantined, its work parks (A is DRAINING — no admitting survivor
    exists), the cancel lands (A serves again), and the parked work
    flushes onto A so every request still completes exactly once."""
    bundle, params = qwen
    prompts = _prompts(6)
    fp = FaultPlan(specs=[FaultSpec("te_crash", te="te-colo1", at_step=0)])
    je = _plane(bundle, params, TopologySpec(colo=2),
                policy="round_robin", fault_plan=fp)
    try:
        rids = [je.submit(p, SP) for p in prompts]
        je.drain("te-colo0")
        je.step()                         # colo1 crashes mid-drain of colo0
        assert "te-colo1" not in [h.te_id for h in je.handles]
        assert je._parked                 # no admitting survivor yet
        h0 = next(h for h in je.handles if h.te_id == "te-colo0")
        assert h0.state is TEState.DRAINING   # the drain could not finish
        je.cancel_drain("te-colo0")       # resurgence: the drain reverses
        assert h0.state is TEState.SERVING
        je.run_to_completion()
        comps = {c.req_id for c in je.completions}
        assert comps == set(rids)
        assert len(je.completions) == len(rids)   # exactly once
        assert not je._parked
        assert any(r["from"] == "parked" for r in je.resubmits)
    finally:
        je.close()


@pytest.mark.slow
@pytest.mark.fleet
def test_scheduler_remove_on_mid_migration_sequence(qwen):
    """``Scheduler.remove`` on a sequence whose KV import is still in
    flight must leave the destination consistent: the pending handle is
    void, pages release, and the engine keeps serving other work."""
    bundle, params = qwen
    src = FlowServe(bundle, params, _ecfg(mode="prefill"), name="srcte")
    dst = FlowServe(bundle, params, _ecfg(mode="decode"), name="dstte")
    src.distflow.link_cluster([dst.distflow])
    rid = src.add_request(Request(prompt_tokens=_prompts(1)[0], sampling=SP))
    ready = []
    while not ready:
        src.step()
        ready = src.pop_migratable()
    assert ready == [rid]
    src.migrate_out(rid, dst, overlap=True)     # async: _kv_pending set
    seq = dst._seqs[rid]
    assert "_kv_pending" in seq.extra
    free_before = dst.pool.free_page_count()
    dst.scheduler.remove(seq)
    seq.extra.pop("_kv_pending", None)          # voided, never scattered
    dst.release_request(rid, keep_prefix=False)
    assert rid not in dst._seqs and not dst.has_work()
    assert dst.pool.free_page_count() > free_before
    # the pair still serves fresh work afterwards (full PD handoff)
    rid2 = src.add_request(Request(prompt_tokens=_prompts(1, seed0=9)[0],
                                   sampling=SP))
    ready = []
    while not ready:
        src.step()
        ready = src.pop_migratable()
    src.migrate_out(rid2, dst, overlap=False)
    comps = dst.run_to_completion()
    assert [c.req_id for c in comps] == [rid2]


@pytest.mark.slow
@pytest.mark.fleet
def test_admission_sheds_live_and_reopens_after_repair(qwen):
    """Graceful degradation end to end: capacity loss shrinks the
    admission bound, excess submits are REJECTED (not queued), and the
    accepted backlog still completes."""
    bundle, params = qwen
    fp = FaultPlan(specs=[FaultSpec("te_crash", te="te-colo1", at_step=0)])
    je = _plane(bundle, params, TopologySpec(colo=2),
                policy="round_robin", fault_plan=fp, admission_limit=2)
    try:
        accepted = [je.submit(p, SP) for p in _prompts(3)]
        je.step()                         # colo1 dies; its work restarts
        assert je.n_serving() == 1
        with pytest.raises(AdmissionRejected):
            for p in _prompts(8, seed0=50):
                accepted.append(je.submit(p, SP))
        assert je.rejections and je.rejections[-1]["n_serving"] == 1
        accepted = [r for r in accepted if r in je.requests]
        comps = {c.req_id for c in je.completions + je.run_to_completion()}
        assert set(accepted) <= comps     # accepted work all completes
        rejected_jobs = [j for j in je.jobs.values()
                         if j.status is Status.REJECTED]
        assert len(rejected_jobs) == len(je.rejections)
    finally:
        je.close()

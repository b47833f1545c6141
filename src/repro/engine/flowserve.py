"""FLOWSERVE — the serving engine (§4). One engine == one model-serving TE.

Master–executor architecture: the master (this class) runs the scheduler,
RTC index, and DistFlow decisions; the executor side is the model runner
(+ page pools), which with ``EngineConfig.tp > 1`` IS an SPMD program
spanning the TE's NPUs — a 1×tp ("data","model") mesh with weights, paged
KV pools and slot caches sharded per launch/sharding.py (DESIGN.md §5).
Modes mirror §4.5: "colocated" (chunked-prefill + decode in one engine),
"prefill" (P-only TE) and "decode" (D-only TE) for PD-disaggregated groups.
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.engine.distflow import (BufferInfo, DistFlow, TransferFault,
                                   _nbytes)
from repro.engine.hotloop import DecodeHotState, pow2_bucket, pow2s
from repro.engine.kv_cache import OutOfPagesError, PagedKVPool, pages_needed
from repro.engine.runners import SequenceState, resolve_family
from repro.engine.rtc import RelationalTensorCache, RTCCostModel
from repro.engine.sampling import SamplingParams, sample_batch
from repro.engine.scheduler import Scheduler, SchedulerConfig
from repro.engine.tokenizer import EOS_ID, ByteTokenizer
from repro.engine.trace import span, spanned
from repro.models.model_factory import ModelBundle

_req_ids = itertools.count()


@dataclass
class Request:
    prompt_tokens: List[int]
    sampling: SamplingParams = field(default_factory=SamplingParams)
    req_id: str = ""
    ctx_id: Optional[str] = None        # explicit context-caching id
    arrival: float = field(default_factory=time.monotonic)
    extra: Dict[str, Any] = field(default_factory=dict)  # modality stubs

    def __post_init__(self):
        if not self.req_id:
            self.req_id = f"req-{next(_req_ids)}"


@dataclass
class Completion:
    req_id: str
    tokens: List[int]
    ttft: float
    finish: float
    arrival: float
    n_prompt: int
    # monotonic time of the first prefill dispatch that held one of the
    # request's tokens (or, for a prompt that needed none, of its prefill
    # being declared done): queue wait = first_dispatch - arrival, prefill
    # = arrival + ttft - first_dispatch
    first_dispatch: float

    @property
    def tpot(self) -> float:
        n = max(len(self.tokens) - 1, 1)
        return (self.finish - self.arrival - self.ttft) / n

    @property
    def jct(self) -> float:
        return self.finish - self.arrival


@dataclass
class EngineConfig:
    mode: str = "colocated"             # colocated | prefill | decode
    tp: int = 1                         # model-axis width of the TE's mesh
    device_offset: int = 0              # first device of the TE's 1×tp window
    n_pages: int = 256
    page_size: int = 16
    n_slots: int = 8                    # SlotRunner slots
    max_len: int = 256                  # SlotRunner per-slot capacity
    max_batch_tokens: int = 64
    max_decode_batch: int = 8
    chunk_size: int = 16
    max_prefill_seqs: int = 8           # concurrent mid-prefill sequences
    enable_prefix_cache: bool = True
    async_sched: bool = True
    fused_decode: bool = True           # NPU-centric hot loop (DESIGN.md §8)
    decode_horizon: int = 8             # max fused multi-step K (1 = off)
    batched_prefill: bool = True        # one-dispatch ragged prefill (§12)
    dtype: Any = jnp.float32
    seed: int = 0


def _executor_safe(fn):
    """Serialize an engine entry point on the per-engine RLock: the fleet
    runtime (core/fleet.py) steps TEs from per-unit worker threads while
    the JE driver thread runs cross-unit actions (drain migration, NPU-fork,
    load reads) — every public mutation must hold the engine's lock. The
    RLock keeps internal reentrancy (step → export → release) free."""
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._lock:
            return fn(self, *args, **kwargs)
    return wrapper


class FlowServe:
    def __init__(self, bundle: ModelBundle, params, ecfg: EngineConfig,
                 name: str = "te-0"):
        self._lock = threading.RLock()   # executor-safety (DESIGN.md §9)
        self.bundle = bundle
        self.cfg: ModelConfig = bundle.cfg
        self.ecfg = ecfg
        self.name = name
        # microkernel registry (DESIGN.md §12): the family — not an if-ladder
        # here — decides pool-vs-slots, KV sharding, and runner construction
        self.family = resolve_family(self.cfg)
        self.runner_kind = self.family.name
        self.tokenizer = ByteTokenizer(max(self.cfg.vocab_size, 259))
        self.distflow = DistFlow(owner=name)
        self.fault_plan = None           # set by FaultPlan.attach (§11)
        self._key = jax.random.PRNGKey(ecfg.seed)

        # SPMD executor mesh: the TE's NPUs form a pure TP group (tp=1 keeps
        # the legacy single-device path; DP happens across TEs via the JE).
        self.mesh = None
        self.device = None
        if ecfg.tp > 1:
            from repro.launch.mesh import make_engine_mesh
            self.mesh = make_engine_mesh(ecfg.tp, offset=ecfg.device_offset)
        elif ecfg.device_offset > 0:
            # tp=1 TEs also honor their device window (DESIGN.md §9): each
            # fleet member owns ONE device, so concurrent per-TE executors
            # genuinely overlap device work instead of queueing on device 0
            self.device = jax.devices()[ecfg.device_offset
                                        % jax.device_count()]
            params = jax.device_put(params, self.device)
            self._key = jax.device_put(self._key, self.device)

        if self.family.uses_pages:
            kv_sharding = None
            if self.mesh is not None and self.family.kv_pool_sharding is not None:
                kv_sharding = self.family.kv_pool_sharding(self.cfg, self.mesh)
            self.pool = PagedKVPool(self.cfg, ecfg.n_pages, ecfg.page_size,
                                    ecfg.dtype, sharding=kv_sharding)
            if self.device is not None:
                # unpinned jits follow their operands, so homing the pool
                # (and params/key above) is all the pinning the TE needs
                self.pool.k = jax.device_put(self.pool.k, self.device)
                self.pool.v = jax.device_put(self.pool.v, self.device)
            cm = RTCCostModel(flops_per_token=2.0 * self.cfg.active_param_count())
            self.rtc = RelationalTensorCache(self.pool, cm) \
                if ecfg.enable_prefix_cache else None
            self.runner = self.family.build(bundle, params, self.pool,
                                            dtype=ecfg.dtype, mesh=self.mesh)
        else:
            self.pool = None
            self.rtc = None
            self.runner = self.family.build(bundle, params, dtype=ecfg.dtype,
                                            mesh=self.mesh, n_slots=ecfg.n_slots,
                                            max_len=ecfg.max_len)
            if self.device is not None:
                self.runner.cache = {k: jax.device_put(v, self.device)
                                     for k, v in self.runner.cache.items()}
            self._state_cache: Dict[tuple, Any] = {} if ecfg.enable_prefix_cache else None

        scfg = SchedulerConfig(max_batch_tokens=ecfg.max_batch_tokens,
                               max_decode_batch=ecfg.max_decode_batch,
                               chunk_size=ecfg.chunk_size,
                               max_prefill_seqs=ecfg.max_prefill_seqs,
                               mode=ecfg.mode)
        self.scheduler = Scheduler(scfg, self.rtc, self.family.uses_pages)
        self._seqs: Dict[str, SequenceState] = {}
        self._requests: Dict[str, Request] = {}
        self._ttft: Dict[str, float] = {}
        self._first_dispatch: Dict[str, float] = {}  # see Completion
        self._next_plan = None
        self._prefill_done_buffer: List[str] = []  # P-mode: ready to migrate
        self.steps = 0
        self.step_wall = 0.0
        self.decode_steps = 0            # decode iterations executed (B-wide)
        self.sampler_dispatches = 0      # STANDALONE dispatches spent sampling
        self.host_dispatches = 0         # device dispatches on the decode path
        # decode token fetches with no later decode dispatch queued behind
        # them, so the device has nothing left to run while the host waits:
        # each per-step (legacy or slot) fetch, and a fused block fetched
        # with no younger block in flight (the last one of a drain). The
        # steady-state horizon-late fetch in _decode_fused_step has the
        # horizon just dispatched behind it and is not one. Counted from
        # the engine's own state, never from the device's timing.
        self.host_syncs = 0
        # work counters, cumulative, bumped at each dispatch: decode
        # dispatches (fused horizons and per-step decodes), decode rows
        # summed over decode steps, and over those steps the rows' live
        # context tokens against the KV slots the program reads (batch
        # bucket x page bucket x page size); prefill tokens packed
        # (extension rows included), the sum over them of position + 1
        # (the context each attends to), and the sum over dispatches of
        # token bucket x page bucket x page size
        self.decode_dispatches = 0
        self.decode_rows = 0
        self.decode_kv_live = 0
        self.decode_kv_slots = 0
        self.prefill_tokens = 0
        self.prefill_kv_live = 0
        self.prefill_kv_slots = 0
        # prefill-side accounting (§12): dispatches counted in BOTH modes so
        # benchmarks can compare dispatches-per-prompt-token; syncs are the
        # batched path's first-token fetches (separate from decode host_syncs,
        # which tests pin to the decode path)
        self.prefill_dispatches = 0      # device dispatches on the prefill path
        self.prefill_syncs = 0           # blocking fetches on the prefill path
        self._prefill_key = None         # persistent in-dispatch sampling key
        self.sample_params: Dict[str, SamplingParams] = {}
        # decode hot loop (DESIGN.md §8): persistent device-resident batch
        # state, in-flight token blocks (fetched one horizon late), and the
        # per-sequence count of sampled-but-uncommitted tokens
        self._hot: Optional[DecodeHotState] = None
        self._inflight: deque = deque()  # (tokens_dev, [(slot, seq_id)], K)
        self._pending: Dict[str, int] = {}
        self._completed_buf: List[Completion] = []
        self._sp_cache: tuple = (None, None, None)  # batch-keyed temps/top_ps

    @property
    def jit_compiles(self) -> int:
        """Decode-path jit cache misses (bucketed keys ⇒ 0 in steady state)."""
        return getattr(self.runner, "jit_compiles", 0)

    @property
    def prefill_jit_compiles(self) -> int:
        """Prefill-path jit cache misses (0 after ``warmup_prefill``)."""
        return getattr(self.runner, "prefill_jit_compiles", 0)

    # ---------------------------------------------------------------- scaling
    @classmethod
    def fork_from(cls, source: "FlowServe", ecfg: EngineConfig,
                  name: str = "te-fork", link: str = "ici") -> "FlowServe":
        """NPU-fork (§6.3): bring up a new TE by forking weights PER-SHARD
        from a live (possibly sharded) TE onto the new TE's own mesh —
        replacing re-initialization / host reload. Each destination shard
        fills via ``jax.device_put`` from the source's resident params (the
        ICI-broadcast analogue; ``link="dcn"`` prices the scale-out
        fallback); DistFlow charges both endpoints. The new TE is linked
        into the source's peer group."""
        from repro.core.scaling import npu_fork_live
        from repro.launch.mesh import make_engine_mesh
        if getattr(source, "fault_plan", None) is not None:
            source.fault_plan.on_fork(source)
        dst_mesh = make_engine_mesh(ecfg.tp, offset=ecfg.device_offset) \
            if ecfg.tp > 1 else None
        with source._lock:   # executor-safe vs a fleet worker stepping src
            params, lr = npu_fork_live(
                source.runner.params, source.cfg, dst_mesh,
                source=source.distflow, link=link,
                dst_device=jax.devices()[ecfg.device_offset])
            te = cls(source.bundle, params, ecfg, name=name)
            source.distflow.link_cluster([te.distflow])
        te.distflow.sim_clock += lr.seconds   # the fork target observed it too
        return te

    @classmethod
    def from_warm(cls, bundle: ModelBundle, host_params, ecfg: EngineConfig,
                  name: str = "te-warm") -> "FlowServe":
        """DRAM-warm bring-up (DESIGN.md §10): construct a TE from a
        ``WarmPool``'s host-pinned params — ``device_put`` onto the TE's
        device window replaces model re-init entirely. The pool entry is
        only read, so any number of TEs can come up from one entry
        concurrently. tp>1 TEs shard through the constructor's mesh path;
        tp=1 TEs are explicitly homed here (the constructor only pins when
        ``device_offset > 0``, but warm params must land on-device even in
        window 0 or every dispatch would re-upload them).

        Entry integrity (DESIGN.md §11): the pool stores arbitrary pytrees
        keyed by name — a stale/mispointed entry would silently build a TE
        from the WRONG weights. Validate the entry's tree structure and
        leaf shapes against ``bundle`` before committing any device memory;
        mismatch raises ``WarmPoolMismatchError``."""
        from repro.core.scaling import WarmPoolMismatchError
        expected = jax.eval_shape(
            lambda k: bundle.init_params(k, jnp.float32),
            jax.random.PRNGKey(0))
        exp_tree = jax.tree_util.tree_structure(expected)
        got_tree = jax.tree_util.tree_structure(host_params)
        exp_shapes = [tuple(l.shape) for l in jax.tree_util.tree_leaves(expected)]
        got_shapes = [tuple(np.shape(l)) for l in
                      jax.tree_util.tree_leaves(host_params)]
        if exp_tree != got_tree or exp_shapes != got_shapes:
            raise WarmPoolMismatchError(
                f"warm-pool entry does not match model "
                f"{getattr(bundle.cfg, 'name', '?')!r} for TE {name}: "
                f"tree/shape mismatch (expected {len(exp_shapes)} leaves, "
                f"got {len(got_shapes)})")
        if ecfg.tp <= 1:
            dev = jax.devices()[ecfg.device_offset % jax.device_count()]
            host_params = jax.device_put(host_params, dev)
        return cls(bundle, host_params, ecfg, name=name)

    @property
    def fork_ready(self) -> bool:
        """True while this TE's params are device-resident, i.e. it can act
        as an NPU-fork source (a TE that drained its params back to the
        warm pool on release is not)."""
        return getattr(self.runner, "params", None) is not None

    @_executor_safe
    def release_params(self, to_host: bool = True):
        """Drain this TE's device-resident params back to host DRAM (the
        RELEASED → WarmPool leg of the cold-start ladder). Returns the host
        pytree (``to_host=True``) or None; either way the device copy is
        dropped and the engine stops being a fork source. Call only after
        the TE is empty — it cannot serve afterwards."""
        params = getattr(self.runner, "params", None)
        if params is None:
            return None
        host = jax.tree.map(lambda a: np.asarray(a), params) if to_host \
            else None
        self.runner.params = None
        return host

    @_executor_safe
    def cancel_queued(self) -> List[Request]:
        """Pull every not-yet-fully-prefilled sequence out of this engine
        (drain support, DESIGN.md §10): mid-PREFILL work on a draining TE
        is re-submitted to the drain destination as a token-level restart
        instead of finishing prefill locally. Returns the original
        ``Request`` objects (req_id + arrival preserved, so latency
        accounting spans the restart); their pages/slots here are freed
        without preserving prefixes."""
        out: List[Request] = []
        for seq in list(self.scheduler.queued_seqs()):
            req = self._requests.get(seq.seq_id)
            if req is None:
                continue
            self.scheduler.remove(seq)
            seq.extra.pop("_kv_pending", None)
            self.release_request(seq.seq_id, keep_prefix=False)
            out.append(req)
        return out

    # ---------------------------------------------------------------- API
    @_executor_safe
    def add_request(self, req: Request) -> str:
        seq = SequenceState(seq_id=req.req_id, tokens=list(req.prompt_tokens),
                            n_prompt=len(req.prompt_tokens), extra=dict(req.extra))
        if not seq.extra:
            seq.extra = {k: np.asarray(v) for k, v in
                         self.bundle.extra_inputs(1, self.ecfg.dtype).items()}
        self._seqs[req.req_id] = seq
        self._requests[req.req_id] = req
        self.sample_params[req.req_id] = req.sampling
        # a reused req_id may carry different sampling params: the cached
        # per-batch temps/top_ps arrays would alias the old request's —
        # and a stale TTFT stamp would suppress re-stamping for the new one
        self._sp_cache = (None, None, None)
        self._ttft.pop(req.req_id, None)
        if self.runner_kind == "slot" and self._state_cache is not None:
            self._try_state_reuse(seq)
        self.scheduler.admit(seq)
        self._drop_idle_plan()
        return req.req_id

    def _drop_idle_plan(self) -> None:
        """An async plan prepared while the engine was idle is empty, and
        stale once work arrives: running it would spend the next step on
        nothing."""
        if self._next_plan is not None and self._next_plan.is_empty():
            self._next_plan = None

    @_executor_safe
    def has_work(self) -> bool:
        return bool(self._inflight or self._completed_buf) \
            or self.scheduler.has_work()

    @_executor_safe
    @spanned("te.step")
    def step(self) -> List[Completion]:
        """One engine iteration: (maybe prepared) plan → execute → sample →
        commit → prepare next plan (async mode prepares before sampling).
        With ``fused_decode`` a pure-decode step is ONE fused device
        dispatch covering a K-step horizon; its token block is fetched a
        horizon later, so completions surface with at most one extra step
        of latency (DESIGN.md §8)."""
        t0 = time.monotonic()
        if self.fault_plan is not None:
            self.fault_plan.on_step(self)
        with span("te.plan"):
            self.scheduler.resolve_prefix()
            self.scheduler.pump_prefetch()
            plan = self._next_plan if (self.ecfg.async_sched
                                       and self._next_plan) \
                else self.scheduler.prepare_next()
        self._next_plan = None
        completions: List[Completion] = []
        if self._inflight and (plan.prefill or not plan.decode):
            # prefill page allocation may preempt a running (in-flight) seq —
            # make host state authoritative before that can happen. And when
            # the plan has NO decode batch (e.g. every sequence EOS-stopped
            # in the previous block), the orphaned in-flight horizon must be
            # committed here or nothing ever would.
            self._drain_inflight()

        # ---------------- prefill chunks
        if plan.prefill:
            with span("te.prefill"):
                if self.family.uses_pages and self.ecfg.batched_prefill:
                    self._prefill_batched(plan.prefill)
                else:
                    self._prefill_legacy(plan.prefill)

        # ---------------- decode batch
        if plan.decode:
            # drop seqs that finished or were preempted (requeued) after the
            # plan was (asynchronously) prepared
            live = self._refilter(plan.decode)
            fused = False
            if live and self.runner_kind == "paged" and self.ecfg.fused_decode:
                fused = self._decode_fused_step(live)
            elif live and self.runner_kind == "slot" and self.ecfg.fused_decode:
                fused = self._decode_slot_fused(live)
            if not fused and live:
                self._drain_inflight()
                live = self._refilter(live)
            if not fused and live and self.runner_kind == "paged":
                for s in live:
                    if s in self.scheduler.running:  # not yet preempted
                        self._ensure_pages(s, len(s.tokens))
                # page pressure may have preempted batch members: they must
                # NOT decode this step (their freed pages may already belong
                # to another sequence — writing would corrupt it)
                live = [s for s in live if s in self.scheduler.running]
            if not fused and live:
                for s in live:
                    handle = s.extra.pop("_kv_pending", None)
                    if handle is not None:   # first decode of a migrated seq
                        self._import_layerwise(handle, s)
                with span("te.decode.dispatch"):
                    logits = self.runner.decode(live)
                self._count_decode(1, len(live),
                                   sum(len(s.tokens) for s in live),
                                   len(live) * max(len(s.pages) for s in live)
                                   if self.runner_kind == "paged" else 0)
                self.host_dispatches += 1
                # async scheduling: the next plan depends only on counts —
                # prepare it *before* sampling commits token values (§4.2)
                if self.ecfg.async_sched:
                    self._next_plan = self._plan_next()
                self._commit_tokens(live, logits)
                if self._hot is not None:
                    self._hot.reset()   # device rows are stale vs host now

        if self.ecfg.async_sched and self._next_plan is None:
            self._next_plan = self._plan_next()
        self.steps += 1
        self.step_wall += time.monotonic() - t0
        completions.extend(self._flush_completed())
        return completions

    def _plan_next(self):
        with span("te.plan"):
            return self.scheduler.prepare_next()

    def _count_decode(self, k: int, rows: int, live_ctx: int,
                      row_pages: int) -> None:
        """Count one decode dispatch of ``k`` steps over ``rows`` rows whose
        live context sums to ``live_ctx`` tokens at its first step (each row
        grows by one per step), reading ``row_pages`` pages of KV slots per
        step (batch width x block-table width; 0 off the paged pool)."""
        self.decode_steps += k
        self.decode_dispatches += 1
        self.decode_rows += k * rows
        if row_pages:
            self.decode_kv_live += k * live_ctx + rows * k * (k - 1) // 2
            self.decode_kv_slots += k * row_pages * self.ecfg.page_size

    def _count_prefill(self, rows, row_pages: int = 0) -> None:
        """Count one prefill dispatch of ``rows`` — ``(start, n_tokens)``
        each — whose queries score ``row_pages`` pages of key slots in all,
        summed over the queries (0 off the paged pool)."""
        for start, n in rows:
            self.prefill_tokens += n
            if row_pages:
                self.prefill_kv_live += n * start + n * (n + 1) // 2
        self.prefill_kv_slots += row_pages * self.ecfg.page_size

    def run_to_completion(self, max_steps: int = 10000) -> List[Completion]:
        out = []
        for _ in range(max_steps):
            if not self.has_work():
                break
            out.extend(self.step())
        return out

    # ------------------------------------------------------- prefill paths
    def _prefill_legacy(self, entries) -> None:
        """Per-sequence prefill (the pre-§12 path, kept behind
        ``batched_prefill=False`` for parity testing; also the slot family's
        path): one batch-1 dispatch per sequence per chunk."""
        for seq, start, chunk in entries:
            if seq.n_cached != start or seq.seq_id not in self._seqs:
                continue  # stale plan entry (seq preempted/finished)
            if self.family.uses_pages:
                if chunk:
                    self._ensure_pages(seq, seq.n_cached + len(chunk))
                    self.runner.prefill_chunk(seq, chunk)
                    self.prefill_dispatches += 1
                    self._count_prefill([(start, len(chunk))],
                                         len(chunk) * len(seq.pages))
            else:
                if seq.slot is None:
                    if not self.runner.alloc_slot(seq):
                        self.scheduler.ready.appendleft(seq)  # no slot; retry
                        if seq in self.scheduler.prefilling:
                            self.scheduler.prefilling.remove(seq)
                        continue
                    snap_key = seq.extra.pop("_state_restore", None)
                    if snap_key is not None:
                        self.runner.restore_state(seq, self._state_cache[snap_key])
                if chunk:
                    self.runner.prefill_chunk(seq, chunk)
                    self.prefill_dispatches += 1
                    self._count_prefill([(start, len(chunk))])
            self._first_dispatch.setdefault(seq.seq_id, time.monotonic())
            done = seq.n_cached >= len(seq.tokens) - 1
            if done:
                self._on_prefill_done(seq)
                self.scheduler.on_prefill_progress(seq, True)
            else:
                self.scheduler.on_prefill_progress(seq, False)

    def _prefill_batched(self, entries) -> None:
        """Batched ragged prefill (the §12 tentpole): pack EVERY planned
        chunk — all sequences, ragged lengths — into ONE padded pow2-bucketed
        dispatch of the prefill microkernel. A chunk that reaches
        ``n_prompt - 1`` also takes the LAST prompt token as an extension
        row, so the prompt's first generated token is sampled inside this
        same dispatch (after it the sequence satisfies the decode invariant
        ``n_cached == len(tokens) - 1`` exactly like a first decode step had
        run). Padding tokens park on the pool's scratch page at position 0,
        attending only to their own garbage slot."""
        ps = self.ecfg.page_size
        todo = []
        for seq, start, chunk in entries:
            if seq.n_cached != start or seq.seq_id not in self._seqs:
                continue  # stale plan entry (seq preempted/finished)
            if not chunk:
                # single-token prompt or fully prefix-cached: prefill is
                # vacuously done; run the done-transition
                self._first_dispatch.setdefault(seq.seq_id, time.monotonic())
                done = seq.n_cached >= len(seq.tokens) - 1
                if done:
                    self._on_prefill_done(seq)
                self.scheduler.on_prefill_progress(seq, done)
                continue
            ext = (self.ecfg.mode != "prefill"
                   and len(seq.tokens) == seq.n_prompt
                   and start + len(chunk) == seq.n_prompt - 1)
            todo.append((seq, start, list(chunk), ext))
        if not todo:
            return
        for seq, start, chunk, ext in todo:
            self._ensure_pages(seq, start + len(chunk) + (1 if ext else 0))
        packed = []
        for seq, start, chunk, ext in todo:
            # a later entry's page allocation may have PREEMPTED an earlier
            # one (pages released, n_cached reset) — the legacy loop catches
            # that per-entry, the batched pack must re-validate before
            # freezing indices; dropped entries are simply re-planned
            if (seq.seq_id not in self._seqs or seq.n_cached != start
                    or len(seq.pages) * ps
                    < start + len(chunk) + (1 if ext else 0)):
                continue
            packed.append((seq, start, chunk, ext))
        if not packed:
            return
        try:
            scratch = self.pool.scratch_page()
        except OutOfPagesError:
            self._prefill_legacy([(s, st, ch) for s, st, ch, _ in packed])
            return

        if self._prefill_key is None:
            self._key, self._prefill_key = jax.random.split(self._key)
        rows = []
        for seq, start, chunk, ext in packed:
            sp = self.sample_params[seq.seq_id]
            rows.append((seq.pages, start,
                         chunk + ([seq.tokens[-1]] if ext else []),
                         sp.temperature if ext else 0.0,
                         sp.top_p if ext else 1.0))
        ops = self._pack_ragged(rows, scratch)
        t_dispatch = time.monotonic()
        _, toks_dev, self._prefill_key = self.runner.prefill_ragged(
            *ops, self._prefill_key)
        self.prefill_dispatches += 1
        # every query scores every entry's gathered run: Tb x Sb x Pb pages
        self._count_prefill([(r[1], len(r[2])) for r in rows],
                            ops[0].shape[0] * ops[5].size)

        # ---- commit: lengths, extension first-tokens, queue transitions
        toks = None
        if any(ext for _, _, _, ext in packed):
            with span("te.prefill.fetch"):
                toks = np.asarray(toks_dev)
            self.prefill_syncs += 1
        for i, (seq, start, chunk, ext) in enumerate(packed):
            self._first_dispatch.setdefault(seq.seq_id, t_dispatch)
            seq.n_cached = start + len(chunk) + (1 if ext else 0)
            if not ext:
                done = seq.n_cached >= len(seq.tokens) - 1
                if done:
                    self._on_prefill_done(seq)
                self.scheduler.on_prefill_progress(seq, done)
                continue
            tok = int(toks[i])
            seq.tokens.append(tok)
            if self._ttft.get(seq.seq_id, 0.0) == 0.0:
                self._ttft[seq.seq_id] = (time.monotonic()
                                          - self._requests[seq.seq_id].arrival)
            self.scheduler.on_prefill_progress(seq, True)
            sp = self.sample_params[seq.seq_id]
            n_new = len(seq.tokens) - seq.n_prompt
            if (sp.stop_on_eos and tok == EOS_ID) or n_new >= sp.max_new_tokens:
                self._finish(seq)

    def _pack_ragged(self, rows, scratch: int):
        """Pack prefill rows — ``(pages, start, tokens, temperature,
        top_p)`` each — into the padded pow2-bucketed operands of
        ``runner.prefill_ragged`` (host-side, numpy): the flat token stream
        with per-token (position, page, slot, entry), one block-table row
        per entry, each entry's final flat index, and per-entry sampling
        params. Padding tokens park on the ``scratch`` page at position 0
        under entry index Sb, which no entry has."""
        ps = self.ecfg.page_size
        sb = pow2_bucket(max(self.ecfg.max_prefill_seqs, len(rows)))
        pb = pow2_bucket(max(len(pages) for pages, *_ in rows))
        n = sum(len(toks) for _, _, toks, _, _ in rows)
        tb = pow2_bucket(n)
        flat_t = np.zeros((tb,), np.int32)
        flat_p = np.zeros((tb,), np.int32)
        flat_pg = np.full((tb,), scratch, np.int32)
        flat_sl = np.zeros((tb,), np.int32)
        seg = np.full((tb,), sb, np.int32)
        bt_seq = np.full((sb, pb), scratch, np.int32)
        final_idx = np.zeros((sb,), np.int32)
        temps = np.zeros((sb,), np.float32)
        top_ps = np.ones((sb,), np.float32)
        t0 = 0
        for i, (pages, start, toks, temp, top_p) in enumerate(rows):
            t1 = t0 + len(toks)
            pos = np.arange(start, start + len(toks), dtype=np.int32)
            flat_t[t0:t1] = toks
            flat_p[t0:t1] = pos
            flat_pg[t0:t1] = np.asarray(pages, np.int32)[pos // ps]
            flat_sl[t0:t1] = pos % ps
            seg[t0:t1] = i
            bt_seq[i, :len(pages)] = pages
            final_idx[i] = t1 - 1
            temps[i] = temp
            top_ps[i] = top_p
            t0 = t1
        return tuple(jnp.asarray(a) for a in (
            flat_t, flat_p, flat_pg, flat_sl, seg, bt_seq, final_idx, temps,
            top_ps))

    @_executor_safe
    def prompt_logits(self, tokens: List[int]) -> jax.Array:
        """Next-token logits (padded vocab) after ``tokens``, computed by
        the engine's own batched prefill microkernel in ONE dispatch over
        pages borrowed from the pool and freed again: no sequence, prefix
        entry or sampling state changes. This is the engine side of a
        logits-vs-reference check."""
        if self.runner_kind != "paged" or not self.ecfg.batched_prefill:
            raise NotImplementedError(
                f"prompt_logits needs the paged batched prefill path, not "
                f"{self.runner_kind!r}")
        pages = self.pool.alloc(pages_needed(len(tokens),
                                             self.ecfg.page_size))
        try:
            ops = self._pack_ragged([(pages, 0, list(tokens), 0.0, 1.0)],
                                    self.pool.scratch_page())
            logits, _, _ = self.runner.prefill_ragged(
                *ops, jax.random.PRNGKey(0))
        finally:
            self.pool.release(pages)
        return logits[0]

    # ------------------------------------------------------- decode hot loop
    def warmup_decode(self, max_pages: Optional[int] = None,
                      horizons: Optional[List[int]] = None) -> int:
        """Precompile the bucketed fused decode jits (the warmup pass of
        DESIGN.md §8): every power-of-two batch bucket up to
        ``max_decode_batch`` × every page bucket up to ``max_pages`` × every
        power-of-two horizon up to ``decode_horizon``. Serving stays
        recompile-free only for sequences within ``max_pages`` pages — pass
        your workload's per-sequence worst case. The default (an even pool
        split across the decode batch) keeps the grid affordable but a
        single long sequence may exceed it and compile its bigger page
        bucket on first growth. Returns the number of executables
        compiled."""
        if self.runner_kind != "paged" or not self.ecfg.fused_decode:
            return 0
        if max_pages is None:
            max_pages = max(1, self.ecfg.n_pages
                            // max(1, self.ecfg.max_decode_batch))
        return self.runner.warmup_fused(
            pow2s(self.ecfg.max_decode_batch), pow2s(max_pages),
            horizons if horizons is not None
            else pow2s(self.ecfg.decode_horizon))

    def warmup_prefill(self, max_tokens: Optional[int] = None,
                       max_pages: Optional[int] = None) -> int:
        """Precompile the batched ragged prefill jit grid (the prefill twin
        of ``warmup_decode``, DESIGN.md §12): every pow2 token bucket up to
        the step budget — plus one extension token per prompt row — × every
        pow2 page bucket up to ``max_pages``. Serving stays recompile-free
        for sequences within ``max_pages`` pages (same caveat as
        ``warmup_decode``). Returns the number of executables compiled."""
        if self.runner_kind != "paged" or not self.ecfg.batched_prefill:
            return 0
        if max_pages is None:
            max_pages = max(1, self.ecfg.n_pages
                            // max(1, self.ecfg.max_decode_batch))
        cap = ((max_tokens if max_tokens is not None
                else self.ecfg.max_batch_tokens)
               + self.ecfg.max_prefill_seqs)
        return self.runner.warmup_ragged(
            pow2s(cap), pow2s(max_pages),
            pow2_bucket(self.ecfg.max_prefill_seqs))

    def _refilter(self, seqs: List[SequenceState]) -> List[SequenceState]:
        return [s for s in seqs if s.seq_id in self._seqs
                and s in self.scheduler.running]

    def _hot_state(self) -> DecodeHotState:
        if self._hot is None:
            sharding = None
            if self.mesh is not None:
                from repro.launch.sharding import engine_decode_state_sharding
                sharding = engine_decode_state_sharding(self.mesh)
            self._key, sub = jax.random.split(self._key)
            self._hot = DecodeHotState(self.pool, sharding=sharding, key=sub)
        return self._hot

    def _decode_fused_step(self, live: List[SequenceState]) -> bool:
        """One NPU-centric decode iteration (DESIGN.md §8): sync the
        persistent device state (zero dispatches in steady state), run a
        K-step fused decode+sample horizon as ONE dispatch, and fetch the
        PREVIOUS horizon's token block — committed one horizon late so the
        fetch is asynchronous. Returns False when the fused path cannot run
        (page pressure that needs preemption); the caller falls back to the
        legacy per-step path."""
        ps = self.pool.page_size
        for _ in range(3):   # a drain restarts the attempt; converges
            if not live:
                return True
            hlen = {s.seq_id: len(s.tokens) + self._pending.get(s.seq_id, 0)
                    for s in live}
            rem = {s.seq_id: self.sample_params[s.seq_id].max_new_tokens
                   - (hlen[s.seq_id] - s.n_prompt) for s in live}
            if min(rem.values()) < 1:
                # a stop is already sitting in an uncommitted block: commit,
                # let the finish release pages, retry with the survivors
                self._drain_inflight()
                live = self._refilter(live)
                continue
            # horizon the scheduler can prove, floored to a pow2 bucket,
            # then shrunk until the page growth fits WITHOUT preemption
            k = self.scheduler.safe_horizon(live, self.ecfg.decode_horizon,
                                            min(rem.values()))
            k = 1 << (max(1, k).bit_length() - 1)
            free = self.pool.free_page_count() + len(self.pool.reclaimable())
            if self.pool._scratch < 0:
                free -= 1                  # the hot state will pin one page
            while k >= 1:
                need = sum(max(0, pages_needed(hlen[s.seq_id] + k, ps)
                               - len(s.pages)) for s in live)
                if need <= free:
                    break
                k //= 2
            if k < 1:
                self._drain_inflight()
                return False               # legacy path may preempt
            try:
                hot = self._hot_state()
                for s in live:
                    self._ensure_pages_no_preempt(s, hlen[s.seq_id] + k)
            except OutOfPagesError:
                self._drain_inflight()
                return False
            rows2 = [(s.seq_id, len(s.pages)) for s in live]
            if self._inflight and (hot.needs_rebuild(rows2)
                                   or hot.oversized(rows2)):
                # bucket regrow — or a ≥2x shrink that would otherwise pay
                # padded-row compute every step — rebuilds rows from host
                # values, which is only coherent once nothing is pending
                self._drain_inflight()
                live = self._refilter(live)
                continue
            for s in live:
                handle = s.extra.pop("_kv_pending", None)
                if handle is not None:   # first decode of a migrated seq
                    self._import_layerwise(handle, s)
            with span("te.decode.sync"):
                self.host_dispatches += hot.sync(
                    [(s.seq_id, s.pages, len(s.tokens),
                      s.tokens[-1] if s.tokens else 0,
                      self.sample_params[s.seq_id].temperature,
                      self.sample_params[s.seq_id].top_p) for s in live],
                    can_shrink=not self._inflight)
            with span("te.decode.dispatch"):
                toks = self.runner.decode_fused(hot, k)
            self.host_dispatches += 1
            self._count_decode(k, len(live), sum(hlen.values()),
                               hot.bb * hot.pb)
            for s in live:
                self._pending[s.seq_id] = \
                    self._pending.get(s.seq_id, 0) + k
            self._inflight.append(
                (toks, [(hot.slot_of[s.seq_id], s.seq_id) for s in live], k))
            # async scheduling (§4.2): the next plan needs only counts
            if self.ecfg.async_sched:
                self._next_plan = self._plan_next()
            # fetch the PREVIOUS horizon's block — computed behind the
            # dispatch above, so the copy does not stall the device
            while len(self._inflight) > 1:
                self._commit_oldest()
            return True
        return False

    def _decode_slot_fused(self, live: List[SequenceState]) -> bool:
        """Slot-family fused decode+sample (the SlotRunner sampling unifier,
        §12 satellite): ONE dispatch runs the all-slot decode step AND
        in-dispatch sampling through ``sampling.sample_core`` — vs the
        legacy path's decode dispatch + standalone sampler dispatch. Only
        the (n_slots,) sampled-token vector crosses to host; logits never
        move. temps/top_ps are slot-indexed (the cache is live on their
        composition, like the legacy batch-keyed cache)."""
        batch_key = tuple((s.seq_id, s.slot) for s in live)
        if self._sp_cache[0] != batch_key:
            temps = np.zeros((self.ecfg.n_slots,), np.float32)
            top_ps = np.ones((self.ecfg.n_slots,), np.float32)
            for s in live:
                sp = self.sample_params[s.seq_id]
                temps[s.slot] = sp.temperature
                top_ps[s.slot] = sp.top_p
            self._sp_cache = (batch_key, temps, top_ps)
        _, temps, top_ps = self._sp_cache
        with span("te.decode.dispatch"):
            toks_dev, self._key = self.runner.decode_sample(
                live, temps, top_ps, self._key)
        self._count_decode(1, len(live), 0, 0)
        self.host_dispatches += 1
        # async scheduling (§4.2): the next plan needs only counts — prepare
        # it before the blocking token fetch
        if self.ecfg.async_sched:
            self._next_plan = self._plan_next()
        with span("te.decode.fetch"):
            toks = np.asarray(toks_dev)
        self.host_syncs += 1
        self._commit_sampled(live, [int(toks[s.slot]) for s in live])
        return True

    def _commit_oldest(self) -> None:
        """Materialize the oldest in-flight token block and commit it:
        append tokens, record TTFT, and finish sequences whose EOS /
        max_new_tokens stop fired (post-stop tokens — sampled because EOS is
        checked one horizon late — are discarded)."""
        toks_dev, rows, k = self._inflight.popleft()
        if not self._inflight:
            self.host_syncs += 1     # nothing dispatched behind this block
        with span("te.decode.fetch"):
            toks = np.asarray(toks_dev)
        for slot, sid in rows:
            seq = self._seqs.get(sid)
            if seq is None or sid not in self._pending:
                continue   # finished by an earlier block's late EOS
            sp = self.sample_params[sid]
            stopped = False
            for j in range(k):
                tok = int(toks[j, slot])
                seq.tokens.append(tok)
                self._pending[sid] -= 1
                if self._ttft.get(sid, 0.0) == 0.0:
                    self._ttft[sid] = \
                        time.monotonic() - self._requests[sid].arrival
                n_new = len(seq.tokens) - seq.n_prompt
                if (sp.stop_on_eos and tok == EOS_ID) \
                        or n_new >= sp.max_new_tokens:
                    stopped = True
                    break
            seq.n_cached = len(seq.tokens) - 1
            if stopped:
                # releasing pages now is safe even with a later block in
                # flight: pool updates chain by dispatch order, and any new
                # owner of these pages writes (and masks) before it reads
                self._finish(seq)

    def _finish(self, seq: SequenceState) -> None:
        """Complete ``seq``: its ``Completion`` goes out with the next
        flush, and its pages, slot and request state are released."""
        sid = seq.seq_id
        req = self._requests[sid]
        self._completed_buf.append(Completion(
            req_id=sid, tokens=seq.tokens[seq.n_prompt:],
            ttft=self._ttft[sid], finish=time.monotonic(),
            arrival=req.arrival, n_prompt=seq.n_prompt,
            first_dispatch=self._first_dispatch.get(sid, req.arrival)))
        self.scheduler.on_finished(seq)
        self.release_request(sid)

    def _drain_inflight(self) -> None:
        """Commit every in-flight horizon — host state becomes
        authoritative. Required before anything that reads or invalidates
        sequence state: legacy decode, preemption, rebuilds, migration."""
        while self._inflight:
            self._commit_oldest()

    def _flush_completed(self) -> List[Completion]:
        out, self._completed_buf = self._completed_buf, []
        return out

    def _ensure_pages_no_preempt(self, seq: SequenceState,
                                 n_tokens: int) -> None:
        """Fused-path page growth: evicting cached prefixes is fine (the
        RTC does that internally) but preemption is not — it would
        invalidate in-flight horizons — so pressure raises and the caller
        falls back to the legacy path."""
        need = pages_needed(n_tokens, self.pool.page_size) - len(seq.pages)
        for _ in range(max(0, need)):
            seq.pages.append(self.rtc.append_block() if self.rtc
                             else self.pool.alloc(1)[0])

    def _import_layerwise(self, handle, seq: SequenceState) -> None:
        """ROADMAP PR-2 follow-up: per-layer ready events. Each layer chunk
        is scattered into the pool the moment IT lands
        (``MigrationHandle.wait_chunk``), so a migrated sequence's first
        decode starts behind the first chunk instead of the last — the
        scatter of chunk i overlaps the wire time of chunk i+1."""
        chunks = getattr(handle, "chunks", None)
        with span("distflow.transfer"):
            if chunks is None:
                self.runner.import_kv(handle.wait(), seq.pages)
                return
            for i in range(len(chunks)):
                self.runner.import_kv({"chunks": [handle.wait_chunk(i)]},
                                      seq.pages)

    # ---------------------------------------------------------------- PD
    @_executor_safe
    def pop_migratable(self) -> List[str]:
        """P-mode: request ids whose prefill finished and KV is exportable."""
        out = self._prefill_done_buffer
        self._prefill_done_buffer = []
        return out

    @_executor_safe
    def migratable_running(self) -> List[str]:
        """Drain support (DESIGN.md §9 scale-in): request ids currently in
        the decode set whose state can move to another TE right now —
        fully prefilled and not still waiting on an in-flight KV import
        (those become migratable after their first decode)."""
        return [s.seq_id for s in self.scheduler.running
                if "_kv_pending" not in s.extra]

    @_executor_safe
    def export_kv(self, req_id: str, host_gather: bool = False):
        """P-mode: KV of the first n_prompt-1 tokens; the decode TE runs the
        last prompt token as its first decode step (by-req transfer, §4.5).
        Default payload is device-resident sharded arrays (DistFlow v2);
        ``host_gather=True`` keeps the v1 numpy round-trip."""
        # snapshot coherently: commit in-flight horizons so tokens/n_cached
        # (and therefore the exported page run) reflect every sampled token
        self._drain_inflight()
        seq = self._seqs[req_id]
        payload = self.runner.export_kv(seq, host_gather=host_gather) \
            if self.runner_kind == "paged" else self.runner.export_kv(seq)
        payload["req_id"] = req_id
        payload["sampling"] = self.sample_params[req_id]
        payload["arrival"] = self._requests[req_id].arrival
        # a mid-decode sequence (drain migration) already produced its first
        # token here — carry the TTFT so the destination doesn't re-stamp it
        payload["ttft"] = self._ttft.get(req_id, 0.0)
        payload["first_dispatch"] = self._first_dispatch.get(
            req_id, payload["arrival"])
        return payload

    def migrate_out(self, req_id: str, dst: "FlowServe", overlap: bool = True,
                    layer_chunks: int = 4, host_gather: bool = False,
                    keep_prefix: bool = True) -> str:
        """Move a prefilled request's KV/state to decode TE ``dst`` over
        DistFlow and release it here (by-request PD migration, §4.5).

        Paged path (v2): sharded page runs travel device-to-device, priced
        bytes/links per parallel ICI link and resharded in flight when the
        TEs' tp differ. With ``overlap=True`` the import is asynchronous:
        ``dst`` keeps stepping its live batch while the KV chunks stream in,
        and blocks only at its first decode of the migrated sequence.
        ``host_gather=True`` forces the v1 host round-trip (benchmarks).
        Slot (recurrent-state) payloads use the v1 path: their state is
        O(pages) smaller, so the host hop is not a hot path.

        Executor-safety: both endpoints' locks are taken up front in
        canonical (name) order — a drain migrating A→B while the fleet
        steps B concurrently must not deadlock against a B→A handoff."""
        first, second = ((self, dst) if self.name <= dst.name
                         else (dst, self))
        with first._lock, second._lock:
            return self._migrate_out_locked(req_id, dst, overlap,
                                            layer_chunks, host_gather,
                                            keep_prefix)

    def _migrate_out_locked(self, req_id: str, dst: "FlowServe",
                            overlap: bool, layer_chunks: int,
                            host_gather: bool, keep_prefix: bool) -> str:
        # committing in-flight horizons may FINISH the candidate (late EOS /
        # max_new_tokens) and release it — a mid-decode drain migration must
        # treat that as "nothing left to move", not export a ghost
        self._drain_inflight()
        if req_id not in self._seqs:
            return req_id
        # a mid-decode migration (drain) leaves the scheduler's queues NOW:
        # release_request below frees pages/slots but doesn't touch queue
        # membership (finishing seqs already left via on_finished), and a
        # zombie in `running` would keep this TE's has_work true forever
        seq = self._seqs[req_id]
        was_running = seq in self.scheduler.running
        self.scheduler.remove(seq)
        payload = self.export_kv(req_id, host_gather=host_gather)
        try:
            if self.runner_kind != "paged" or host_gather:
                if host_gather and self.runner_kind == "paged":
                    # the v1 path is a genuine host round-trip: price the DtoH
                    # gather (here) and the HtoD pool rewrite (on dst) that the
                    # device-resident path never pays
                    n_kv = _nbytes([payload["k"], payload["v"]])
                    self.distflow.charge(n_kv, "pcie_dram")
                with span("distflow.transfer"):
                    self.distflow.transfer(
                        BufferInfo(owner=self.name, tier="npu",
                                   payload=payload),
                        BufferInfo(owner=dst.name, tier="npu",
                                   deliver=dst.import_request))
                if host_gather and self.runner_kind == "paged":
                    dst.distflow.charge(n_kv, "pcie_dram")
            else:
                kv = {"k": payload.pop("k"), "v": payload.pop("v")}
                with span("distflow.transfer"):
                    handle = self.distflow.transfer_sharded(
                        kv, dst.name, dst_sharding=dst.pool.run_sharding(),
                        src_tp=self.ecfg.tp, dst_tp=dst.ecfg.tp,
                        layer_chunks=layer_chunks)
                payload["kv_handle"] = handle
                dst.import_request(payload)
                if not overlap:
                    dst.finish_pending_imports()
        except (TransferFault, OutOfPagesError):
            # the migration did not land: a TransferFault fires BEFORE any
            # delivery and an OutOfPagesError rolls the destination back
            # before committing state — either way the destination is
            # untouched, so restore this TE's authoritative state (the seq
            # left the run queue above) and let the pump retry/backoff
            # (DESIGN.md §11) instead of stranding a zombie sequence
            if was_running and req_id in self._seqs:
                self.scheduler.admit_running(seq)
            raise
        # injected source crash mid-migration: the destination already
        # imported (the sequence continues there), but this TE dies before
        # acking/cleaning up — recovery must dedupe against the survivor
        if self.fault_plan is not None:
            self.fault_plan.on_migration(self, dst.name)
        # keep_prefix=True preserves the prefill prefix in this TE's RTC so
        # later shared-prefix requests skip the recompute (§4.3)
        self.release_request(req_id, keep_prefix=keep_prefix)
        return req_id

    @_executor_safe
    def finish_pending_imports(self) -> None:
        """D-mode: synchronously drain every deferred KV import (the eager
        complement of the decode-time lazy wait)."""
        for seq in self._seqs.values():
            handle = seq.extra.pop("_kv_pending", None)
            if handle is not None:
                self._import_layerwise(handle, seq)

    @_executor_safe
    def void_pending_imports(self, dead_owners) -> List[Request]:
        """Recovery (DESIGN.md §11): void every in-flight KV import whose
        SOURCE endpoint died. The chunks may reference the dead TE's pool
        arrays, so they are never scattered — the sequence's local state is
        released and its original ``Request`` returned for a prompt-level
        restart on a survivor. Idempotent per sequence (the handle is
        popped), which is what makes recovery dedupe-safe."""
        out: List[Request] = []
        for seq in list(self._seqs.values()):
            handle = seq.extra.get("_kv_pending")
            if handle is None \
                    or getattr(handle, "src_owner", None) not in dead_owners:
                continue
            seq.extra.pop("_kv_pending", None)
            req = self._requests.get(seq.seq_id)
            self.scheduler.remove(seq)
            self.release_request(seq.seq_id, keep_prefix=False)
            if req is not None:
                out.append(req)
        return out

    @_executor_safe
    def release_request(self, req_id: str, keep_prefix: bool = True) -> None:
        seq = self._seqs.pop(req_id, None)
        self._pending.pop(req_id, None)
        self._first_dispatch.pop(req_id, None)
        if self._hot is not None:
            self._hot.evict(req_id)   # a reused id must join fresh, not alias
        if seq is None:
            return
        if self.runner_kind == "paged" and seq.pages:
            own = seq.pages[seq.reused_pages:]
            shared = seq.pages[:seq.reused_pages]
            preserve = self.rtc is not None and keep_prefix and seq.n_cached > 0
            if preserve:
                self.rtc.preserve_prefix(tuple(seq.tokens[:seq.n_cached]),
                                         seq.pages,
                                         ctx_id=self._requests[req_id].ctx_id)
            self.pool.release(own, keep_cached=preserve)
            if shared:
                self.pool.release(shared, keep_cached=True)
        elif self.runner_kind == "slot":
            if self._state_cache is not None and seq.slot is not None:
                key = tuple(seq.tokens[:seq.n_cached])
                if key and len(self._state_cache) < 32:
                    self._state_cache[key] = self.runner.snapshot_state(seq)
            self.runner.free_slot(seq)
        self._requests.pop(req_id, None)

    @_executor_safe
    def import_request(self, payload) -> str:
        """D-mode: accept a migrated (prefilled) request from a prefill TE.
        The next decode step processes the final prompt token. Drain
        migrations (DESIGN.md §9) arrive MID-decode: their tokens extend
        past the prompt and their TTFT already happened on the source TE,
        so it's seeded here instead of re-stamped at the next commit."""
        req = Request(prompt_tokens=payload["tokens"][:payload["n_prompt"]],
                      sampling=payload["sampling"], req_id=payload["req_id"])
        req.arrival = payload["arrival"]
        if (payload.get("ttft", 0.0) > 0.0
                and len(payload["tokens"]) > payload["n_prompt"]):
            self._ttft[req.req_id] = payload["ttft"]
        if "first_dispatch" in payload:
            self._first_dispatch[req.req_id] = payload["first_dispatch"]
        seq = SequenceState(seq_id=req.req_id,
                            tokens=list(payload["tokens"]),
                            n_prompt=payload["n_prompt"],
                            n_cached=payload["n_cached"])
        self._seqs[req.req_id] = seq
        self._requests[req.req_id] = req
        self.sample_params[req.req_id] = req.sampling
        self._sp_cache = (None, None, None)   # same aliasing rule as add
        if self.runner_kind == "paged":
            n_pages = payload.get("n_pages")
            if n_pages is None:
                n_pages = payload["k"].shape[1]
            # allocate through the RTC when present: cached (zero-ref)
            # prefix pages are evicted COHERENTLY with the index, so a
            # decode TE whose pool filled up with preserved prefixes can
            # still admit migrations; true pressure raises BEFORE any
            # sequence state is committed (backpressure, DESIGN.md §9)
            seq.pages = []
            try:
                for _ in range(n_pages):
                    seq.pages.append(self.rtc.append_block() if self.rtc
                                     else self.pool.alloc(1)[0])
            except OutOfPagesError:
                self.pool.release(seq.pages)
                self._seqs.pop(req.req_id, None)
                self._requests.pop(req.req_id, None)
                self.sample_params.pop(req.req_id, None)
                raise
            handle = payload.get("kv_handle")
            if handle is not None:
                # async migration (DistFlow v2): KV chunks are still in
                # flight — decode other sequences freely; the first decode
                # step touching THIS sequence waits and scatters.
                seq.extra["_kv_pending"] = handle
            else:
                self.runner.import_kv(payload, seq.pages)
        else:
            if not self.runner.alloc_slot(seq):
                # same backpressure signal as the paged path's pool.alloc —
                # callers gate migrations on destination capacity
                self._seqs.pop(req.req_id, None)
                self._requests.pop(req.req_id, None)
                self.sample_params.pop(req.req_id, None)
                raise OutOfPagesError(
                    f"decode TE {self.name} has no free slot for migrated "
                    f"request {req.req_id}")
            self.runner.import_kv(payload, seq)
        self.scheduler.admit_running(seq)
        self._drop_idle_plan()
        return req.req_id

    # ---------------------------------------------------------------- internals
    def _ensure_pages(self, seq: SequenceState, n_tokens: int) -> None:
        need = pages_needed(n_tokens, self.pool.page_size) - len(seq.pages)
        for _ in range(max(0, need)):
            while True:
                try:
                    page = (self.rtc.append_block() if self.rtc
                            else self.pool.alloc(1)[0])
                    break
                except OutOfPagesError:
                    victim = self._pick_victim(exclude=seq)
                    if victim is None:
                        raise
                    self._preempt(victim)
            seq.pages.append(page)

    def _pick_victim(self, exclude: SequenceState) -> Optional[SequenceState]:
        """Most recently admitted page-holding seq (decoding, then
        mid-prefill), excluding the requester."""
        for pool in (self.scheduler.running, self.scheduler.prefilling):
            for cand in reversed(pool):
                if cand is not exclude and cand.pages:
                    return cand
        return None

    def _preempt(self, seq: SequenceState) -> None:
        # commit in-flight horizons first: the victim may have uncommitted
        # tokens, and requeue resets state the commits would corrupt
        if self._inflight:
            self._drain_inflight()
            if seq.seq_id not in self._seqs \
                    or (seq not in self.scheduler.running
                        and seq not in self.scheduler.prefilling):
                return   # the drain already finished (released) the victim
        self._pending.pop(seq.seq_id, None)
        if self._hot is not None:
            self._hot.reset()   # victim's device row must not be reused
        own = seq.pages[seq.reused_pages:]
        shared = seq.pages[:seq.reused_pages]
        self.pool.release(own)
        if shared:
            self.pool.release(shared, keep_cached=True)
        seq.reused_pages = 0
        # a not-yet-imported migration is void: its pages were just released
        # and requeue re-prefills from scratch — never scatter the stale run
        seq.extra.pop("_kv_pending", None)
        self.scheduler.requeue(seq)

    def _on_prefill_done(self, seq: SequenceState) -> None:
        """Prefill covered tokens [0, n_prompt-1); the final prompt token is
        processed by the decode path (its KV write + first-token logits),
        either locally (colocated) or on the decode TE (PD-disaggregated)."""
        if self.ecfg.mode == "prefill":
            self._prefill_done_buffer.append(seq.seq_id)
            self._ttft[seq.seq_id] = time.monotonic() - self._requests[seq.seq_id].arrival

    def _commit_tokens(self, seqs: List[SequenceState], logits) -> None:
        """Legacy (non-fused) sampling: the whole decode batch in ONE
        vmapped device dispatch (one PRNG split per step, not one fold_in
        per sequence), then commit tokens / completions on the host. The
        per-batch temperature/top_p arrays are cached keyed on the batch
        composition — join/finish/preempt changes the key, which is the
        invalidation."""
        self._key, sub = jax.random.split(self._key)
        batch_key = tuple(s.seq_id for s in seqs)
        if self._sp_cache[0] != batch_key:
            sps = [self.sample_params[sid] for sid in batch_key]
            self._sp_cache = (
                batch_key,
                np.asarray([sp.temperature for sp in sps], np.float32),
                np.asarray([sp.top_p for sp in sps], np.float32))
        _, temps, top_ps = self._sp_cache
        with span("te.decode.fetch"):
            toks = np.asarray(sample_batch(logits, temps, top_ps, sub,
                                           self.cfg.vocab_size))
        self.sampler_dispatches += 1
        self.host_dispatches += 1
        self.host_syncs += 1             # np.asarray blocks on this step
        self._commit_sampled(seqs, [int(toks[i]) for i in range(len(seqs))])

    def _commit_sampled(self, seqs: List[SequenceState],
                        toks: List[int]) -> None:
        """Commit one freshly sampled token per sequence: append, stamp
        TTFT, and finish on EOS / max_new_tokens."""
        for seq, tok in zip(seqs, toks):
            sp = self.sample_params[seq.seq_id]
            seq.tokens.append(tok)
            if seq.seq_id not in self._ttft or self._ttft[seq.seq_id] == 0.0:
                self._ttft[seq.seq_id] = time.monotonic() - self._requests[seq.seq_id].arrival
            n_new = len(seq.tokens) - seq.n_prompt
            if (sp.stop_on_eos and tok == EOS_ID) or n_new >= sp.max_new_tokens:
                self._finish(seq)

    def _try_state_reuse(self, seq: SequenceState) -> None:
        """SSM prefix cache: longest state checkpoint whose token prefix
        matches the prompt (exact-boundary reuse, DESIGN.md §4). n_cached is
        committed now (the scheduler plans chunks from it); the snapshot is
        restored once a slot is assigned."""
        best_key, best_len = None, 0
        prompt = tuple(seq.tokens[:seq.n_prompt])
        for key in self._state_cache or {}:
            n = len(key)
            if n > best_len and n < len(prompt) and prompt[:n] == key:
                best_key, best_len = key, n
        if best_key is not None:
            seq.extra["_state_restore"] = best_key
            seq.n_cached = best_len

    # stats -------------------------------------------------------------
    def prefix_cache_stats(self) -> Dict[str, int]:
        return dict(self.rtc.stats) if self.rtc else {}

    @_executor_safe
    def load_metrics(self) -> Dict[str, float]:
        """Real load signals for the JE's live TEHandle adapter
        (DESIGN.md §9), replacing the hand-maintained floats:

        * ``queued_prefill_tokens`` — prefill tokens still owed to queued
          sequences (``Scheduler.queued_prefill_tokens``);
        * ``inflight_decode_tokens`` — remaining ``max_new_tokens`` budget
          of every sequence resident in THIS engine (queued or decoding;
          in-flight fused horizons count via ``_pending``). A PD pair's
          sequences live in exactly one endpoint at a time, so summing the
          pair never double-counts;
        * ``horizon_headroom`` — the fused multi-step horizon the scheduler
          can currently prove (§8): a TE decoding K steps per dispatch
          serves its decode budget cheaper, which the JE folds into the
          load comparison;
        * ``n_queued`` / ``n_running`` / ``occupancy`` /
          ``free_page_frac`` — queue-depth and capacity signals.
        """
        sch = self.scheduler
        decode_toks = 0
        running_rem = []
        running = set(id(s) for s in sch.running)
        for seq in self._seqs.values():
            sp = self.sample_params.get(seq.seq_id)
            if sp is None:
                continue
            produced = (max(0, len(seq.tokens) - seq.n_prompt)
                        + self._pending.get(seq.seq_id, 0))
            rem = max(0, sp.max_new_tokens - produced)
            decode_toks += rem
            if id(seq) in running:
                running_rem.append(rem)
        headroom = 1
        if (self.runner_kind == "paged" and self.ecfg.fused_decode
                and running_rem):
            # same proof the fused path runs (§8): the budget term is the
            # batch's min remaining max_new_tokens, not the horizon cap
            headroom = sch.safe_horizon(list(sch.running),
                                        self.ecfg.decode_horizon,
                                        max(1, min(running_rem)))
        return {
            "queued_prefill_tokens": float(sch.queued_prefill_tokens()),
            "inflight_decode_tokens": float(decode_toks),
            "horizon_headroom": float(max(1, headroom)),
            "n_queued": sch.queue_depth(),
            "n_running": len(sch.running),
            "occupancy": sch.occupancy(),
            "free_page_frac": (self.pool.free_page_count() / self.pool.n_pages
                               if self.pool is not None else 1.0),
        }

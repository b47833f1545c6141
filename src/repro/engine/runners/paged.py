"""Paged-KV runner family: attention-only towers (dense / MoE / SWA /
local-global / qk-norm) batching through the engine's paged pool.

``PagedRunner`` is the family facade — it owns the shared executor state
(params + shardings, page pool, per-layer window schedule, compile
counters) and delegates to the two phase microkernels (DESIGN.md §12):

  * ``PagedPrefillRunner`` — chunked prefill. Two shapes of the same
    scatter-then-attend step:
      - ``prefill_ragged``: the WHOLE step's prefill plan — every
        sequence's chunk, ragged lengths and all — packed into ONE padded
        pow2-bucketed dispatch. Flat token stream with per-token
        (page, slot, position, entry) indices, one KV scatter per layer
        across all sequences, one gather per layer of each entry's page
        run (not one per token), the packed queries attending over all
        entries' keys under a segment mask, logits taken only at
        chunk-final rows, and first-token sampling fused in
        (``sample_core`` under a ``lax.cond`` all-greedy shortcut) so a
        completing prompt leaves the dispatch with its first token.
      - ``prefill_chunk``: the legacy batch-1 per-sequence path, kept
        behind ``EngineConfig.batched_prefill=False`` for parity testing.
  * ``PagedDecodeRunner`` — the decode hot loop (DESIGN.md §8): legacy
    per-step jit plus the fused decode+sample K-step horizon.

With ``mesh`` set (EngineConfig.tp > 1) the facade is the TE's SPMD
executor: weights live sharded per launch/sharding.py's policy, the page
pool shards whole KV heads over `model`, and every phase jit pins
in/out shardings so each step is one SPMD program spanning the mesh.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.engine.kv_cache import PagedKVPool
from repro.engine.runners.base import SequenceState
from repro.kernels import ref as KREF
from repro.launch import sharding as SH
from repro.models import layers as L
from repro.models import serving as S
from repro.models import transformer as T
from repro.models.model_factory import ModelBundle


def page_rows(bt, ps: int):
    """Row of each token slot of the pages ``bt`` (..., Pb) within one layer
    of the pool, (..., Pb, ps): slot j of a run holds its token j."""
    return bt[..., None] * ps + jnp.arange(ps, dtype=jnp.int32)


def layer_rows(pool, li: int, rows):
    """The token rows ``rows`` (any shape) of layer ``li`` of the stacked
    pool (layers, pages, ps, Hkv, hd), as (*rows.shape, Hkv, hd): one gather
    from the pool seen as (layers·pages·ps, Hkv, hd), which moves only those
    rows. Both phases read KV this way. On TPU the layer slice
    (``pool[li]``) compiles as a copy of the layer's whole pool, and a gather
    of whole pages (``pool[li, bt]``, or the pool seen as
    (layers·pages, ps, Hkv, hd)) as a split of the whole pool."""
    flat = pool.reshape(-1, *pool.shape[3:])
    return flat[li * pool.shape[1] * pool.shape[2] + rows]


class PagedRunner:
    """Family facade: shared state + phase delegation (public API of the
    pre-registry PagedRunner, preserved verbatim)."""

    def __init__(self, bundle: ModelBundle, params, pool: PagedKVPool,
                 dtype=jnp.float32, mesh=None):
        self.bundle = bundle
        self.cfg = bundle.cfg
        self.pool = pool
        self.dtype = dtype
        self.mesh = mesh
        if mesh is not None:
            self._param_sh = SH.engine_param_shardings(self.cfg, params, mesh)
            self._kv_sh = pool.sharding if pool.sharding is not None \
                else SH.engine_kv_pool_sharding(self.cfg, mesh)
            self._repl = NamedSharding(mesh, P())
            params = jax.device_put(params, self._param_sh)
        self.params = params
        self._wins = [int(w) for w in np.asarray(T.window_schedule(self.cfg))]
        # jit_compiles counts DECODE-path cache misses (bucketed keys ⇒ 0 in
        # steady state after warmup); prefill_jit_compiles is the prefill
        # side of the same accounting — split counters because the engine's
        # warmup passes are per-phase.
        self.jit_compiles = 0
        self.prefill_jit_compiles = 0
        self.prefill = PagedPrefillRunner(self)
        self.decoder = PagedDecodeRunner(self)

    def _mlp(self, p, x):
        """One block's feed-forward half (norm, dense or MoE MLP, optional
        post-norm), the residual left to the caller."""
        cfg = self.cfg
        h = L.apply_norm(x, p["ln2"], cfg.norm)
        if "moe" in p:
            from repro.models import moe as M
            m = M.moe_apply(p["moe"], h, cfg.moe, cfg.mlp_act, groups=1)
        else:
            m = L.mlp_apply(p["mlp"], h, cfg.mlp_act)
        if cfg.post_norms:
            m = L.apply_norm(m, p["ln2_post"], cfg.norm)
        return m

    def _jit_step(self, fn, donate: Tuple[int, ...]):
        """jit with TP shardings pinned when the runner spans a mesh:
        weights keep their placement, token/page operands replicate, and the
        (donated) KV pool stays head-sharded in and out."""
        if self.mesh is None:
            return jax.jit(fn, donate_argnums=donate)
        r, kv = self._repl, self._kv_sh
        return jax.jit(fn, donate_argnums=donate,
                       in_shardings=(self._param_sh, r, r, r, kv, kv),
                       out_shardings=(r, kv, kv))

    # phase delegation — the facade keeps the flat call surface the engine
    # and tests use; each method body lives on exactly one phase runner.
    def decode(self, seqs: List[SequenceState]) -> jax.Array:
        return self.decoder.decode(seqs)

    def decode_fused(self, state, k_steps: int) -> jax.Array:
        return self.decoder.decode_fused(state, k_steps)

    def warmup_fused(self, batch_buckets, page_buckets, horizons) -> int:
        return self.decoder.warmup_fused(batch_buckets, page_buckets,
                                         horizons)

    def prefill_chunk(self, seq: SequenceState, chunk_tokens: List[int]
                      ) -> Optional[jax.Array]:
        return self.prefill.prefill_chunk(seq, chunk_tokens)

    def prefill_ragged(self, *args, **kw):
        return self.prefill.prefill_ragged(*args, **kw)

    def warmup_ragged(self, token_buckets, page_buckets, n_rows: int) -> int:
        return self.prefill.warmup_ragged(token_buckets, page_buckets,
                                          n_rows)

    # ------------------------------------------------------------ PD export
    def export_kv(self, seq: SequenceState, host_gather: bool = False):
        """DistFlow payload for PD-disaggregation: page run + metadata.

        Default (v2): the run stays a sharded ``jax.Array`` pair — one jit'd
        gather, no host round-trip; DistFlow moves/reshards it device-to-
        device. ``host_gather=True`` keeps the v1 numpy path (benchmark
        baseline and DCN/pickle-style escape hatch)."""
        meta = {"tokens": list(seq.tokens), "n_prompt": seq.n_prompt,
                "n_cached": seq.n_cached, "n_pages": len(seq.pages)}
        if host_gather:
            k, v = self.pool.gather(seq.pages)
            return {"k": np.asarray(k), "v": np.asarray(v),
                    "host_gather": True, **meta}
        k, v = self.pool.gather_device(seq.pages)
        return {"k": k, "v": v, **meta}

    def import_kv(self, payload, pages: List[int]) -> None:
        """Install a migrated page run. v2 payloads (device arrays or the
        layer-chunked ``{"chunks": [...]}`` a MigrationHandle.wait() yields)
        go through the donated jit'd scatter; v1 host payloads keep the
        un-jitted full-pool rewrite for benchmark comparison."""
        if payload.get("host_gather"):
            idx = jnp.asarray(pages[:payload["k"].shape[1]], jnp.int32)
            self.pool.k = self.pool.k.at[:, idx].set(jnp.asarray(payload["k"]))
            self.pool.v = self.pool.v.at[:, idx].set(jnp.asarray(payload["v"]))
            self.pool.full_pool_copies += 2          # k and v each rewritten
            return
        chunks = payload.get("chunks")
        if chunks is None:
            chunks = [(0, payload["k"], payload["v"])]
        # the run covers the pages allocated at import time; a lazy (overlap)
        # import may fire after _ensure_pages appended the next decode page
        pages = pages[:chunks[0][1].shape[1]]
        target = self.pool.run_sharding()
        for l0, k_run, v_run in chunks:
            # no-op when DistFlow already resharded onto this mesh; real
            # placement change only for payloads that skipped transfer_sharded
            k_run = jax.device_put(k_run, target)
            v_run = jax.device_put(v_run, target)
            self.pool.scatter_run(pages, k_run, v_run, layer_start=l0)


# ===========================================================================
# Prefill microkernel
# ===========================================================================


class PagedPrefillRunner:
    def __init__(self, rt: PagedRunner):
        self.rt = rt
        self._prefill_fns: Dict[Tuple[int, int], Any] = {}
        # batched ragged prefill jits, keyed (token_bucket, page_bucket,
        # n_rows) — all pow2/static, so a warmed engine never recompiles.
        self._ragged_fns: Dict[Tuple[int, int, int], Any] = {}

    # ------------------------------------------------- legacy per-sequence
    def prefill_chunk(self, seq: SequenceState, chunk_tokens: List[int]
                      ) -> Optional[jax.Array]:
        """Run one prompt chunk; returns last-token logits when this chunk
        completes the prompt (so the engine can sample the first token)."""
        rt = self.rt
        c = len(chunk_tokens)
        start = seq.n_cached
        npages = len(seq.pages)
        fn = self._prefill_fn(c, npages)
        tokens = jnp.asarray(chunk_tokens, jnp.int32)[None]
        bt = jnp.asarray(seq.pages, jnp.int32)[None]
        logits, rt.pool.k, rt.pool.v = fn(
            rt.params, tokens, jnp.asarray([start], jnp.int32), bt,
            rt.pool.k, rt.pool.v)
        seq.n_cached = start + c
        if seq.n_cached >= seq.n_prompt:
            return logits[0]
        return None

    def _prefill_fn(self, c: int, npages: int):
        key = (c, npages)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        self.rt.prefill_jit_compiles += 1
        rt = self.rt
        cfg = rt.cfg
        wins = rt._wins
        ps = rt.pool.page_size

        def run(params, tokens, start, bt, k_pool, v_pool):
            x = T.embed(cfg, params, tokens)                    # (1,C,D)
            positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
            flat = start[0] + jnp.arange(c)
            page = bt[0, flat // ps]
            slot = flat % ps
            total = npages * ps
            kpos_base = jnp.arange(total, dtype=jnp.int32)[None]
            for li in range(cfg.n_layers):
                p = jax.tree.map(lambda a: a[li], params["blocks"])
                h = L.apply_norm(x, p["ln1"], cfg.norm)
                q, k_new, v_new = L.attn_qkv(p["attn"], h, cfg.n_heads,
                                             cfg.n_kv_heads, cfg.head_dim,
                                             positions, cfg.rope_theta, cfg.qk_norm,
                                             rotary_dim=cfg.rotary_dim)
                k_pool = k_pool.at[li, page, slot].set(k_new[0])
                v_pool = v_pool.at[li, page, slot].set(v_new[0])
                k_seq = k_pool[li, bt[0]].reshape(1, total, cfg.n_kv_heads, cfg.head_dim)
                v_seq = v_pool[li, bt[0]].reshape(1, total, cfg.n_kv_heads, cfg.head_dim)
                kpos = jnp.where(kpos_base < (start[0] + c), kpos_base,
                                 T.GLOBAL_WINDOW + 1)
                mask = L.causal_mask(positions, kpos)
                mask &= kpos[:, None, :] > (positions[:, :, None] - wins[li])
                o = L.attention(q, k_seq, v_seq, mask, cfg.attn_logit_softcap)
                x = x + S._post_attn(cfg, p, L.attn_out(p["attn"], o))
                x = x + rt._mlp(p, x)
            logits = T.unembed(cfg, params, x[:, -1:])[:, 0]
            return logits, k_pool, v_pool

        run = rt._jit_step(run, donate=(4, 5))
        self._prefill_fns[key] = run
        return run

    # ------------------------------------------------- batched ragged
    def prefill_ragged(self, tokens, positions, pages, slots, seg, bt_seq,
                       final_idx, temps, top_ps, key):
        """ONE dispatch for the whole step's prefill plan (DESIGN.md §12).

        Packed operands (host-built by the engine):
          tokens/positions/pages/slots  (Tb,)    flat ragged token stream;
                                                 padding tokens point at the
                                                 pool's scratch page, slot 0,
                                                 position 0
          seg                           (Tb,)    entry index of each token;
                                                 Sb (no entry) for padding
          bt_seq                        (Sb, Pb) each entry's page run,
                                                 scratch-padded; padding
                                                 entries are all scratch
          final_idx                     (Sb,)    flat index of each entry's
                                                 chunk-final token
          temps/top_ps                  (Sb,)    per-entry sampling params
        Returns (logits (Sb, Vp), sampled tokens (Sb,), chained PRNG key);
        row i is entries[i]'s chunk-final position. The pools update in
        place (donated)."""
        rt = self.rt
        tb = int(tokens.shape[0])
        sb, pb = (int(d) for d in bt_seq.shape)
        fn = self._ragged_fn(tb, pb, sb)
        logits, toks, key, rt.pool.k, rt.pool.v = fn(
            rt.params, tokens, positions, pages, slots, seg, bt_seq,
            final_idx, temps, top_ps, key, rt.pool.k, rt.pool.v)
        return logits, toks, key

    def _ragged_fn(self, tb: int, pb: int, sb: int):
        key_t = (tb, pb, sb)
        fn = self._ragged_fns.get(key_t)
        if fn is not None:
            return fn
        self.rt.prefill_jit_compiles += 1
        rt = self.rt
        cfg = rt.cfg
        wins = rt._wins
        ps = rt.pool.page_size
        total = pb * ps
        from repro.engine.sampling import greedy_core, sample_core

        def run(params, tokens, positions, page, slot, seg, bt_seq,
                final_idx, temps, top_ps, key, k_pool, v_pool):
            # the packed tokens are one batch row (1, Tb, D) of queries; the
            # keys are every entry's page run gathered once, side by side on
            # one axis of Sb·total keys — key j is entry j // total's token
            # j % total. A token attends only inside its own entry, so
            # sequences never see each other's pages.
            x = T.embed(cfg, params, tokens[None])              # (1,Tb,D)
            pos2 = positions[None]                              # (1,Tb)
            kj = jnp.arange(sb * total, dtype=jnp.int32)
            kpos = kj % total
            # slot j of a gathered run holds its entry's token j; slots past
            # the token's own position are either unwritten or another
            # step's future — one causal mask covers both. Padding tokens
            # (entry Sb) match no key and leave with a uniform, finite mix.
            own = ((seg[:, None] == (kj // total)[None])
                   & (kpos[None] <= positions[:, None]))[None]  # (1,Tb,K)
            # key j's row in one layer of the pool: each entry's pages, side
            # by side on one key axis (1, Sb·total, Hkv, hd)
            run_rows = page_rows(bt_seq, ps).reshape(-1)
            for li in range(cfg.n_layers):
                p = jax.tree.map(lambda a: a[li], params["blocks"])
                with jax.named_scope("attention"):
                    h = L.apply_norm(x, p["ln1"], cfg.norm)
                    q, k_new, v_new = L.attn_qkv(
                        p["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                        cfg.head_dim, pos2, cfg.rope_theta, cfg.qk_norm,
                        rotary_dim=cfg.rotary_dim)
                # ONE scatter of the whole step's fresh KV, all sequences at
                # once; chunk-internal attention works because the scatter
                # precedes the gather within the layer.
                with jax.named_scope("kv_scatter"):
                    k_pool = k_pool.at[li, page, slot].set(k_new[0])
                    v_pool = v_pool.at[li, page, slot].set(v_new[0])
                with jax.named_scope("kv_gather"):
                    k_seq = layer_rows(k_pool, li, run_rows)[None]
                    v_seq = layer_rows(v_pool, li, run_rows)[None]
                with jax.named_scope("attention"):
                    mask = own & (kpos[None, None]
                                  > (pos2[:, :, None] - wins[li]))
                    o = L.attention(q, k_seq, v_seq, mask,
                                    cfg.attn_logit_softcap)
                    x = x + S._post_attn(cfg, p, L.attn_out(p["attn"], o))
                with jax.named_scope("mlp"):
                    x = x + rt._mlp(p, x)
            # unembed ONLY the chunk-final rows — (Sb, Vp), not (Tb, Vp)
            with jax.named_scope("lm_head"):
                logits = T.unembed(cfg, params, x[:, final_idx])[0]
            with jax.named_scope("sample"):
                key, sub = jax.random.split(key)
                all_greedy = jnp.all(temps <= 0.0)
                toks = jax.lax.cond(
                    all_greedy,
                    lambda lg: greedy_core(lg, cfg.vocab_size),
                    lambda lg: sample_core(lg, temps, top_ps, sub,
                                           cfg.vocab_size),
                    logits)
            return logits, toks, key, k_pool, v_pool

        if rt.mesh is None:
            fn = jax.jit(run, donate_argnums=(11, 12))
        else:
            r, kv = rt._repl, rt._kv_sh
            fn = jax.jit(run, donate_argnums=(11, 12),
                         in_shardings=(rt._param_sh, r, r, r, r, r, r, r, r,
                                       r, r, kv, kv),
                         out_shardings=(r, r, r, kv, kv))
        self._ragged_fns[key_t] = fn
        return fn

    def warmup_ragged(self, token_buckets, page_buckets, n_rows: int) -> int:
        """Precompile the batched-prefill jit grid ahead of serving (the
        prefill twin of ``warmup_fused``): every token bucket × every page
        bucket at the engine's fixed row count. Runs each combination once
        against a transient throwaway KV pool (donated and chained
        call-to-call). Returns the number of executables compiled."""
        rt = self.rt
        k = jnp.zeros_like(rt.pool.k)
        v = jnp.zeros_like(rt.pool.v)
        if rt.mesh is not None:
            k = jax.device_put(k, rt._kv_sh)
            v = jax.device_put(v, rt._kv_sh)
        key = jax.random.PRNGKey(0)
        n = 0
        for tb in sorted(set(token_buckets)):
            for pb in sorted(set(page_buckets)):
                fn = self._ragged_fn(tb, pb, n_rows)
                _, _, key, k, v = fn(
                    rt.params, jnp.zeros((tb,), jnp.int32),
                    jnp.zeros((tb,), jnp.int32), jnp.zeros((tb,), jnp.int32),
                    jnp.zeros((tb,), jnp.int32),
                    jnp.zeros((tb,), jnp.int32),
                    jnp.zeros((n_rows, pb), jnp.int32),
                    jnp.zeros((n_rows,), jnp.int32),
                    jnp.zeros((n_rows,), jnp.float32),
                    jnp.ones((n_rows,), jnp.float32), key, k, v)
                n += 1
        jax.block_until_ready(k)
        return n


# ===========================================================================
# Decode microkernel (the hot loop of DESIGN.md §8)
# ===========================================================================


class PagedDecodeRunner:
    def __init__(self, rt: PagedRunner):
        self.rt = rt
        self._decode_fns: Dict[int, Any] = {}
        # bucketed fused decode+sample jits, keyed (k_steps, batch_bucket,
        # page_bucket); misses count into the facade's jit_compiles.
        self._fused_fns: Dict[Tuple[int, int, int], Any] = {}

    def decode(self, seqs: List[SequenceState]) -> jax.Array:
        """One decode step for a batch of sequences. The new token of each
        seq is seqs[i].tokens[-1]; KV is written at position len(tokens)-1.
        Caller must have appended a page if needed."""
        rt = self.rt
        b = len(seqs)
        maxp = max(len(s.pages) for s in seqs)
        bt = np.zeros((b, maxp), np.int32)
        for i, s in enumerate(seqs):
            bt[i, :len(s.pages)] = s.pages
        tokens = jnp.asarray([s.tokens[-1] for s in seqs], jnp.int32)
        lengths = jnp.asarray([len(s.tokens) for s in seqs], jnp.int32)
        fn = self._decode_fn(maxp)
        logits, rt.pool.k, rt.pool.v = fn(
            rt.params, tokens, jnp.asarray(bt), lengths, rt.pool.k, rt.pool.v)
        for s in seqs:
            s.n_cached = len(s.tokens)
        return logits

    def _decode_body(self, params, tokens, bt, lengths, k_pool, v_pool):
        """Traceable single decode step: (B,) token ids + device metadata →
        (B, Vp) logits + updated pools. Shared by the legacy per-step jit and
        the fused decode+sample horizon (DESIGN.md §8)."""
        rt = self.rt
        cfg = rt.cfg
        wins = rt._wins
        ps = rt.pool.page_size
        b = tokens.shape[0]
        x = T.embed(cfg, params, tokens[:, None])
        pos = (lengths - 1)[:, None]
        bidx = jnp.arange(b)
        page = bt[bidx, (lengths - 1) // ps]
        slot = (lengths - 1) % ps
        # each row's page run in one layer of the pool: (B, Pb·ps)
        rows = page_rows(bt, ps).reshape(b, -1)
        for li in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[li], params["blocks"])
            with jax.named_scope("attention"):
                h = L.apply_norm(x, p["ln1"], cfg.norm)
                q, k_new, v_new = L.attn_qkv(p["attn"], h, cfg.n_heads,
                                             cfg.n_kv_heads, cfg.head_dim,
                                             pos, cfg.rope_theta, cfg.qk_norm,
                                             rotary_dim=cfg.rotary_dim)
            with jax.named_scope("kv_scatter"):
                k_pool = k_pool.at[li, page, slot].set(k_new[:, 0])
                v_pool = v_pool.at[li, page, slot].set(v_new[:, 0])
            with jax.named_scope("kv_gather"):
                k_run = layer_rows(k_pool, li, rows)   # (B, Pb·ps, Hkv, hd)
                v_run = layer_rows(v_pool, li, rows)
            win = wins[li] if wins[li] < T.GLOBAL_WINDOW else None
            with jax.named_scope("attention"):
                o = KREF.decode_attention_ref(q[:, 0], k_run, v_run, lengths,
                                              softcap=cfg.attn_logit_softcap,
                                              window=win)
                x = x + S._post_attn(cfg, p,
                                     L.attn_out(p["attn"], o[:, None]))
            with jax.named_scope("mlp"):
                x = x + rt._mlp(p, x)
        with jax.named_scope("lm_head"):
            logits = T.unembed(cfg, params, x)[:, 0]
        return logits, k_pool, v_pool

    def _decode_fn(self, maxp: int):
        if maxp in self._decode_fns:
            return self._decode_fns[maxp]
        self.rt.jit_compiles += 1

        def step(params, tokens, bt, lengths, k_pool, v_pool):
            return self._decode_body(params, tokens, bt, lengths,
                                     k_pool, v_pool)

        step = self.rt._jit_step(step, donate=(4, 5))
        self._decode_fns[maxp] = step
        return step

    # ---------------------------------------------- fused decode hot loop
    def decode_fused(self, state, k_steps: int) -> jax.Array:
        """NPU-centric decode (DESIGN.md §8): run ``k_steps`` decode+sample
        iterations as ONE device dispatch over the persistent device-resident
        batch state. Sampling is fused into the step — logits never leave the
        device — and the carried metadata (lengths, last tokens, PRNG key)
        advances in-jit, so the host's only job is this dispatch. Returns the
        (k_steps, batch_bucket) sampled-token block WITHOUT materializing it
        on the host; the caller fetches it asynchronously a horizon later."""
        rt = self.rt
        fn = self._decode_fused_fn(k_steps, state.bb, state.pb)
        (toks, state.key, state.last_tok, state.lengths,
         rt.pool.k, rt.pool.v) = fn(
            rt.params, state.bt, state.active, state.temps, state.top_ps,
            state.key, state.last_tok, state.lengths,
            rt.pool.k, rt.pool.v)
        return toks

    def _decode_fused_fn(self, k_steps: int, bb: int, pb: int):
        key_t = (k_steps, bb, pb)
        fn = self._fused_fns.get(key_t)
        if fn is not None:
            return fn
        rt = self.rt
        rt.jit_compiles += 1
        cfg = rt.cfg
        from repro.engine.sampling import greedy_core, sample_core

        def horizon(params, bt, active, temps, top_ps, key, last_tok,
                    lengths, k_pool, v_pool):
            act = active.astype(jnp.int32)
            # the all-greedy shortcut v1's sample_batch takes on the host,
            # moved in-jit: one traced predicate selects pure argmax over the
            # full top-p pipeline at runtime (per-row results are identical)
            all_greedy = jnp.all(temps <= 0.0)

            def one(carry, _):
                key, last_tok, lengths, k_pool, v_pool = carry
                logits, k_pool, v_pool = self._decode_body(
                    params, last_tok, bt, lengths, k_pool, v_pool)
                with jax.named_scope("sample"):
                    key, sub = jax.random.split(key)
                    toks = jax.lax.cond(
                        all_greedy,
                        lambda lg: greedy_core(lg, cfg.vocab_size),
                        lambda lg: sample_core(lg, temps, top_ps, sub,
                                               cfg.vocab_size),
                        logits)
                    # padding rows: freeze token + length so their KV write
                    # stays parked at slot 0 of the pool's scratch page
                    toks = jnp.where(active, toks, last_tok)
                return (key, toks, lengths + act, k_pool, v_pool), toks

            (key, last_tok, lengths, k_pool, v_pool), toks = jax.lax.scan(
                one, (key, last_tok, lengths, k_pool, v_pool), None,
                length=k_steps)
            return toks, key, last_tok, lengths, k_pool, v_pool

        if rt.mesh is None:
            fn = jax.jit(horizon, donate_argnums=(8, 9))
        else:
            r, kv = rt._repl, rt._kv_sh
            fn = jax.jit(horizon, donate_argnums=(8, 9),
                         in_shardings=(rt._param_sh, r, r, r, r, r, r, r,
                                       kv, kv),
                         out_shardings=(r, r, r, r, kv, kv))
        self._fused_fns[key_t] = fn
        return fn

    def warmup_fused(self, batch_buckets, page_buckets, horizons) -> int:
        """Precompile the bucketed fused decode jits ahead of serving (the
        §4.2 warmup pass) so steady state never recompiles. Runs each bucket
        combination once against a transient throwaway KV pool (donated and
        chained call-to-call, so the warmup never touches live pages and
        peaks at one extra pool copy). Returns the number of executables
        compiled. Note: ``jit.lower().compile()`` does NOT seed the dispatch
        cache, so the warmup must really call."""
        rt = self.rt
        k = jnp.zeros_like(rt.pool.k)
        v = jnp.zeros_like(rt.pool.v)
        if rt.mesh is not None:
            k = jax.device_put(k, rt._kv_sh)
            v = jax.device_put(v, rt._kv_sh)
        key = jax.random.PRNGKey(0)
        n = 0
        for k_steps in sorted(set(horizons)):
            for bb in sorted(set(batch_buckets)):
                for pb in sorted(set(page_buckets)):
                    fn = self._decode_fused_fn(k_steps, bb, pb)
                    _, key, _, _, k, v = fn(
                        rt.params, jnp.zeros((bb, pb), jnp.int32),
                        jnp.zeros((bb,), bool), jnp.zeros((bb,), jnp.float32),
                        jnp.ones((bb,), jnp.float32), key,
                        jnp.zeros((bb,), jnp.int32),
                        jnp.ones((bb,), jnp.int32), k, v)
                    n += 1
        jax.block_until_ready(k)
        return n

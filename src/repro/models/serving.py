"""Serving entry points: cache init, prefill, decode_step — per family.

Sharding-aware design decisions (DESIGN.md §5):
  * decode uses *naive* masked attention (Sq=1 ⇒ scores are (B,H,1,Sk),
    tiny) so GSPMD can shard the KV sequence dim over the `model` axis and
    lower softmax/contraction reductions to psum — the flash-decode pattern
    expressed at the XLA level.
  * prefill computes attention from the *fresh* k/v activations (flash,
    chunk-scanned, no sharding conflict) and scatters k/v into the
    seq-sharded cache as a separate pure data movement.
  * caches are dense stacked arrays: (L, B, Smax, Hkv, hd). Engine-level
    paging (RTC block tables) maps pages onto these slots.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.transformer import GLOBAL_WINDOW

Cache = Dict[str, Any]


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def attn_layer_count(cfg: ModelConfig) -> int:
    return sum(1 for k in cfg.layer_kinds() if k.startswith("attn"))


def ring_len(cfg: ModelConfig, align: int = 256) -> int:
    """Ring-buffer length for windowed archs: window + one aligned chunk of
    slack (so the mesh can shard the ring dim 256 ways)."""
    assert cfg.window is not None
    return ((cfg.window + align + align - 1) // align) * align


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, ring: bool = False) -> Cache:
    """Dense cache sized for `max_len` tokens of context. With ``ring=True``
    (windowed archs only) the attention cache is a rotating buffer of
    ring_len(cfg) slots — decode memory ∝ window, not context (§Perf)."""
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    cache: Cache = {"length": jnp.zeros((batch,), jnp.int32)}
    la = attn_layer_count(cfg)
    s_alloc = max_len
    if ring:
        assert cfg.attn_kind in ("swa", "hybrid_rglru"), cfg.attn_kind
        s_alloc = min(max_len, ring_len(cfg))
    if la:
        cache["k"] = jnp.zeros((la, batch, s_alloc, hkv, hd), dtype)
        cache["v"] = jnp.zeros((la, batch, s_alloc, hkv, hd), dtype)
    if cfg.attn_kind == "rwkv":
        h = cfg.d_model // cfg.rwkv.head_dim
        cache["state"] = jnp.zeros((cfg.n_layers, batch, h, cfg.rwkv.head_dim,
                                    cfg.rwkv.head_dim), jnp.float32)
        cache["last_tm"] = jnp.zeros((cfg.n_layers, batch, cfg.d_model), dtype)
        cache["last_cm"] = jnp.zeros((cfg.n_layers, batch, cfg.d_model), dtype)
    if cfg.attn_kind == "hybrid_rglru":
        nr = cfg.n_layers - la
        w, cw = cfg.rglru.lru_width, cfg.rglru.conv1d_width
        cache["h"] = jnp.zeros((nr, batch, w), jnp.float32)
        cache["conv"] = jnp.zeros((nr, batch, cw - 1, w), dtype)
    if cfg.vision is not None:
        nc = len(cfg.cross_attn_layers())
        cache["cross_k"] = jnp.zeros((nc, batch, cfg.vision.n_patches, hkv, hd), dtype)
        cache["cross_v"] = jnp.zeros((nc, batch, cfg.vision.n_patches, hkv, hd), dtype)
    if cfg.encoder is not None:
        cache["cross_k"] = jnp.zeros((cfg.n_layers, batch, cfg.encoder.n_frames, hkv, hd), dtype)
        cache["cross_v"] = jnp.zeros((cfg.n_layers, batch, cfg.encoder.n_frames, hkv, hd), dtype)
    return cache


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill(cfg: ModelConfig, params, tokens: jax.Array, cache: Cache,
            vision_embeds: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None,
            attn_impl: str = "auto", n_valid=None) -> Tuple[jax.Array, Cache]:
    """Process a prompt chunk starting at cache['length'] (per sequence).
    Returns (last-position logits (B, Vp), updated cache).

    Attention within the chunk sees fresh activations (flash path); tokens
    also attend to previously cached context when cache['length'] > 0 by
    concatenating the cached prefix (engine chunked-prefill path).

    ``n_valid`` (static or traced scalar, bucketed-prefill contract,
    DESIGN.md §12): only the first n_valid of the s chunk positions are
    real. Pad positions are masked out of attention by position sentinels,
    made exact identity steps in the recurrences, and excluded from the
    length/logits bookkeeping — their (garbage) KV writes land in slots a
    later chunk overwrites or decode masks by length.
    """
    b, s = tokens.shape
    nv = s if n_valid is None else n_valid
    start = cache["length"]
    positions = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    x = T.embed(cfg, params, tokens)
    new_cache = dict(cache)
    kinds = cfg.layer_kinds()
    wins = T.window_schedule(cfg)

    if cfg.vision is not None and vision_embeds is not None:
        _fill_cross_cache(cfg, params["cross_blocks"], vision_embeds, new_cache)
    if cfg.encoder is not None:
        assert frames is not None
        mem = T.encode(cfg, params, frames, attn_impl)
        _fill_cross_cache(cfg, params["cross_blocks"], mem, new_cache)

    if cfg.attn_kind == "rwkv":
        x, new_cache = _rwkv_prefill(cfg, params, x, new_cache, n_valid)
    elif cfg.attn_kind == "hybrid_rglru":
        x, new_cache = _rglru_prefill(cfg, params, x, positions, new_cache,
                                      attn_impl, n_valid)
    else:
        # pads need no explicit masking here: their cache writes sit at
        # positions > every real query (causally excluded) and are
        # overwritten by the next chunk / masked by `length` at decode.
        x, new_cache = _attn_prefill(cfg, params, x, positions, new_cache,
                                     attn_impl, wins, kinds)

    new_cache["length"] = start + nv
    if n_valid is None:
        x_last = x[:, -1:, :]
    else:
        x_last = jax.lax.dynamic_slice_in_dim(x, nv - 1, 1, axis=1)
    logits = T.unembed(cfg, params, x_last)
    return logits[:, 0, :], new_cache


def _cache_kpos(cache_len_total: int, start: jax.Array, s: int) -> jax.Array:
    """Positions of cache slots: slot i holds token i; unwritten slots get a
    huge sentinel so masks exclude them."""
    idx = jnp.arange(cache_len_total, dtype=jnp.int32)[None, :]
    valid = idx < (start + s)[:, None]
    return jnp.where(valid, idx, GLOBAL_WINDOW + 1)


def _write_kv(cache_k, cache_v, li, k_new, v_new, start):
    b, s = k_new.shape[0], k_new.shape[1]
    bidx = jnp.arange(b)[:, None]
    widx = start[:, None] + jnp.arange(s)[None, :]
    return (cache_k.at[li, bidx, widx].set(k_new),
            cache_v.at[li, bidx, widx].set(v_new))


def _attn_prefill(cfg, params, x, positions, cache, attn_impl, wins, kinds):
    start = cache["length"]
    b, s, _ = x.shape
    has_prefix = cache["k"].shape[2] > 0
    is_vlm = cfg.vision is not None
    is_encdec = cfg.encoder is not None
    cross_layers = set(cfg.cross_attn_layers()) if is_vlm else set()

    smax = cache["k"].shape[2]
    # Engine chunked-prefill (small caches) attends jointly over the cache
    # after writing fresh k/v — exact continuation semantics. The large
    # single-shot path (dry-run 32k prefill, start==0) attends over the
    # fresh activations with the flash scan and writes the cache separately.
    joint_over_cache = smax <= 2048

    def run_block(i_attn, p, h, win, ck, cv):
        hh = L.apply_norm(h, p["ln1"], cfg.norm)
        q, k_new, v_new = L.attn_qkv(p["attn"], hh, cfg.n_heads, cfg.n_kv_heads,
                                     cfg.head_dim, positions, cfg.rope_theta, cfg.qk_norm,
                                     rotary_dim=cfg.rotary_dim)
        ck, cv = _write_kv(ck, cv, i_attn, k_new, v_new, start)
        if joint_over_cache:
            k_pos = _cache_kpos(smax, start, h.shape[1])
            mask = L.causal_mask(positions, k_pos)
            mask &= k_pos[:, None, :] > (positions[:, :, None] - win)
            o = L.attention(q, ck[i_attn], cv[i_attn], mask, cfg.attn_logit_softcap)
        else:
            o = T._self_attention(cfg, q, k_new, v_new, positions, positions,
                                  win, attn_impl, False)
        h = h + _post_attn(cfg, p, L.attn_out(p["attn"], o))
        hh = L.apply_norm(h, p["ln2"], cfg.norm)
        if "moe" in p:
            from repro.models import moe as M
            m = M.moe_apply(p["moe"], hh, cfg.moe, cfg.mlp_act, groups=T._moe_groups(hh))
        else:
            m = L.mlp_apply(p["mlp"], hh, cfg.mlp_act)
        if cfg.post_norms:
            m = L.apply_norm(m, p["ln2_post"], cfg.norm)
        return h + m, ck, cv

    ck, cv = cache["k"], cache["v"]
    i_attn = 0
    for i, kind in enumerate(kinds):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        x, ck, cv = run_block(i_attn, p, x, wins[i], ck, cv)
        if is_vlm and i in cross_layers:
            ci = sorted(cross_layers).index(i)
            pc = jax.tree.map(lambda a: a[ci], params["cross_blocks"])
            x = T.cross_block_apply(cfg, pc, x, cache["cross_k"][ci],
                                    cache["cross_v"][ci], gated=True)
        if is_encdec:
            pc = jax.tree.map(lambda a: a[i], params["cross_blocks"])
            x = T.cross_block_apply(cfg, pc, x, cache["cross_k"][i],
                                    cache["cross_v"][i], gated=False)
        i_attn += 1
    cache = dict(cache)
    cache["k"], cache["v"] = ck, cv
    return x, cache


def _post_attn(cfg, p, o):
    if cfg.post_norms:
        o = L.apply_norm(o, p["ln1_post"], cfg.norm)
    return o


def _rwkv_prefill(cfg, params, x, cache, n_valid=None):
    from repro.models.transformer import rwkv_block_apply

    def body(carry, xs):
        h = carry
        p, st, ltm, lcm = xs
        h, st, ltm, lcm = rwkv_block_apply(cfg, p, h, st, ltm, lcm, chunked=True,
                                           n_valid=n_valid)
        return h, (st, ltm, lcm)

    x, (st, ltm, lcm) = jax.lax.scan(body, x, (params["blocks"], cache["state"],
                                               cache["last_tm"], cache["last_cm"]))
    cache = dict(cache)
    cache["state"], cache["last_tm"], cache["last_cm"] = st, ltm, lcm
    return x, cache


def _rglru_prefill(cfg, params, x, positions, cache, attn_impl, n_valid=None):
    from repro.models.transformer import attn_block_apply, rglru_block_apply
    start = cache["length"]
    ck, cv = cache.get("k"), cache.get("v")
    hs, convs = cache["h"], cache["conv"]
    new_h, new_conv = [], []
    ri = ai = 0
    for kind in cfg.layer_kinds():
        if kind == "rglru":
            p = params["rglru_blocks"][ri]
            x, h_i, c_i = T.rglru_block_apply(cfg, p, x, hs[ri], convs[ri],
                                              n_valid=n_valid)
            new_h.append(h_i)
            new_conv.append(c_i)
            ri += 1
        else:
            p = params["attn_blocks"][ai]
            win = jnp.int32(cfg.window or GLOBAL_WINDOW)
            smax = ck.shape[2]
            hh = L.apply_norm(x, p["ln1"], cfg.norm)
            q, k_new, v_new = L.attn_qkv(p["attn"], hh, cfg.n_heads, cfg.n_kv_heads,
                                         cfg.head_dim, positions, cfg.rope_theta, cfg.qk_norm,
                                         rotary_dim=cfg.rotary_dim)
            ck, cv = _write_kv(ck, cv, ai, k_new, v_new, start)
            if smax <= 2048:  # joint continuation over cache (engine path)
                k_pos = _cache_kpos(smax, start, x.shape[1])
                mask = L.causal_mask(positions, k_pos)
                mask &= k_pos[:, None, :] > (positions[:, :, None] - win)
                o = L.attention(q, ck[ai], cv[ai], mask, cfg.attn_logit_softcap)
            else:
                o = T._self_attention(cfg, q, k_new, v_new, positions, positions,
                                      win, attn_impl, False)
            x = x + L.attn_out(p["attn"], o)
            hh = L.apply_norm(x, p["ln2"], cfg.norm)
            x = x + L.mlp_apply(p["mlp"], hh, cfg.mlp_act)
            ai += 1
    cache = dict(cache)
    cache["h"] = jnp.stack(new_h)
    cache["conv"] = jnp.stack(new_conv)
    if ck is not None:
        cache["k"], cache["v"] = ck, cv
    return x, cache


def _fill_cross_cache(cfg, cross_blocks, mem, cache):
    n = cache["cross_k"].shape[0]
    ks, vs = [], []
    for i in range(n):
        pa = jax.tree.map(lambda a: a[i], cross_blocks)["attn"]
        k, v = T.memory_kv(cfg, pa, mem)
        ks.append(k)
        vs.append(v)
    cache["cross_k"] = jnp.stack(ks)
    cache["cross_v"] = jnp.stack(vs)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def decode_step(cfg: ModelConfig, params, token: jax.Array, cache: Cache
                ) -> Tuple[jax.Array, Cache]:
    """One decode step for every family. token: (B,) int32.
    Returns (logits (B, Vp), updated cache)."""
    b = token.shape[0]
    lengths = cache["length"]
    positions = lengths[:, None]                                  # (B,1)
    x = T.embed(cfg, params, token[:, None])
    wins = T.window_schedule(cfg)
    kinds = cfg.layer_kinds()
    new_cache = dict(cache)

    if cfg.attn_kind == "rwkv":
        x, new_cache = _rwkv_decode(cfg, params, x, new_cache)
    elif cfg.attn_kind == "hybrid_rglru":
        x, new_cache = _rglru_decode(cfg, params, x, positions, new_cache)
    elif cfg.vision is not None:
        x, new_cache = _attn_decode(cfg, params, x, positions, new_cache,
                                    wins, vlm=True)
    elif cfg.encoder is not None:
        x, new_cache = _attn_decode(cfg, params, x, positions, new_cache,
                                    wins, encdec=True)
    else:
        x, new_cache = _attn_decode(cfg, params, x, positions, new_cache, wins)

    new_cache["length"] = lengths + 1
    logits = T.unembed(cfg, params, x)
    return logits[:, 0, :], new_cache


def _decode_attention(cfg, p, x, positions, k_cache, v_cache, win, lengths):
    """One self-attention block in decode mode (naive masked attention over
    the seq-sharded cache — flash-decode via GSPMD reductions).

    With perf_flags.windowed_decode and a static sliding window covering
    every attn layer (SWA / hybrid archs), only the trailing `window+1`
    cache positions are read — bytes ∝ window instead of context length.
    """
    from repro.models import perf_flags as PF
    b = x.shape[0]
    smax = k_cache.shape[1]
    h = L.apply_norm(x, p["ln1"], cfg.norm)
    q, k_new, v_new = L.attn_qkv(p["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.head_dim, positions, cfg.rope_theta, cfg.qk_norm,
                                 rotary_dim=cfg.rotary_dim)
    bidx = jnp.arange(b)
    static_win = cfg.window if cfg.attn_kind in ("swa", "hybrid_rglru") else None
    ring = (static_win is not None and smax <= ring_len(cfg)
            and cfg.window < 2 ** 20)
    if ring:
        # rotating buffer: slot j holds the newest token t ≡ j (mod ring).
        # No gathers: the whole (small) ring is attended, masks do the rest,
        # and the ring dim itself shards over the mesh.
        lm1 = lengths  # position of the incoming token
        k_cache = k_cache.at[bidx, lm1 % smax].set(k_new[:, 0])
        v_cache = v_cache.at[bidx, lm1 % smax].set(v_new[:, 0])
        j = jnp.arange(smax, dtype=jnp.int32)[None, :]
        delta = jnp.mod(lm1[:, None] - j, smax)            # ≥ 0
        t = lm1[:, None] - delta                           # token id per slot
        k_pos = jnp.where(t >= 0, t, GLOBAL_WINDOW + 1)
        mask = L.causal_mask(positions, k_pos)
        mask &= k_pos[:, None, :] > (positions[:, :, None] - win)
        o = L.attention(q, k_cache, v_cache, mask, cfg.attn_logit_softcap)
        o = L.attn_out(p["attn"], o)
        return o, k_cache, v_cache

    k_cache = k_cache.at[bidx, lengths].set(k_new[:, 0])
    v_cache = v_cache.at[bidx, lengths].set(v_new[:, 0])

    if (PF.get().windowed_decode and static_win is not None
            and static_win + 1 < smax):
        span = static_win + 1
        start = jnp.clip(lengths - static_win, 0, smax - span)
        cols = start[:, None] + jnp.arange(span)[None, :]          # (B, span)
        k_r = k_cache[bidx[:, None], cols]                         # (B, span, Hkv, hd)
        v_r = v_cache[bidx[:, None], cols]
        k_pos = jnp.where(cols <= lengths[:, None], cols, GLOBAL_WINDOW + 1)
    else:
        k_r, v_r = k_cache, v_cache
        k_pos = jnp.where(jnp.arange(smax)[None, :] <= lengths[:, None],
                          jnp.arange(smax, dtype=jnp.int32)[None, :],
                          GLOBAL_WINDOW + 1)
    mask = L.causal_mask(positions, k_pos)
    mask &= k_pos[:, None, :] > (positions[:, :, None] - win)
    o = L.attention(q, k_r, v_r, mask, cfg.attn_logit_softcap)
    o = L.attn_out(p["attn"], o)
    return o, k_cache, v_cache


def _attn_decode(cfg, params, x, positions, cache, wins, vlm=False, encdec=False):
    lengths = cache["length"]
    if vlm or encdec:
        # unrolled (cross blocks interleave); still cheap at Sq=1.
        return _attn_decode_unrolled(cfg, params, x, positions, cache, wins,
                                     vlm=vlm, encdec=encdec)

    def body(h, xs):
        p, kc, vc, w = xs
        o, kc, vc = _decode_attention(cfg, p, h, positions, kc, vc, w, lengths)
        h = h + _post_attn(cfg, p, o)
        hh = L.apply_norm(h, p["ln2"], cfg.norm)
        if "moe" in p:
            from repro.models import moe as M
            m = M.moe_apply(p["moe"], hh, cfg.moe, cfg.mlp_act, groups=1)
        else:
            m = L.mlp_apply(p["mlp"], hh, cfg.mlp_act)
        if cfg.post_norms:
            m = L.apply_norm(m, p["ln2_post"], cfg.norm)
        return h + m, (kc, vc)

    x, (ck, cv) = jax.lax.scan(body, x, (params["blocks"], cache["k"],
                                         cache["v"], wins))
    cache = dict(cache)
    cache["k"], cache["v"] = ck, cv
    return x, cache


def _attn_decode_unrolled(cfg, params, x, positions, cache, wins, vlm, encdec):
    lengths = cache["length"]
    ck, cv = cache["k"], cache["v"]
    cross_layers = sorted(cfg.cross_attn_layers()) if vlm else []
    for i, kind in enumerate(cfg.layer_kinds()):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        o, k_i, v_i = _decode_attention(cfg, p, x, positions, ck[i], cv[i],
                                        wins[i], lengths)
        ck, cv = ck.at[i].set(k_i), cv.at[i].set(v_i)
        x = x + _post_attn(cfg, p, o)
        h = L.apply_norm(x, p["ln2"], cfg.norm)
        if "moe" in p:
            from repro.models import moe as M
            m = M.moe_apply(p["moe"], h, cfg.moe, cfg.mlp_act, groups=1)
        else:
            m = L.mlp_apply(p["mlp"], h, cfg.mlp_act)
        if cfg.post_norms:
            m = L.apply_norm(m, p["ln2_post"], cfg.norm)
        x = x + m
        if vlm and i in cross_layers:
            ci = cross_layers.index(i)
            pc = jax.tree.map(lambda a: a[ci], params["cross_blocks"])
            x = T.cross_block_apply(cfg, pc, x, cache["cross_k"][ci],
                                    cache["cross_v"][ci], gated=True)
        if encdec:
            pc = jax.tree.map(lambda a: a[i], params["cross_blocks"])
            x = T.cross_block_apply(cfg, pc, x, cache["cross_k"][i],
                                    cache["cross_v"][i], gated=False)
    cache = dict(cache)
    cache["k"], cache["v"] = ck, cv
    return x, cache


def _rwkv_decode(cfg, params, x, cache):
    def body(h, xs):
        p, st, ltm, lcm = xs
        h, st, ltm, lcm = T.rwkv_block_apply(cfg, p, h, st, ltm, lcm, chunked=False)
        return h, (st, ltm, lcm)

    x, (st, ltm, lcm) = jax.lax.scan(body, x, (params["blocks"], cache["state"],
                                               cache["last_tm"], cache["last_cm"]))
    cache = dict(cache)
    cache["state"], cache["last_tm"], cache["last_cm"] = st, ltm, lcm
    return x, cache


def _rglru_decode(cfg, params, x, positions, cache):
    lengths = cache["length"]
    ck, cv = cache["k"], cache["v"]
    hs, convs = cache["h"], cache["conv"]
    new_h, new_conv = [], []
    ri = ai = 0
    for kind in cfg.layer_kinds():
        if kind == "rglru":
            p = params["rglru_blocks"][ri]
            x, h_i, c_i = T.rglru_block_apply(cfg, p, x, hs[ri], convs[ri], decode=True)
            new_h.append(h_i)
            new_conv.append(c_i)
            ri += 1
        else:
            p = params["attn_blocks"][ai]
            o, k_i, v_i = _decode_attention(cfg, p, x, positions, ck[ai], cv[ai],
                                            jnp.int32(cfg.window or GLOBAL_WINDOW), lengths)
            ck, cv = ck.at[ai].set(k_i), cv.at[ai].set(v_i)
            x = x + o
            h = L.apply_norm(x, p["ln2"], cfg.norm)
            x = x + L.mlp_apply(p["mlp"], h, cfg.mlp_act)
            ai += 1
    cache = dict(cache)
    cache["h"], cache["conv"] = jnp.stack(new_h), jnp.stack(new_conv)
    cache["k"], cache["v"] = ck, cv
    return x, cache

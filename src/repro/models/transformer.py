"""Config-driven model zoo: init / forward / prefill / decode for every
assigned architecture family.

Layer execution strategies (see DESIGN.md §5 and the scan-cost note):
  * homogeneous towers (dense / moe / rwkv / enc-dec / vlm groups) run under
    ``jax.lax.scan`` over stacked layer params → compact HLO, fast compiles,
    correct memory analysis on the production mesh;
  * heterogeneous towers (recurrentgemma's rglru/attn mix) unroll a static
    Python loop (26 cheap blocks);
  * exact-FLOPs cost probes call the per-block functions directly
    (``*_block_apply``) with unrolled chunked attention.

Caches are dense stacked arrays (engine-level paging lives in
repro.engine.kv_cache; the compiled step always sees gathered pages).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as G
from repro.models import rwkv6 as R

GLOBAL_WINDOW = 2 ** 30  # sentinel "window" meaning full causal attention


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _init_attn_block(cfg: ModelConfig, key, dtype, cross: bool = False) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": L.init_norm(cfg.d_model, cfg.norm),
        "attn": L.init_attn(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                            cfg.head_dim, cfg.qk_norm, dtype),
    }
    if cross:
        p["gate_attn"] = jnp.zeros((), jnp.float32)
        p["gate_mlp"] = jnp.zeros((), jnp.float32)
    p["ln2"] = L.init_norm(cfg.d_model, cfg.norm)
    if cfg.moe is not None and not cross:
        p["moe"] = M.init_moe(k2, cfg.d_model, cfg.moe, cfg.mlp_act, dtype)
    else:
        p["mlp"] = L.init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype)
    if cfg.post_norms:
        p["ln1_post"] = L.init_norm(cfg.d_model, cfg.norm)
        p["ln2_post"] = L.init_norm(cfg.d_model, cfg.norm)
    return p


def _init_rwkv_block(cfg: ModelConfig, key, dtype) -> dict:
    return {
        "ln1": L.init_norm(cfg.d_model, cfg.norm),
        "tm": R.init_rwkv_block(key, cfg.d_model, cfg.d_ff, cfg.rwkv.head_dim, dtype),
        "ln2": L.init_norm(cfg.d_model, cfg.norm),
    }


def _init_rglru_block(cfg: ModelConfig, key, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.init_norm(cfg.d_model, cfg.norm),
        "rec": G.init_rglru_block(k1, cfg.d_model, cfg.rglru.lru_width,
                                  cfg.rglru.conv1d_width, dtype),
        "ln2": L.init_norm(cfg.d_model, cfg.norm),
        "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype),
    }


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 8)
    vp = cfg.padded_vocab
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(keys[-1], (vp, cfg.d_model)) * 0.02).astype(dtype),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(keys[-2], (cfg.d_model, vp))
                             * (1.0 / math.sqrt(cfg.d_model))).astype(dtype)

    kinds = cfg.layer_kinds()
    if cfg.attn_kind == "rwkv":
        params["blocks"] = _stack([_init_rwkv_block(cfg, keys[i], dtype)
                                   for i in range(cfg.n_layers)])
    elif cfg.attn_kind == "hybrid_rglru":
        # heterogeneous: keep per-kind lists (unrolled execution)
        params["rglru_blocks"] = [
            _init_rglru_block(cfg, keys[i], dtype)
            for i, k in enumerate(kinds) if k == "rglru"]
        params["attn_blocks"] = [
            _init_attn_block(cfg, keys[i], dtype)
            for i, k in enumerate(kinds) if k.startswith("attn")]
    else:
        params["blocks"] = _stack([_init_attn_block(cfg, keys[i], dtype)
                                   for i in range(cfg.n_layers)])

    if cfg.vision is not None:
        n_cross = len(cfg.cross_attn_layers())
        params["cross_blocks"] = _stack([
            _init_attn_block(cfg, keys[-3 - i], dtype, cross=True)
            for i in range(n_cross)])
    if cfg.encoder is not None:
        ek = jax.random.split(keys[-4], cfg.encoder.n_layers)
        params["enc_blocks"] = _stack([_init_attn_block(cfg, ek[i], dtype)
                                       for i in range(cfg.encoder.n_layers)])
        params["enc_final_norm"] = L.init_norm(cfg.d_model, cfg.norm)
        dk = jax.random.split(keys[-5], cfg.n_layers)
        params["cross_blocks"] = _stack([
            {"ln": L.init_norm(cfg.d_model, cfg.norm),
             "attn": L.init_attn(dk[i], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.head_dim, False, dtype)}
            for i in range(cfg.n_layers)])
    return params


# ---------------------------------------------------------------------------
# Per-layer window schedule (traced-friendly: GLOBAL_WINDOW == full causal)
# ---------------------------------------------------------------------------


def window_schedule(cfg: ModelConfig) -> jnp.ndarray:
    win = []
    for kind in cfg.layer_kinds():
        if kind == "attn_local":
            win.append(cfg.window or GLOBAL_WINDOW)
        else:
            win.append(GLOBAL_WINDOW)
    return jnp.asarray(win, jnp.int32)


# ---------------------------------------------------------------------------
# Block applications (shared by scan bodies, unrolled loops and cost probes)
# ---------------------------------------------------------------------------


def _self_attention(cfg: ModelConfig, q, k, v, q_pos, k_pos, window,
                    attn_impl: str, unroll_probe: bool, causal=True):
    from repro.models import actsharding as AS
    from repro.models import perf_flags as PF
    sk = k.shape[1]
    if attn_impl == "naive" or (attn_impl == "auto" and sk <= 2048):
        mask = L.causal_mask(q_pos, k_pos) if causal else (k_pos < GLOBAL_WINDOW)[:, None, :]
        if causal:
            mask &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
        return L.attention(q, k, v, mask, cfg.attn_logit_softcap)
    # context-parallel attention for archs whose head count cannot shard on
    # the 16-way model axis (granite 24H, recurrentgemma 10H): shard the
    # query rows over `model`, keep K/V whole — each shard computes 1/16 of
    # the rows instead of replicating the full quadratic (§Perf).
    q = AS.constrain_tag(q, "attn_q_seq")
    # banded SWA attention: FLOPs/bytes ∝ S·(window+Q) instead of S²
    if (PF.get().banded_swa_prefill and cfg.attn_kind == "swa" and causal
            and cfg.window is not None and cfg.window + 1024 < sk
            and q.shape[1] == sk):
        o = L.banded_swa_attention(q, k, v, cfg.window,
                                   softcap=cfg.attn_logit_softcap)
    else:
        chunk = min(1024, sk)
        o = L.flash_attention(q, k, v, q_pos, k_pos, window=window,
                              softcap=cfg.attn_logit_softcap, chunk=chunk,
                              unroll=unroll_probe, causal=causal)
    return AS.constrain_tag(o, "attn_q_seq")


def attn_block_apply(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
                     window, kv_write=None, attn_impl: str = "auto",
                     unroll_probe: bool = False) -> Tuple[jax.Array, Optional[tuple]]:
    """One pre-norm attention block. ``kv_write``: None for self-contained
    fwd (train/prefill-from-scratch); or a dict {'k','v','k_pos','write_at'}
    carrying the dense cache slice for this layer (decode / cached prefill).
    Returns (x_out, updated (k, v) or None)."""
    h = L.apply_norm(x, p["ln1"], cfg.norm)
    q, k_new, v_new = L.attn_qkv(p["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.head_dim, positions, cfg.rope_theta, cfg.qk_norm,
                                 rotary_dim=cfg.rotary_dim)
    updated = None
    if kv_write is None:
        k_all, v_all, k_pos = k_new, v_new, positions
    else:
        bidx = jnp.arange(x.shape[0])[:, None]
        widx = kv_write["write_at"][:, None] + jnp.arange(x.shape[1])[None, :]
        k_all = kv_write["k"].at[bidx, widx].set(k_new)
        v_all = kv_write["v"].at[bidx, widx].set(v_new)
        k_pos = kv_write["k_pos"]
        updated = (k_all, v_all)
    o = _self_attention(cfg, q, k_all, v_all, positions, k_pos,
                        window, attn_impl, unroll_probe)
    o = L.attn_out(p["attn"], o)
    if cfg.post_norms:
        o = L.apply_norm(o, p["ln1_post"], cfg.norm)
    x = x + o
    h = L.apply_norm(x, p["ln2"], cfg.norm)
    if "moe" in p:
        m = M.moe_apply(p["moe"], h, cfg.moe, cfg.mlp_act, groups=_moe_groups(h))
    else:
        m = L.mlp_apply(p["mlp"], h, cfg.mlp_act)
    if cfg.post_norms:
        m = L.apply_norm(m, p["ln2_post"], cfg.norm)
    return x + m, updated


def _moe_groups(h: jax.Array) -> int:
    # one capacity group per data shard; tokens per group must stay >= 64
    t = h.shape[0] * h.shape[1]
    for g in (16, 8, 4, 2, 1):
        if t % g == 0 and t // g >= 64:
            return g
    return 1


def cross_block_apply(cfg: ModelConfig, p: dict, x: jax.Array, mem_k: jax.Array,
                      mem_v: jax.Array, gated: bool) -> jax.Array:
    """Cross-attention block. mem_k/mem_v: (B, P, Hkv, hd) precomputed from
    the modality memory (vision patches / encoder output)."""
    from repro.models import actsharding as AS
    h = L.apply_norm(x, p["ln1"] if "ln1" in p else p["ln"], cfg.norm)
    b, s, _ = h.shape
    q = jnp.einsum("bsd,dh->bsh", h, p["attn"]["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    q = AS.constrain_tag(q, "cross_q")
    o = L.attention(q, mem_k, mem_v, None, cfg.attn_logit_softcap)
    o = AS.constrain_tag(o, "cross_q")
    o = L.attn_out(p["attn"], o)
    if gated:
        x = x + jnp.tanh(p["gate_attn"]).astype(o.dtype) * o
        h = L.apply_norm(x, p["ln2"], cfg.norm)
        m = L.mlp_apply(p["mlp"], h, cfg.mlp_act)
        x = x + jnp.tanh(p["gate_mlp"]).astype(m.dtype) * m
        return x
    return x + o


def memory_kv(cfg: ModelConfig, p_attn: dict, mem: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Project modality memory into (k, v) for cross attention (no rope)."""
    b, s, _ = mem.shape
    k = jnp.einsum("bsd,dh->bsh", mem, p_attn["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = jnp.einsum("bsd,dh->bsh", mem, p_attn["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return k, v


def rwkv_block_apply(cfg: ModelConfig, p: dict, x, state, last_tm, last_cm,
                     chunked=True, unroll_probe=False, n_valid=None):
    h = L.apply_norm(x, p["ln1"], cfg.norm)
    y, state, last_tm = R.rwkv_time_mix(p["tm"], h, cfg.rwkv.head_dim, state, last_tm,
                                        chunked=chunked, unroll=unroll_probe,
                                        n_valid=n_valid)
    x = x + y
    h = L.apply_norm(x, p["ln2"], cfg.norm)
    y, last_cm = R.rwkv_channel_mix(p["tm"], h, last_cm, n_valid=n_valid)
    return x + y, state, last_tm, last_cm


def rglru_block_apply(cfg: ModelConfig, p: dict, x, h0, conv_state, decode=False,
                      n_valid=None):
    h = L.apply_norm(x, p["ln1"], cfg.norm)
    y, h0, conv_state = G.rglru_block_apply(p["rec"], h, h0, conv_state, decode=decode,
                                            n_valid=n_valid)
    x = x + y
    h = L.apply_norm(x, p["ln2"], cfg.norm)
    return x + L.mlp_apply(p["mlp"], h, cfg.mlp_act), h0, conv_state


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed(cfg: ModelConfig, params, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(cfg: ModelConfig, params, x: jax.Array) -> jax.Array:
    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    if cfg.final_logit_softcap is not None:
        logits = (cfg.final_logit_softcap
                  * jnp.tanh(logits.astype(jnp.float32) / cfg.final_logit_softcap)).astype(logits.dtype)
    return logits


# ---------------------------------------------------------------------------
# Forward (teacher-forced, used by train / prefill-from-scratch)
# ---------------------------------------------------------------------------


def _maybe_remat(fn, remat: bool):
    """Per-layer rematerialization: wraps a scan body (or an unrolled block)
    so bwd saves only the layer-boundary activations — which the launcher
    additionally sequence-shards via actsharding.constrain."""
    return jax.checkpoint(fn) if remat else fn


def forward(cfg: ModelConfig, params, tokens: jax.Array,
            positions: Optional[jax.Array] = None,
            vision_embeds: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None,
            attn_impl: str = "auto", scan_layers: bool = True,
            unroll_probe: bool = False, remat: bool = False) -> jax.Array:
    """tokens: (B, S) -> logits (B, S, padded_vocab)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = embed(cfg, params, tokens)

    if cfg.attn_kind == "rwkv":
        x = _rwkv_tower(cfg, params, x, scan_layers, unroll_probe, remat)
    elif cfg.attn_kind == "hybrid_rglru":
        x = _rglru_tower(cfg, params, x, positions, attn_impl, unroll_probe, remat)
    elif cfg.vision is not None and vision_embeds is not None:
        x = _vlm_tower(cfg, params, x, positions, vision_embeds, attn_impl,
                       unroll_probe, remat)
    elif cfg.encoder is not None:
        assert frames is not None, "enc-dec model needs frame embeddings"
        mem = encode(cfg, params, frames, attn_impl, scan_layers, unroll_probe, remat)
        x = _decoder_tower(cfg, params, x, positions, mem, attn_impl,
                           scan_layers, unroll_probe, remat)
    else:
        x = _dense_tower(cfg, params, x, positions, attn_impl, scan_layers,
                         unroll_probe, remat)
    return unembed(cfg, params, x)


def _dense_tower(cfg, params, x, positions, attn_impl, scan_layers,
                 unroll_probe, remat=False):
    from repro.models import actsharding as AS
    wins = window_schedule(cfg)

    def block(h, p, w):
        p = AS.constrain_block(p, "blocks")
        h, _ = attn_block_apply(cfg, p, h, positions, w, None, attn_impl,
                                unroll_probe)
        return AS.constrain(h)

    blk = _maybe_remat(block, remat)
    if not scan_layers:
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[i], params["blocks"])
            x = blk(x, p, wins[i])
        return x

    def body(h, xs):
        p, w = xs
        return blk(h, p, w), None

    x, _ = jax.lax.scan(body, AS.constrain(x), (params["blocks"], wins))
    return x


def _rwkv_tower(cfg, params, x, scan_layers, unroll_probe, remat=False):
    from repro.models import actsharding as AS
    b, s, d = x.shape
    h = cfg.d_model // cfg.rwkv.head_dim
    zeros_state = jnp.zeros((b, h, cfg.rwkv.head_dim, cfg.rwkv.head_dim), jnp.float32)
    zeros_last = jnp.zeros((b, d), x.dtype)

    def block(hid, p):
        p = AS.constrain_block(p, "blocks")
        hid, _, _, _ = rwkv_block_apply(cfg, p, hid, zeros_state, zeros_last,
                                        zeros_last, True, unroll_probe)
        return AS.constrain(hid)

    blk = _maybe_remat(block, remat)

    def body(hid, p):
        return blk(hid, p), None

    if scan_layers:
        x, _ = jax.lax.scan(body, AS.constrain(x), params["blocks"])
    else:
        for i in range(cfg.n_layers):
            x = blk(x, jax.tree.map(lambda a: a[i], params["blocks"]))
    return x


def _rglru_tower(cfg, params, x, positions, attn_impl, unroll_probe, remat=False):
    from repro.models import actsharding as AS
    b = x.shape[0]
    w = cfg.rglru.lru_width
    cw = cfg.rglru.conv1d_width

    def rec_block(h, p):
        h, _, _ = rglru_block_apply(cfg, p, h,
                                    jnp.zeros((b, w), jnp.float32),
                                    jnp.zeros((b, cw - 1, w), x.dtype))
        return AS.constrain(h)

    def att_block(h, p):
        h, _ = attn_block_apply(cfg, p, h, positions,
                                jnp.int32(cfg.window or GLOBAL_WINDOW),
                                None, attn_impl, unroll_probe)
        return AS.constrain(h)

    rec_blk = _maybe_remat(rec_block, remat)
    att_blk = _maybe_remat(att_block, remat)
    kinds = cfg.layer_kinds()
    period = cfg.rglru.recurrent_per_attn + 1
    n_periods = len(kinds) // period
    # Scan over the repeating [rglru × r, attn] period: keeps the HLO (and
    # SPMD-partitioner time) ~n_periods× smaller than a full unroll, which
    # is what makes the 512-device train compile tractable. The remainder
    # layers (26 = 8·3 + 2 for recurrentgemma) run unrolled.
    scan_ok = (n_periods >= 2
               and all(kinds[i * period: (i + 1) * period]
                       == kinds[:period] for i in range(n_periods)))
    if not scan_ok:
        ri = ai = 0
        for kind in kinds:
            if kind == "rglru":
                x = rec_blk(x, params["rglru_blocks"][ri])
                ri += 1
            else:
                x = att_blk(x, params["attn_blocks"][ai])
                ai += 1
        return x

    n_rec_in = sum(1 for k in kinds[:period] if k == "rglru")
    rec_stack = _stack([_stack(params["rglru_blocks"][i * n_rec_in:
                                                      (i + 1) * n_rec_in])
                        for i in range(n_periods)])
    att_stack = _stack(params["attn_blocks"][:n_periods])

    def period_body(h, xs):
        p_rec, p_att = xs
        for j in range(n_rec_in):
            h = rec_blk(h, jax.tree.map(lambda a: a[j], p_rec))
        h = att_blk(h, p_att)
        return h, None

    x, _ = jax.lax.scan(period_body, x, (rec_stack, att_stack))
    # remainder layers (beyond the last full period)
    ri = n_periods * n_rec_in
    ai = n_periods
    for kind in kinds[n_periods * period:]:
        if kind == "rglru":
            x = rec_blk(x, params["rglru_blocks"][ri])
            ri += 1
        else:
            x = att_blk(x, params["attn_blocks"][ai])
            ai += 1
    return x


def _vlm_tower(cfg, params, x, positions, vision_embeds, attn_impl,
               unroll_probe, remat=False):
    from repro.models import actsharding as AS
    every = cfg.vision.cross_attn_every
    n_groups = cfg.n_layers // every
    wins = window_schedule(cfg).reshape(n_groups, every)
    grouped = jax.tree.map(
        lambda a: a.reshape(n_groups, every, *a.shape[1:]), params["blocks"])

    def group(h, pg, wg, pc):
        def inner(h2, xs2):
            p, wv = xs2
            p = AS.constrain_block(p, "blocks")
            h2, _ = attn_block_apply(cfg, p, h2, positions, wv, None,
                                     attn_impl, unroll_probe)
            return AS.constrain(h2), None

        h, _ = jax.lax.scan(inner, h, (pg, wg))
        mk, mv = memory_kv(cfg, pc["attn"], vision_embeds)
        h = cross_block_apply(cfg, pc, h, mk, mv, gated=True)
        return AS.constrain(h)

    grp = _maybe_remat(group, remat)

    def group_body(h, xs):
        pg, wg, pc = xs
        return grp(h, pg, wg, pc), None

    x, _ = jax.lax.scan(group_body, AS.constrain(x),
                        (grouped, wins, params["cross_blocks"]))
    return x


def encode(cfg, params, frames, attn_impl="auto", scan_layers=True,
           unroll_probe=False, remat=False):
    """Bidirectional encoder over precomputed frame embeddings (B, F, D)."""
    from repro.models import actsharding as AS
    b, f, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(f, dtype=jnp.int32)[None], (b, f))
    x = frames

    def block(h, p):
        p = AS.constrain_block(p, "enc_blocks")
        hh = L.apply_norm(h, p["ln1"], cfg.norm)
        q, k, v = L.attn_qkv(p["attn"], hh, cfg.n_heads, cfg.n_kv_heads,
                             cfg.head_dim, pos, cfg.rope_theta, cfg.qk_norm,
                             rotary_dim=cfg.rotary_dim)
        o = _self_attention(cfg, q, k, v, pos, pos, None, attn_impl,
                            unroll_probe, causal=False)
        h = h + L.attn_out(p["attn"], o)
        hh = L.apply_norm(h, p["ln2"], cfg.norm)
        return AS.constrain(h + L.mlp_apply(p["mlp"], hh, cfg.mlp_act))

    blk = _maybe_remat(block, remat)

    def body(h, p):
        return blk(h, p), None

    if scan_layers:
        x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    else:
        for i in range(cfg.encoder.n_layers):
            x = blk(x, jax.tree.map(lambda a: a[i], params["enc_blocks"]))
    return L.apply_norm(x, params["enc_final_norm"], cfg.norm)


def _decoder_tower(cfg, params, x, positions, mem, attn_impl, scan_layers,
                   unroll_probe, remat=False):
    from repro.models import actsharding as AS

    def block(h, p, pc):
        p = AS.constrain_block(p, "blocks")
        h, _ = attn_block_apply(cfg, p, h, positions, GLOBAL_WINDOW, None,
                                attn_impl, unroll_probe)
        mk, mv = memory_kv(cfg, pc["attn"], mem)
        return AS.constrain(cross_block_apply(cfg, pc, h, mk, mv, gated=False))

    blk = _maybe_remat(block, remat)

    def body(h, xs):
        p, pc = xs
        return blk(h, p, pc), None

    if scan_layers:
        x, _ = jax.lax.scan(body, AS.constrain(x),
                            (params["blocks"], params["cross_blocks"]))
    else:
        for i in range(cfg.n_layers):
            x = blk(x, jax.tree.map(lambda a: a[i], params["blocks"]),
                    jax.tree.map(lambda a: a[i], params["cross_blocks"]))
    return x

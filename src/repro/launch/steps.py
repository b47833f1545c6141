"""Compiled step functions for the dry-run / launchers.

One builder per step kind; each returns ``(fn, example_inputs)`` where
example_inputs are ShapeDtypeStructs (nothing is allocated):

  * build_train_step   — fwd(remat, scan) → grads → AdamW
  * build_prefill_step — prompt → (last logits, full KV cache) [scan towers]
  * build_decode_step  — serving.decode_step (one token, cache in/out)

The prefill builders here produce the cache *without* scatter writes
(from-scratch prefill: cache = stacked fresh K/V), which is both the
efficient artifact and what the PD-disaggregated prefill TE ships to
decode TEs.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import layers as L
from repro.models import serving as S
from repro.models import transformer as T
from repro.models.model_factory import ModelBundle, cross_entropy, get_model
from repro.training.optimizer import OptimizerConfig, adamw_update, init_opt_state

FLASH_CHUNK = 1024


def example_batch(cfg: ModelConfig, shape: ShapeConfig, dtype=jnp.bfloat16
                  ) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    out: Dict[str, Any] = {}
    if shape.kind == "train":
        out["tokens"] = sds((b, s), jnp.int32)
        out["targets"] = sds((b, s), jnp.int32)
        out["mask"] = sds((b, s), jnp.float32)
    elif shape.kind == "prefill":
        out["tokens"] = sds((b, s), jnp.int32)
    else:  # decode
        out["token"] = sds((b,), jnp.int32)
    if cfg.vision is not None and shape.kind != "decode":
        out["vision_embeds"] = sds((b, cfg.vision.n_patches, cfg.d_model), dtype)
    if cfg.encoder is not None and shape.kind != "decode":
        out["frames"] = sds((b, cfg.encoder.n_frames, cfg.d_model), dtype)
    return out


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------


def default_microbatches(cfg: ModelConfig) -> int:
    """Gradient-accumulation factor for the 1M-token train_4k step.
    MoE dispatch (top_k·capacity_factor ≈ 2.5× token duplication) and VLM
    cross-attention memories need smaller live activation sets."""
    if cfg.vision is not None:
        return 8      # cross-attn score tensors over 1601 patches
    if cfg.moe is not None:
        return 4
    return 1


def build_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig = OptimizerConfig(),
                     remat: bool = True, attn_impl: str = "flash",
                     microbatches: int = 1):
    def loss_fn(params, tokens, targets, mask, extra):
        # remat is applied per layer inside the towers (scan-body
        # checkpointing), NOT around the whole forward — wrapping the whole
        # forward still saves every scan iteration's residuals.
        logits = T.forward(cfg, params, tokens, attn_impl=attn_impl,
                           scan_layers=True, remat=remat, **extra)
        return cross_entropy(logits, targets, mask, cfg.vocab_size)

    def train_step(params, opt_state, tokens, targets, mask, extra):
        if microbatches > 1:
            def resh(a):
                return a.reshape((microbatches, a.shape[0] // microbatches)
                                 + a.shape[1:])

            mb = (resh(tokens), resh(targets), resh(mask),
                  {k: resh(v) for k, v in extra.items()})

            def body(carry, xs):
                g_acc, l_acc = carry
                t, y, m, ex = xs
                l, g = jax.value_and_grad(loss_fn)(params, t, y, m, ex)
                g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                 g_acc, g)
                return (g, l_acc + l), None

            zero = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(body, (zero, 0.0), mb)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets,
                                                      mask, extra)
        params, opt_state, metrics = adamw_update(opt_cfg, params, grads, opt_state)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# Prefill (from scratch, scan towers, cache as stacked fresh K/V)
# ---------------------------------------------------------------------------


def build_prefill_step(cfg: ModelConfig, attn_impl: str = "flash"):
    if cfg.attn_kind == "rwkv":
        return _prefill_rwkv(cfg)
    if cfg.attn_kind == "hybrid_rglru":
        return _prefill_hybrid(cfg, attn_impl)
    if cfg.encoder is not None:
        return _prefill_encdec(cfg, attn_impl)
    if cfg.vision is not None:
        return _prefill_vlm(cfg, attn_impl)
    return _prefill_dense(cfg, attn_impl)


def _attn_for_prefill(cfg, q, k, v, positions, win, attn_impl):
    from repro.models import actsharding as AS
    from repro.models import perf_flags as PF
    s = q.shape[1]
    if attn_impl == "naive" or s <= 2048:
        mask = L.causal_mask(positions, positions)
        mask &= positions[:, None, :] > (positions[:, :, None] - win)
        return L.attention(q, k, v, mask, cfg.attn_logit_softcap)
    q = AS.constrain_tag(q, "attn_q_seq")  # context-parallel rows (§Perf)
    # banded SWA path: needs a static window shared by every scanned layer
    if (PF.get().banded_swa_prefill and cfg.attn_kind == "swa"
            and cfg.window is not None and cfg.window + 1024 < s):
        o = L.banded_swa_attention(q, k, v, cfg.window,
                                   softcap=cfg.attn_logit_softcap)
    else:
        o = L.flash_attention(q, k, v, positions, positions, window=win,
                              softcap=cfg.attn_logit_softcap, chunk=FLASH_CHUNK)
    return AS.constrain_tag(o, "attn_q_seq")


def _block_with_kv(cfg, p, x, positions, win, attn_impl):
    """Pre-norm attention block that also returns this layer's fresh K/V."""
    h = L.apply_norm(x, p["ln1"], cfg.norm)
    q, k_new, v_new = L.attn_qkv(p["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.head_dim, positions, cfg.rope_theta,
                                 cfg.qk_norm, rotary_dim=cfg.rotary_dim)
    o = _attn_for_prefill(cfg, q, k_new, v_new, positions, win, attn_impl)
    x = x + S._post_attn(cfg, p, L.attn_out(p["attn"], o))
    h = L.apply_norm(x, p["ln2"], cfg.norm)
    if "moe" in p:
        from repro.models import moe as M
        m = M.moe_apply(p["moe"], h, cfg.moe, cfg.mlp_act, groups=T._moe_groups(h))
    else:
        m = L.mlp_apply(p["mlp"], h, cfg.mlp_act)
    if cfg.post_norms:
        m = L.apply_norm(m, p["ln2_post"], cfg.norm)
    return x + m, k_new, v_new


def _prefill_dense(cfg, attn_impl):
    def prefill(params, tokens, extra):
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x = T.embed(cfg, params, tokens)
        wins = T.window_schedule(cfg)

        def body(h, xs):
            p, w = xs
            h, k, v = _block_with_kv(cfg, p, h, positions, w, attn_impl)
            return h, (k, v)

        x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], wins))
        logits = T.unembed(cfg, params, x[:, -1:])[:, 0]
        cache = {"k": ks, "v": vs,
                 "length": jnp.full((b,), s, jnp.int32)}
        return logits, cache

    return prefill


def _prefill_vlm(cfg, attn_impl):
    every = cfg.vision.cross_attn_every
    n_groups = cfg.n_layers // every

    def prefill(params, tokens, extra):
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x = T.embed(cfg, params, tokens)
        wins = T.window_schedule(cfg).reshape(n_groups, every)
        grouped = jax.tree.map(
            lambda a: a.reshape(n_groups, every, *a.shape[1:]), params["blocks"])
        vis = extra["vision_embeds"]

        def group_body(h, xs):
            pg, wg, pc = xs

            def inner(h2, xs2):
                p, w = xs2
                h2, k, v = _block_with_kv(cfg, p, h2, positions, w, attn_impl)
                return h2, (k, v)

            h, (ks, vs) = jax.lax.scan(inner, h, (pg, wg))
            mk, mv = T.memory_kv(cfg, pc["attn"], vis)
            h = T.cross_block_apply(cfg, pc, h, mk, mv, gated=True)
            return h, (ks, vs, mk, mv)

        x, (ks, vs, cks, cvs) = jax.lax.scan(group_body, x,
                                             (grouped, wins, params["cross_blocks"]))
        logits = T.unembed(cfg, params, x[:, -1:])[:, 0]
        cache = {"k": ks.reshape(cfg.n_layers, b, s, cfg.n_kv_heads, cfg.head_dim),
                 "v": vs.reshape(cfg.n_layers, b, s, cfg.n_kv_heads, cfg.head_dim),
                 "cross_k": cks, "cross_v": cvs,
                 "length": jnp.full((b,), s, jnp.int32)}
        return logits, cache

    return prefill


def _prefill_encdec(cfg, attn_impl):
    def prefill(params, tokens, extra):
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        mem = T.encode(cfg, params, extra["frames"], attn_impl="flash")
        x = T.embed(cfg, params, tokens)

        def body(h, xs):
            p, pc = xs
            h, k, v = _block_with_kv(cfg, p, h, positions,
                                     jnp.int32(T.GLOBAL_WINDOW), attn_impl)
            mk, mv = T.memory_kv(cfg, pc["attn"], mem)
            h = T.cross_block_apply(cfg, pc, h, mk, mv, gated=False)
            return h, (k, v, mk, mv)

        x, (ks, vs, cks, cvs) = jax.lax.scan(body, x, (params["blocks"],
                                                       params["cross_blocks"]))
        logits = T.unembed(cfg, params, x[:, -1:])[:, 0]
        cache = {"k": ks, "v": vs, "cross_k": cks, "cross_v": cvs,
                 "length": jnp.full((b,), s, jnp.int32)}
        return logits, cache

    return prefill


def _prefill_rwkv(cfg):
    def prefill(params, tokens, extra):
        b, s = tokens.shape
        x = T.embed(cfg, params, tokens)
        h = cfg.d_model // cfg.rwkv.head_dim
        z_state = jnp.zeros((b, h, cfg.rwkv.head_dim, cfg.rwkv.head_dim), jnp.float32)
        z_last = jnp.zeros((b, cfg.d_model), x.dtype)

        def body(hid, p):
            hid, st, ltm, lcm = T.rwkv_block_apply(cfg, p, hid, z_state, z_last,
                                                   z_last, chunked=True)
            return hid, (st, ltm, lcm)

        x, (st, ltm, lcm) = jax.lax.scan(body, x, params["blocks"])
        logits = T.unembed(cfg, params, x[:, -1:])[:, 0]
        cache = {"state": st, "last_tm": ltm, "last_cm": lcm,
                 "length": jnp.full((b,), s, jnp.int32)}
        return logits, cache

    return prefill


def _prefill_hybrid(cfg, attn_impl):
    def prefill(params, tokens, extra):
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x = T.embed(cfg, params, tokens)
        w = cfg.rglru.lru_width
        cw = cfg.rglru.conv1d_width
        ks, vs, hs, convs = [], [], [], []
        ri = ai = 0
        for kind in cfg.layer_kinds():
            if kind == "rglru":
                p = params["rglru_blocks"][ri]
                x, h_i, c_i = T.rglru_block_apply(
                    cfg, p, x, jnp.zeros((b, w), jnp.float32),
                    jnp.zeros((b, cw - 1, w), x.dtype))
                hs.append(h_i)
                convs.append(c_i)
                ri += 1
            else:
                p = params["attn_blocks"][ai]
                x, k, v = _block_with_kv(cfg, p, x, positions,
                                         jnp.int32(cfg.window or T.GLOBAL_WINDOW),
                                         attn_impl)
                ks.append(k)
                vs.append(v)
                ai += 1
        logits = T.unembed(cfg, params, x[:, -1:])[:, 0]
        cache = {"k": jnp.stack(ks), "v": jnp.stack(vs),
                 "h": jnp.stack(hs), "conv": jnp.stack(convs),
                 "length": jnp.full((b,), s, jnp.int32)}
        return logits, cache

    return prefill


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def build_decode_step(cfg: ModelConfig):
    def decode(params, token, cache):
        return S.decode_step(cfg, params, token, cache)

    return decode


def decode_cache_struct(cfg: ModelConfig, shape: ShapeConfig,
                        dtype=jnp.bfloat16):
    """ShapeDtypeStruct pytree of a decode cache at this shape's context."""
    return jax.eval_shape(
        lambda: S.init_cache(cfg, shape.global_batch, shape.seq_len, dtype))

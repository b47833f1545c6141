"""Exact-cost probes for the roofline (DESIGN.md; see the scan-cost note).

XLA's HloCostAnalysis counts a while-loop body ONCE, so the scanned full
artifacts undercount FLOPs by the trip count. Probes fix this: we compile
*single-block* functions (attention chunk-scans unrolled ⇒ no while loops
anywhere) under the same mesh/shardings and scale by the block multiplier.

    roofline_cost(cell) = Σ_kind  mult_kind × cost(block_kind) + cost(outer)

Train probes wrap the block in jax.checkpoint and differentiate, matching
the remat schedule of the real train step; they include the AdamW update
of the block's params.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.sharding import batch_spec, param_specs
from repro.models import layers as L
from repro.models import serving as S
from repro.models import transformer as T
from repro.models.model_factory import cross_entropy
from repro.training.optimizer import OptimizerConfig, adamw_update, init_opt_state

Probe = Tuple[str, Callable, Tuple, Any, int]   # (name, fn, inputs, in_specs, mult)

_OPT = OptimizerConfig()


def _counts(cfg: ModelConfig) -> Dict[str, int]:
    kinds = cfg.layer_kinds()
    return {
        "attn_global": sum(1 for k in kinds if k == "attn_global"),
        "attn_local": sum(1 for k in kinds if k == "attn_local"),
        "rwkv": sum(1 for k in kinds if k == "rwkv"),
        "rglru": sum(1 for k in kinds if k == "rglru"),
    }


def _block_params_like(cfg: ModelConfig, params_like, kind: str):
    if kind in ("attn_global", "attn_local"):
        if cfg.attn_kind == "hybrid_rglru":
            return params_like["attn_blocks"][0]
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                            params_like["blocks"])
    if kind == "rwkv":
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                            params_like["blocks"])
    if kind == "rglru":
        return params_like["rglru_blocks"][0]
    if kind == "cross":
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                            params_like["cross_blocks"])
    if kind == "enc":
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                            params_like["enc_blocks"])
    raise KeyError(kind)


def _block_spec_tree(cfg: ModelConfig, block_like, mode: str, dp):
    """Param specs for a single (unstacked) block: reuse param_specs by
    wrapping in the stacked-tree naming so path rules match."""
    wrapped = {"blocks": jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((1,) + a.shape, a.dtype), block_like)}
    spec = param_specs(cfg, wrapped, mode, dp)["blocks"]
    return jax.tree.map(lambda s: P(*s[1:]), spec,
                        is_leaf=lambda x: isinstance(x, P))


def _apply_block(cfg: ModelConfig, kind: str, p, x, positions, win, extra=None,
                 decode_cache=None):
    """Single block fwd (probe mode: flash scans unrolled)."""
    if kind in ("attn_global", "attn_local"):
        if decode_cache is not None:
            lengths, k_c, v_c = decode_cache
            o, k_c, v_c = S._decode_attention(cfg, p, x, positions, k_c, v_c,
                                              win, lengths)
            h = x + S._post_attn(cfg, p, o)
            hh = L.apply_norm(h, p["ln2"], cfg.norm)
            if "moe" in p:
                from repro.models import moe as M
                m = M.moe_apply(p["moe"], hh, cfg.moe, cfg.mlp_act, groups=1)
            else:
                m = L.mlp_apply(p["mlp"], hh, cfg.mlp_act)
            if cfg.post_norms:
                m = L.apply_norm(m, p["ln2_post"], cfg.norm)
            return h + m
        x, _ = T.attn_block_apply(cfg, p, x, positions, win, None,
                                  attn_impl="flash", unroll_probe=True)
        return x
    if kind == "rwkv":
        b, s, d = x.shape
        h = cfg.d_model // cfg.rwkv.head_dim
        st = jnp.zeros((b, h, cfg.rwkv.head_dim, cfg.rwkv.head_dim), jnp.float32)
        lx = jnp.zeros((b, d), x.dtype)
        x, _, _, _ = T.rwkv_block_apply(cfg, p, x, st, lx, lx,
                                        chunked=s > 1, unroll_probe=True)
        return x
    if kind == "rglru":
        b = x.shape[0]
        w, cw = cfg.rglru.lru_width, cfg.rglru.conv1d_width
        x, _, _ = T.rglru_block_apply(cfg, p, x,
                                      jnp.zeros((b, w), jnp.float32),
                                      jnp.zeros((b, cw - 1, w), x.dtype),
                                      decode=x.shape[1] == 1)
        return x
    raise KeyError(kind)


def build_probes(cfg: ModelConfig, shape: ShapeConfig, params_like, dp,
                 dtype=jnp.bfloat16, mode: str = None,
                 act_mode: str = None) -> List[Probe]:
    """Probe list for one cell. Multipliers sum over the layer schedule.
    `mode` overrides the param-spec mode (e.g. "serve" for ZeRO-2 train);
    `act_mode` mirrors the launcher's activation layout so probe inputs see
    the same sharding the scanned artifact's layer boundaries use."""
    b, s = shape.global_batch, shape.seq_len
    if mode is None:
        mode = "train" if shape.kind == "train" else "serve"
    counts = _counts(cfg)
    x_sds = jax.ShapeDtypeStruct((b, s if shape.kind != "decode" else 1,
                                  cfg.d_model), dtype)
    x_spec = batch_spec(shape, dp, ndim=3)
    if shape.kind == "train":
        if act_mode == "batch_all":
            x_spec = P(tuple(dp) + ("model",), None, None)
        elif s % 16 == 0:
            x_spec = P(dp, "model", None)   # sequence-parallel boundaries
    probes: List[Probe] = []

    def add_block(kind: str, mult: int, win):
        if mult == 0:
            return
        block_like = _block_params_like(cfg, params_like, kind)
        block_specs = _block_spec_tree(cfg, block_like, mode, dp)
        positions_fn = _positions(shape, b, s)
        if shape.kind == "train":
            def fn(p, x):
                def f(p_, x_):
                    blk = functools.partial(_apply_block, cfg, kind)
                    out = jax.checkpoint(blk)(p_, x_, positions_fn(), win)
                    return jnp.sum(out.astype(jnp.float32))
                loss, grads = jax.value_and_grad(f, argnums=(0, 1))(p, x)
                gp, gx = grads
                p2, _, _ = adamw_update(_OPT, p, gp, init_opt_state(p))
                acc = jnp.sum(gx.astype(jnp.float32))
                for leaf in jax.tree.leaves(p2):
                    if leaf.dtype != jnp.int32:
                        acc = acc + jnp.sum(leaf.astype(jnp.float32))
                return acc
            probes.append((f"block_{kind}", fn, (block_like, x_sds),
                           (block_specs, x_spec), mult))
        elif shape.kind == "decode":
            from repro.models import perf_flags as PF
            from repro.models.serving import ring_len
            s_kv = s
            if (PF.get().ring_buffer_decode
                    and cfg.attn_kind in ("swa", "hybrid_rglru")):
                s_kv = min(s, ring_len(cfg))
            kv_sds = jax.ShapeDtypeStruct((b, s_kv, cfg.n_kv_heads, cfg.head_dim), dtype)
            kv_spec = (P(dp, "model", None, None) if b >= 16
                       else P(None, ("data", "model"), None, None))
            if kind in ("attn_global", "attn_local"):
                def fn(p, x, k_c, v_c):
                    lengths = jnp.full((b,), s - 1, jnp.int32)
                    positions = lengths[:, None]
                    out = _apply_block(cfg, kind, p, x, positions, win,
                                       decode_cache=(lengths, k_c, v_c))
                    return out
                probes.append((f"block_{kind}", fn,
                               (block_like, x_sds, kv_sds, kv_sds),
                               (block_specs, x_spec, kv_spec, kv_spec), mult))
            else:
                def fn(p, x):
                    positions = jnp.full((b, 1), s - 1, jnp.int32)
                    return _apply_block(cfg, kind, p, x, positions, win)
                probes.append((f"block_{kind}", fn, (block_like, x_sds),
                               (block_specs, x_spec), mult))
        else:  # prefill
            def fn(p, x):
                return _apply_block(cfg, kind, p, x, positions_fn(), win)
            probes.append((f"block_{kind}", fn, (block_like, x_sds),
                           (block_specs, x_spec), mult))

    win_local = jnp.int32(cfg.window or T.GLOBAL_WINDOW)
    add_block("attn_global", counts["attn_global"], jnp.int32(T.GLOBAL_WINDOW))
    add_block("attn_local", counts["attn_local"], win_local)
    add_block("rwkv", counts["rwkv"], None)
    add_block("rglru", counts["rglru"], None)

    # cross-attention blocks (vlm / enc-dec decoders)
    if cfg.vision is not None or cfg.encoder is not None:
        probes.append(_cross_probe(cfg, shape, params_like, dp, dtype, x_sds,
                                   x_spec, mode))
    # encoder tower (enc-dec): runs on prefill/train steps only
    if cfg.encoder is not None and shape.kind != "decode":
        probes.append(_encoder_probe(cfg, shape, params_like, dp, dtype, mode))

    probes.append(_outer_probe(cfg, shape, params_like, dp, dtype))
    return probes


def _cross_probe(cfg, shape, params_like, dp, dtype, x_sds, x_spec, mode) -> Probe:
    b = shape.global_batch
    mem_len = cfg.vision.n_patches if cfg.vision is not None else cfg.encoder.n_frames
    mult = len(cfg.cross_attn_layers()) if cfg.vision is not None else cfg.n_layers
    gated = cfg.vision is not None
    block_like = _block_params_like(cfg, params_like, "cross")
    block_specs = _block_spec_tree(cfg, block_like, mode, dp)
    mem_sds = jax.ShapeDtypeStruct((b, mem_len, cfg.d_model), dtype)
    mem_spec = batch_spec(shape, dp, ndim=3)

    def fwd(p, x, mem):
        mk, mv = T.memory_kv(cfg, p["attn"], mem)
        return T.cross_block_apply(cfg, p, x, mk, mv, gated=gated)

    if shape.kind == "train":
        def fn(p, x, mem):
            def f(p_, x_):
                out = jax.checkpoint(fwd)(p_, x_, mem)
                return jnp.sum(out.astype(jnp.float32))
            _, (gp, gx) = jax.value_and_grad(f, argnums=(0, 1))(p, x)
            p2, _, _ = adamw_update(_OPT, p, gp, init_opt_state(p))
            acc = jnp.sum(gx.astype(jnp.float32))
            for leaf in jax.tree.leaves(p2):
                if leaf.dtype != jnp.int32:
                    acc = acc + jnp.sum(leaf.astype(jnp.float32))
            return acc
    else:
        fn = fwd
    return ("block_cross", fn, (block_like, x_sds, mem_sds),
            (block_specs, x_spec, mem_spec), mult)


def _encoder_probe(cfg, shape, params_like, dp, dtype, mode) -> Probe:
    b = shape.global_batch
    f_len = cfg.encoder.n_frames
    block_like = _block_params_like(cfg, params_like, "enc")
    block_specs = _block_spec_tree(cfg, block_like, mode, dp)
    x_sds = jax.ShapeDtypeStruct((b, f_len, cfg.d_model), dtype)
    x_spec = batch_spec(shape, dp, ndim=3)

    def fwd(p, x):
        pos = jnp.broadcast_to(jnp.arange(f_len, dtype=jnp.int32)[None], (b, f_len))
        h = L.apply_norm(x, p["ln1"], cfg.norm)
        q, k, v = L.attn_qkv(p["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                             cfg.head_dim, pos, cfg.rope_theta, cfg.qk_norm,
                             rotary_dim=cfg.rotary_dim)
        o = L.flash_attention(q, k, v, pos, pos, softcap=cfg.attn_logit_softcap,
                              chunk=min(1024, f_len), unroll=True, causal=False)
        x = x + L.attn_out(p["attn"], o)
        h = L.apply_norm(x, p["ln2"], cfg.norm)
        return x + L.mlp_apply(p["mlp"], h, cfg.mlp_act)

    if shape.kind == "train":
        def fn(p, x):
            def f(p_, x_):
                return jnp.sum(jax.checkpoint(fwd)(p_, x_).astype(jnp.float32))
            _, (gp, gx) = jax.value_and_grad(f, argnums=(0, 1))(p, x)
            p2, _, _ = adamw_update(_OPT, p, gp, init_opt_state(p))
            acc = jnp.sum(gx.astype(jnp.float32))
            for leaf in jax.tree.leaves(p2):
                if leaf.dtype != jnp.int32:
                    acc = acc + jnp.sum(leaf.astype(jnp.float32))
            return acc
    else:
        fn = fwd
    return ("block_enc", fn, (block_like, x_sds), (block_specs, x_spec),
            cfg.encoder.n_layers)


def _positions(shape, b, s):
    if shape.kind == "decode":
        return lambda: jnp.full((b, 1), s - 1, jnp.int32)
    return lambda: jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))


def _outer_probe(cfg: ModelConfig, shape: ShapeConfig, params_like, dp,
                 dtype) -> Probe:
    """Embedding + final norm + head (+ loss & grads & adam in train)."""
    b = shape.global_batch
    s = shape.seq_len if shape.kind != "decode" else 1
    mode = "train" if shape.kind == "train" else "serve"
    sub_like = {"embed": params_like["embed"],
                "final_norm": params_like["final_norm"]}
    if "lm_head" in params_like:
        sub_like["lm_head"] = params_like["lm_head"]
    sub_specs = param_specs(cfg, sub_like, mode, dp)
    tok_sds = jax.ShapeDtypeStruct((b, s), jnp.int32)
    tok_spec = batch_spec(shape, dp, ndim=2)

    if shape.kind == "train":
        def fn(p, tokens, targets, mask):
            def f(p_):
                x = T.embed(cfg, p_, tokens)
                logits = T.unembed(cfg, p_, x)
                return cross_entropy(logits, targets, mask, cfg.vocab_size)
            loss, g = jax.value_and_grad(f)(p)
            p2, _, _ = adamw_update(_OPT, p, g, init_opt_state(p))
            return loss, jax.tree.map(lambda a: jnp.sum(a.astype(jnp.float32)), p2)
        mask_sds = jax.ShapeDtypeStruct((b, s), jnp.float32)
        return ("outer", fn, (sub_like, tok_sds, tok_sds, mask_sds),
                (sub_specs, tok_spec, tok_spec, tok_spec), 1)

    def fn(p, tokens):
        x = T.embed(cfg, p, tokens)
        return T.unembed(cfg, p, x[:, -1:])
    return ("outer", fn, (sub_like, tok_sds), (sub_specs, tok_spec), 1)

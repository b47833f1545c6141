"""Core neural-net building blocks, pure-functional JAX.

Everything here is written so that GSPMD can partition it on the production
mesh: plain einsum/where math, fp32 softmax/norm accumulation, bf16 weights.
The Pallas kernels in ``repro.kernels`` implement the serving hot paths of
the same math and are validated against these references.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    # gemma-style (1 + w) keeps zero-init identity; generic enough for all archs
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def apply_norm(x: jax.Array, p: dict, kind: str) -> jax.Array:
    if kind == "layernorm":
        return layer_norm(x, p["scale"], p["bias"])
    return rms_norm(x, p["scale"])


def init_norm(d: int, kind: str, dtype=jnp.float32) -> dict:
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return {"scale": jnp.zeros((d,), dtype)}  # rms uses (1 + w)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               rotary_dim: int) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).

    Rotates the leading ``rotary_dim`` channels of each head — channel i
    paired with i + rotary_dim/2 at frequency theta^(-2i/rotary_dim) — and
    passes the rest through unchanged (partial rotary)."""
    hd = x.shape[-1]
    if rotary_dim < hd:
        rot = apply_rope(x[..., :rotary_dim], positions, theta, rotary_dim)
        return jnp.concatenate([rot, x[..., rotary_dim:]], axis=-1)
    half = hd // 2
    freqs = rope_freqs(hd, theta)                                # (half,)
    ang = positions[..., None].astype(jnp.float32) * freqs       # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                             # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if hd % 2:  # odd head_dim (h2o-danube head_dim=120 is even; safety anyway)
        out = jnp.concatenate([out, x[..., 2 * half:].astype(jnp.float32)], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (reference jnp paths used by the compiled distributed steps)
# ---------------------------------------------------------------------------


def _softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, Hkv, hd) -> (B, S, Hkv*n_rep, hd)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def causal_mask(q_pos: jax.Array, k_pos: jax.Array, window: Optional[int] = None) -> jax.Array:
    """Boolean (..., Sq, Sk): True = attend. Sliding window keeps
    k_pos in (q_pos - window, q_pos]."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        m &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return m


def attention(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
              softcap: Optional[float] = None, scale: Optional[float] = None) -> jax.Array:
    """Naive (materialized-scores) attention, grouped-query form: KV heads
    are never repeated/materialized (critical for the seq-sharded decode
    cache — a broadcast here forces GSPMD into full rematerialization).
    q: (B,Sq,H,hd); k,v: (B,Sk,Hkv,hd)."""
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(b, sq, hkv, g, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
    scores = _softcap(scores, softcap)
    if mask is not None:  # None = attend to everything (cross attention)
        m = mask[:, None, None, :, :] if mask.ndim == 3 else mask
        scores = jnp.where(m, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return o.reshape(b, sq, h, hd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_positions: jax.Array, k_positions: jax.Array,
                    window: Optional[int] = None, softcap: Optional[float] = None,
                    chunk: int = 1024, unroll: bool = False,
                    causal: bool = True) -> jax.Array:
    """Memory-efficient attention: scans over key/value chunks with a running
    (max, sum, acc) triple so the (Sq, Sk) score matrix is never materialized.
    This is the compiled-artifact path for 32k/500k contexts. ``unroll=True``
    removes the while-loop so cost_analysis counts every chunk (probe mode).

    q: (B,Sq,H,hd); k,v: (B,Sk,Hkv,hd); positions give absolute token indices.
    """
    b, sq, h, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    n_rep = h // hkv
    scale = 1.0 / math.sqrt(hd)
    n_chunks = max(1, (sk + chunk - 1) // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)), constant_values=2 ** 30)
    kc = k.reshape(b, n_chunks, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    pc = k_positions.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    qf = q.reshape(b, sq, hkv, n_rep, hd).astype(jnp.float32)  # grouped-query

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        kb, vb, pb = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kb.astype(jnp.float32)) * scale
        s = _softcap(s, softcap)
        if causal:
            msk = causal_mask(q_positions, pb, window)           # (B, Sq, C)
        else:
            msk = (pb < 2 ** 30)[:, None, :] & jnp.ones((b, sq, 1), bool)
        s = jnp.where(msk[:, None, None, :, :], s, -1e30)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[..., None])
        corr = jnp.exp(m_prev - m_cur)
        l_cur = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
        return (m_cur, l_cur, acc), None

    init = (jnp.full((b, hkv, n_rep, sq), -jnp.inf, jnp.float32),
            jnp.zeros((b, hkv, n_rep, sq), jnp.float32),
            jnp.zeros((b, hkv, n_rep, sq, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, (kc, vc, pc),
                                  unroll=n_chunks if unroll else 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]           # (B,Hkv,G,Sq,hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


def banded_swa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         window: int, softcap: Optional[float] = None,
                         q_block: int = 1024) -> jax.Array:
    """Sliding-window self-attention over a gathered diagonal band: query
    block i attends keys [i·Q − window, i·Q + Q). FLOPs and bytes scale with
    S·(window+Q) instead of S² (the full-causal chunk scan computes every
    masked chunk). Exact w.r.t. masked attention (validated in tests).

    q: (B,S,H,hd); k,v: (B,S,Hkv,hd); from-scratch prefill (positions =
    arange(S)). S % q_block == 0.
    """
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qb = min(q_block, s)
    assert s % qb == 0, (s, qb)
    nb = s // qb
    band = window + qb
    scale = 1.0 / math.sqrt(hd)

    starts = jnp.arange(nb) * qb - window                          # (nb,)
    idx = starts[:, None] + jnp.arange(band)[None, :]              # (nb, band)
    valid_idx = idx >= 0
    idx_c = jnp.clip(idx, 0, s - 1)
    kb = k[:, idx_c]                                               # (B,nb,band,Hkv,hd)
    vb = v[:, idx_c]
    qg = q.reshape(b, nb, qb, hkv, g, hd)

    sc = jnp.einsum("bnqhgd,bnkhd->bnhgqk", qg.astype(jnp.float32),
                    kb.astype(jnp.float32)) * scale
    sc = _softcap(sc, softcap)
    qpos = (jnp.arange(nb) * qb)[:, None] + jnp.arange(qb)[None, :]  # (nb, qb)
    mask = idx[:, None, :] <= qpos[:, :, None]                     # causal
    mask &= idx[:, None, :] > (qpos[:, :, None] - window)          # window
    mask &= valid_idx[:, None, :]
    sc = jnp.where(mask[None, :, None, None], sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    o = jnp.einsum("bnhgqk,bnkhd->bnqhgd", pr, vb)
    return o.reshape(b, s, h, hd)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_apply(p: dict, x: jax.Array, act: str) -> jax.Array:
    if act in ("swiglu", "geglu"):
        gate = jnp.einsum("...d,df->...f", x, p["w_gate"])
        up = jnp.einsum("...d,df->...f", x, p["w_up"])
        g = jax.nn.silu(gate) if act == "swiglu" else jax.nn.gelu(gate, approximate=True)
        h = g * up
    elif act == "sqrelu":
        h = jnp.square(jax.nn.relu(jnp.einsum("...d,df->...f", x, p["w_up"])))
    else:
        raise ValueError(act)
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


def init_mlp(key: jax.Array, d: int, f: int, act: str, dtype=jnp.bfloat16) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {"w_up": (jax.random.normal(k2, (d, f)) * s_in).astype(dtype),
         "w_down": (jax.random.normal(k3, (f, d)) * s_out).astype(dtype)}
    if act in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(k1, (d, f)) * s_in).astype(dtype)
    return p


# ---------------------------------------------------------------------------
# Attention block params
# ---------------------------------------------------------------------------


def init_attn(key: jax.Array, d: int, n_heads: int, n_kv: int, hd: int,
              qk_norm: bool = False, dtype=jnp.bfloat16) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    so = 1.0 / math.sqrt(n_heads * hd)
    p = {"wq": (jax.random.normal(kq, (d, n_heads * hd)) * s).astype(dtype),
         "wk": (jax.random.normal(kk, (d, n_kv * hd)) * s).astype(dtype),
         "wv": (jax.random.normal(kv, (d, n_kv * hd)) * s).astype(dtype),
         "wo": (jax.random.normal(ko, (n_heads * hd, d)) * so).astype(dtype)}
    if qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def attn_qkv(p: dict, x: jax.Array, n_heads: int, n_kv: int, hd: int,
             positions: jax.Array, theta: float, qk_norm: bool = False, *,
             rotary_dim: int):
    """q, k, v of one layer, q and k rotated over their leading
    ``rotary_dim`` channels (``ModelConfig.rotary_dim``)."""
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(b, s, n_heads, hd)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(b, s, n_kv, hd)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(b, s, n_kv, hd)
    if qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    with jax.named_scope("rope"):
        q = apply_rope(q, positions, theta, rotary_dim)
        k = apply_rope(k, positions, theta, rotary_dim)
    return q, k, v


def attn_out(p: dict, o: jax.Array) -> jax.Array:
    b, s, h, hd = o.shape
    return jnp.einsum("bsh,hd->bsd", o.reshape(b, s, h * hd), p["wo"])

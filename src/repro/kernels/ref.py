"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each kernel's test sweeps shapes and
dtypes and asserts allclose against the function here. The engine can also
run on these directly (CPU path)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_tables: jax.Array, lengths: jax.Array,
                        softcap: Optional[float] = None,
                        window: Optional[int] = None) -> jax.Array:
    """Decode attention over a paged KV cache.

    q: (B, H, hd) — one query per sequence (position = lengths-1).
    k_pages / v_pages: (NP, P, Hkv, hd) global page pools.
    block_tables: (B, MAXP) int32 page ids (padded with 0; masked by length).
    lengths: (B,) int32 — valid tokens per sequence (incl. current token).
    Returns (B, H, hd).
    """
    b, h, hd = q.shape
    np_, p, hkv, _ = k_pages.shape
    maxp = block_tables.shape[1]
    g = h // hkv
    scale = 1.0 / math.sqrt(hd)

    with jax.named_scope("kv_gather"):
        k = k_pages[block_tables].reshape(b, maxp * p, hkv, hd)  # (B, L, Hkv, hd)
        v = v_pages[block_tables].reshape(b, maxp * p, hkv, hd)
    pos = jnp.arange(maxp * p, dtype=jnp.int32)[None, :]
    valid = pos < lengths[:, None]
    if window is not None:
        valid &= pos > (lengths[:, None] - 1 - window)

    qh = q.reshape(b, hkv, g, hd).astype(jnp.float32)
    kh = k.transpose(0, 2, 1, 3).astype(jnp.float32)             # (B, Hkv, L, hd)
    vh = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhld->bhgl", qh, kh) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgl,bhld->bhgd", pr, vh)
    return o.reshape(b, h, hd).astype(q.dtype)


def flash_prefill_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                      softcap: Optional[float] = None,
                      window: Optional[int] = None) -> jax.Array:
    """Causal (optionally sliding-window, softcapped) self-attention.
    q: (B, S, H, hd); k, v: (B, S, Hkv, hd). Returns (B, S, H, hd)."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = 1.0 / math.sqrt(hd)
    qp = jnp.arange(s)
    mask = qp[None, :, None] >= qp[None, None, :]
    if window is not None:
        mask &= qp[None, None, :] > (qp[None, :, None] - window)

    qh = q.reshape(b, s, hkv, g, hd).astype(jnp.float32)
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", qh, k.astype(jnp.float32)) * scale
    if softcap is not None:
        sc = softcap * jnp.tanh(sc / softcap)
    sc = jnp.where(mask[:, None, None], sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", pr, v.astype(jnp.float32))
    return o.reshape(b, s, h, hd).astype(q.dtype)


def wkv6_ref(r, k, v, w, u, state=None):
    """Sequential WKV6 recurrence — see repro.models.rwkv6.wkv_sequential."""
    from repro.models.rwkv6 import wkv_sequential
    return wkv_sequential(r, k, v, w, u, state)


def rglru_ref(a: jax.Array, b: jax.Array, h0: jax.Array) -> jax.Array:
    """Sequential linear recurrence h_t = a_t h_{t-1} + b_t.
    a, b: (B, T, W); h0: (B, W). Returns h (B, T, W)."""
    def step(h, xs):
        at, bt = xs
        h = at * h + bt
        return h, h

    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    _, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                         (jnp.moveaxis(af, 1, 0), jnp.moveaxis(bf, 1, 0)))
    return jnp.moveaxis(hs, 0, 1).astype(a.dtype)

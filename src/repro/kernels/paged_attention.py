"""Pallas TPU paged-attention decode kernel (flash-decode over KV pages).

This is FLOWSERVE's hot decode path: one query token per sequence attends
over that sequence's pages of the global KV pool, with the block table
scalar-prefetched so page blocks can be DMA'd from HBM into VMEM by the
BlockSpec index_map (the TPU-native analogue of vLLM's PagedAttention
gather).

Layout. A page of the pool is (P, Hkv, hd); its trailing (Hkv, hd) dims are
contiguous, so the kernel views the pool as (NP, P·Hkv, hd) and DMAs one
WHOLE page — every KV head — per grid step. A block of one head,
(1, P, 1, hd), is refused by the TPU lowering: its second-minor dim (1) is
neither a multiple of 8 nor the full Hkv. Row r = p·Hkv + h of the page
block is token p of KV head h.

Grid: (B, NP) — NP innermost so the running-softmax scratch carries across
a sequence's pages. Per step the kernel holds in VMEM:
    q block      (H, hd)           all query heads of one sequence
    k/v page     (P·Hkv, hd)
    acc scratch  (H, hd) fp32 + m/l (H, 1)
All heads are scored against the whole page in one (H, hd)·(hd, P·Hkv)
matmul; a query row sees only the columns of its own KV head (GQA: query
head h_q reads KV head h_q // G), the rest are masked. That spends Hkv×
the FLOPs of a per-head loop on MXU-sized tiles, in a step that is bound
by the page DMA. Validated in interpret mode against
ref.paged_attention_ref across shape/dtype sweeps, and compiled for v5e at
qwen3-8b widths in tests/test_chip_compile.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    # scalar-prefetch refs
    block_tables_ref,            # (B, NP) int32
    lengths_ref,                 # (B,) int32
    # blocked operands
    q_ref,                       # (1, H, hd)
    k_ref,                       # (1, P·Hkv, hd)
    v_ref,                       # (1, P·Hkv, hd)
    o_ref,                       # (1, H, hd)
    # scratch
    m_ref, l_ref, acc_ref,
    *, page_size: int, n_pages: int, n_kv_heads: int, group: int,
    softcap: Optional[float], window: Optional[int], scale: float,
):
    b, p = pl.program_id(0), pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    start = p * page_size

    @pl.when(start < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                         # (H, hd)
        k = k_ref[0].astype(jnp.float32)                         # (P·Hkv, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)    # query head
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)    # p·Hkv + h
        pos = start + col // n_kv_heads
        valid = (row // group == col % n_kv_heads) & (pos < length)
        if window is not None:
            valid &= pos > (length - 1 - window)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                                      # (H, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pr = jnp.where(valid, jnp.exp(s - m_cur), 0.0)
        corr = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pr, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pr, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(p == n_pages - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array,
                    softcap: Optional[float] = None,
                    window: Optional[int] = None,
                    interpret: bool = True) -> jax.Array:
    """q: (B, H, hd); k_pages/v_pages: (NP_pool, P, Hkv, hd);
    block_tables: (B, NP) int32; lengths: (B,). Returns (B, H, hd)."""
    b, h, hd = q.shape
    n_pool, page_size, hkv, _ = k_pages.shape
    n_pages = block_tables.shape[1]
    rows = page_size * hkv
    kp = k_pages.reshape(n_pool, rows, hd)
    vp = v_pages.reshape(n_pool, rows, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda bi, pi, bt, ln: (bi, 0, 0)),
            pl.BlockSpec((1, rows, hd), lambda bi, pi, bt, ln: (bt[bi, pi], 0, 0)),
            pl.BlockSpec((1, rows, hd), lambda bi, pi, bt, ln: (bt[bi, pi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda bi, pi, bt, ln: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, page_size=page_size, n_pages=n_pages, n_kv_heads=hkv,
        group=h // hkv, softcap=softcap, window=window,
        scale=1.0 / math.sqrt(hd))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, q, kp, vp)

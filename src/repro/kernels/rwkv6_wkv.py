"""Pallas TPU chunked WKV6 kernel (RWKV-6 "Finch" recurrence).

TPU adaptation of the (GPU, warp-per-head) WKV kernels: instead of a
per-timestep recurrence we run the chunk-parallel schedule — within-chunk
pairwise interactions become (C×C)·(C×hd) MXU matmuls in log-decay space;
the cross-chunk state (hd×hd per head, fp32) lives in VMEM scratch and is
carried across the innermost grid dimension. Grid: (B, H, NC).
VMEM per step: r/k/v/w chunks (C, hd), state (hd, hd) fp32, out (C, hd).

The operands are transposed to head-major (B, H, T, hd) before the call,
so a block is (1, 1, C, hd) with trailing dims (C, hd): a multiple of 8 by
the full head width, which the TPU lowering accepts. A block of one head
cut from (B, T, H, hd), (1, C, 1, hd), is refused there (second-minor dim
1 is neither a multiple of 8 nor the full H). ``u`` is viewed as
(H, 1, hd) for the same reason.
Validated in interpret mode against the sequential oracle ref.wkv6_ref,
and compiled for v5e at rwkv6-1.6b widths in tests/test_chip_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models.rwkv6 import LOG_DECAY_CLAMP


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref,
            *, chunk: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    rb = r_ref[0, 0].astype(jnp.float32)                         # (C, hd)
    kb = k_ref[0, 0].astype(jnp.float32)
    vb = v_ref[0, 0].astype(jnp.float32)
    wb = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                             # (1, hd)
    s = state_ref[...]                                           # (hd_k, hd_v)

    lw = jnp.clip(jnp.log(jnp.maximum(wb, 1e-38)), LOG_DECAY_CLAMP, 0.0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum over the chunk as a lower-triangular matmul (the
    # TPU lowering has no cumsum)
    cum = jax.lax.dot_general(jnp.where(jj <= ii, 1.0, 0.0), lw,
                              (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # (C, hd)
    dec_in = jnp.exp(cum - lw)                                   # Π_{j<i} w
    y_state = jax.lax.dot_general(rb * dec_in, s, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    q_side = rb * jnp.exp(cum - lw)
    k_side = kb * jnp.exp(-cum)
    scores = jax.lax.dot_general(q_side, k_side, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (C, C)
    scores = jnp.where(jj < ii, scores, 0.0)
    bonus = jnp.sum(rb * u * kb, axis=1, keepdims=True)  # (C, 1)
    y = y_state + jax.lax.dot_general(scores, vb, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    y = y + bonus * vb
    o_ref[0, 0] = y.astype(o_ref.dtype)

    total = cum[-1:, :]                                          # (1, hd)
    k_dec = kb * jnp.exp(total - cum)                            # k_j Π_{l>j} w_l
    state_ref[...] = (jnp.exp(total).T * s
                      + jax.lax.dot_general(k_dec, vb, (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32))


def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array, u: jax.Array,
         chunk: int = 64, interpret: bool = True) -> jax.Array:
    """r,k,v,w: (B, T, H, hd); u: (H, hd). Returns y: (B, T, H, hd).
    T must be a multiple of `chunk` (pad upstream with w=1, k=0)."""
    b, t, h, hd = r.shape
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk

    kernel = functools.partial(_kernel, chunk=chunk)
    spec = pl.BlockSpec((1, 1, chunk, hd), lambda bi, hi, ci: (bi, hi, ci, 0))
    heads_first = lambda x: x.transpose(0, 2, 1, 3)             # (B, H, T, hd)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, 1, hd), lambda bi, hi, ci: (hi, 0, 0))],
        out_specs=spec,
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, h, t, hd), r.dtype),
        interpret=interpret,
    )(heads_first(r), heads_first(k), heads_first(v), heads_first(w),
      u.reshape(h, 1, hd))
    return heads_first(out)

"""Pallas TPU RG-LRU linear-recurrence kernel (RecurrentGemma/Griffin).

h_t = a_t ⊙ h_{t-1} + b_t, per channel. The width dimension maps onto
vector lanes (block over W, multiples of 128); the sequence is chunked
with the carried state in VMEM scratch across the innermost grid dim.
Within a chunk the recurrence is a statically unrolled run of fused vector
ops over the chunk's rows — elementwise recurrences have no MXU work, so
lane-parallelism over W is the whole game on TPU. Grid: (B, NW, NC).

Every row access is static: a traced row index (``a[i]`` inside a
``fori_loop``) lowers to a ``dynamic_slice``, which the TPU lowering
refuses. ``h0`` is viewed as (B, 1, W) so its block (1, 1, Wb) has
trailing dims (1, Wb) — the full unit dim by a lane multiple — for any B;
a (1, Wb) block over (B, W) is refused for 1 < B < 8.
Validated in interpret mode against ref.rglru_ref, and compiled for v5e at
recurrentgemma-2b widths in tests/test_chip_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, o_ref, h_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)                             # (C, Wb)
    b = b_ref[0].astype(jnp.float32)
    h = h_ref[...]                                               # (1, Wb)
    rows = []
    for i in range(chunk):
        h = a[i:i + 1] * h + b[i:i + 1]
        rows.append(h)
    o_ref[0] = jnp.concatenate(rows, axis=0).astype(o_ref.dtype)
    h_ref[...] = h


def rglru(a: jax.Array, b: jax.Array, h0: jax.Array,
          chunk: int = 128, block_w: int = 128,
          interpret: bool = True) -> jax.Array:
    """a, b: (B, T, W); h0: (B, W). Returns h: (B, T, W).
    T % chunk == 0 and W % block_w == 0 (pad upstream)."""
    bsz, t, w = a.shape
    assert t % chunk == 0 and w % block_w == 0, (t, w, chunk, block_w)
    nc, nw = t // chunk, w // block_w

    kernel = functools.partial(_kernel, chunk=chunk)
    spec = pl.BlockSpec((1, chunk, block_w), lambda bi, wi, ci: (bi, ci, wi))
    out = pl.pallas_call(
        kernel,
        grid=(bsz, nw, nc),
        in_specs=[spec, spec,
                  pl.BlockSpec((1, 1, block_w), lambda bi, wi, ci: (bi, 0, wi))],
        out_specs=spec,
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((bsz, t, w), a.dtype),
        interpret=interpret,
    )(a, b, h0.reshape(bsz, 1, w))
    return out

"""Sampling for FLOWSERVE's model generator: greedy / temperature / top-p.

Three entry points:
  * ``sample``       — one SamplingParams for a whole logits batch (oracle /
                       offline paths).
  * ``sample_batch`` — per-row temperature/top-p as arrays, one jit'd device
                       dispatch for the whole decode batch (one
                       ``fold_in``-free split per step, not one dispatch per
                       sequence).
  * ``sample_core``  — the traceable per-row sampling math itself, shared by
                       ``sample_batch`` and the fused decode+sample step
                       (DESIGN.md §8): fusing callers inline it into the
                       decode jit so logits never leave the device, and both
                       paths stay bit-identical by construction.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0      # 0 => greedy
    top_p: float = 1.0
    max_new_tokens: int = 64
    stop_on_eos: bool = True


def sample(logits: jax.Array, params: SamplingParams, key: jax.Array,
           vocab_size: int) -> jax.Array:
    """logits: (B, Vp) -> token ids (B,). Pad-vocab ids are masked."""
    vp = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if vp > vocab_size:
        logits = jnp.where(jnp.arange(vp)[None, :] >= vocab_size, -1e30, logits)
    if params.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / params.temperature
    if params.top_p < 1.0:
        keep = nucleus_mask(logits, jnp.full((logits.shape[0],),
                                             params.top_p, jnp.float32))
        logits = jnp.where(keep, logits, -1e30)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _order_key(x: jax.Array) -> jax.Array:
    """float32 -> uint32 with the same order (for an exact threshold
    search over the float values)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    bits = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return (jax.lax.bitcast_convert_type(bits, jnp.uint32)
            ^ jnp.uint32(0x80000000))


def nucleus_mask(scaled: jax.Array, top_p: jax.Array) -> jax.Array:
    """(B, V) top-p mask: keep the highest logits until their probability
    reaches ``top_p`` (B,), ties at the cut kept — the set a descending
    sort + cumsum selects. It is found without a sort: the cut is the
    largest logit whose at-or-above mass still reaches ``top_p``, located
    exactly by a 32-step bisection over the logits' order keys. A sort of a
    152k vocabulary alone costs ~20 s of TPU compile time in every program
    that samples."""
    probs = jax.nn.softmax(scaled, axis=-1)
    keys = _order_key(scaled)
    top_p = top_p.reshape(-1, 1)

    def halve(_, lohi):
        lo, hi = lohi
        gap = hi - lo
        mid = lo + (gap >> 1) + (gap & 1)            # ceil midpoint, no overflow
        mass = jnp.sum(jnp.where(keys >= mid, probs, 0.0), axis=-1,
                       keepdims=True)
        ok = mass >= top_p
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    b = scaled.shape[0]
    lo, _ = jax.lax.fori_loop(
        0, 32, halve, (jnp.zeros((b, 1), jnp.uint32),
                       jnp.full((b, 1), 0xFFFFFFFF, jnp.uint32)))
    return keys >= lo


def sample_core(logits: jax.Array, temperature: jax.Array, top_p: jax.Array,
                key: jax.Array, vocab_size: int) -> jax.Array:
    """Traceable per-row sampling: (B, Vp) logits + per-row params + ONE step
    key -> (B,) token ids. Every row is independent, so a bucket-padded batch
    samples its real rows bit-identically to the exact-size batch (greedy
    rows never consume randomness)."""
    vp = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if vp > vocab_size:
        logits = jnp.where(jnp.arange(vp)[None, :] >= vocab_size, -1e30, logits)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # greedy rows (t<=0) still flow through the stochastic path below with a
    # clamped temperature; their result is discarded by the final where.
    t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / t
    limited = jnp.where(nucleus_mask(scaled, top_p), scaled, -1e30)
    final = jnp.where((top_p < 1.0)[:, None], limited, scaled)
    keys = jax.random.split(key, logits.shape[0])
    drawn = jax.vmap(
        lambda k, row: jax.random.categorical(k, row))(keys, final)
    return jnp.where(temperature <= 0.0, greedy, drawn.astype(jnp.int32))


@functools.partial(jax.jit, static_argnums=(4,))
def _sample_batch(logits: jax.Array, temperature: jax.Array, top_p: jax.Array,
                  key: jax.Array, vocab_size: int) -> jax.Array:
    return sample_core(logits, temperature, top_p, key, vocab_size)


def greedy_core(logits: jax.Array, vocab_size: int) -> jax.Array:
    """Traceable pad-masked argmax — the all-greedy shortcut. Row-for-row
    identical to ``sample_core`` at temperature<=0, without the
    softmax/nucleus/categorical pipeline (fused decode branches here via
    ``lax.cond`` when every row of the batch is greedy)."""
    vp = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if vp > vocab_size:
        logits = jnp.where(jnp.arange(vp)[None, :] >= vocab_size, -1e30, logits)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(1,))
def _greedy_batch(logits: jax.Array, vocab_size: int) -> jax.Array:
    return greedy_core(logits, vocab_size)


def sample_batch(logits: jax.Array, temperature, top_p, key: jax.Array,
                 vocab_size: int) -> jax.Array:
    """logits: (B, Vp) with per-row params -> token ids (B,). One device
    dispatch for the whole batch; an all-greedy batch (the common serving
    default) skips the softmax/nucleus/categorical pipeline entirely."""
    temperature = np.asarray(temperature, np.float32)
    if temperature.size == 0 or float(temperature.max()) <= 0.0:
        return _greedy_batch(logits, vocab_size)
    return _sample_batch(logits, jnp.asarray(temperature),
                         jnp.asarray(top_p, jnp.float32), key, vocab_size)

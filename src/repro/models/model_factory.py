"""Bundle a ModelConfig into callables the engine / launcher / tests use."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, get_config, smoke_config
from repro.models import serving as S
from repro.models import transformer as T


@dataclass(frozen=True)
class ModelBundle:
    cfg: ModelConfig
    init_params: Callable[..., Dict[str, Any]]
    forward: Callable[..., jax.Array]          # teacher-forced logits
    init_cache: Callable[..., S.Cache]
    prefill: Callable[..., Any]
    decode_step: Callable[..., Any]

    def loss_fn(self, params, tokens, targets, mask, **extra):
        """Mean next-token cross-entropy over `mask`-ed positions."""
        logits = self.forward(self.cfg, params, tokens, **extra)
        return cross_entropy(logits, targets, mask, self.cfg.vocab_size)

    def extra_inputs(self, batch: int, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
        """Modality-stub inputs (zeros) for vlm/audio families."""
        cfg = self.cfg
        out: Dict[str, jax.Array] = {}
        if cfg.vision is not None:
            out["vision_embeds"] = jnp.zeros((batch, cfg.vision.n_patches, cfg.d_model), dtype)
        if cfg.encoder is not None:
            out["frames"] = jnp.zeros((batch, cfg.encoder.n_frames, cfg.d_model), dtype)
        return out


def cross_entropy(logits: jax.Array, targets: jax.Array, mask: jax.Array,
                  vocab_size: int) -> jax.Array:
    """logits: (B,S,Vp) — pad-vocab entries are excluded by masking."""
    vp = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if vp > vocab_size:
        pad = jnp.arange(vp) >= vocab_size
        logits = jnp.where(pad[None, None, :], -1e30, logits)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)


def get_model(name_or_cfg, smoke: bool = False,
              n_layers: Optional[int] = None) -> ModelBundle:
    """``smoke`` shrinks the widths to a CPU-sized variant; ``n_layers``
    cuts the depth and keeps the widths (a published-width model cut to
    what one chip holds)."""
    cfg = name_or_cfg if isinstance(name_or_cfg, ModelConfig) else get_config(name_or_cfg)
    if smoke:
        cfg = smoke_config(cfg)
    if n_layers is not None and n_layers != cfg.n_layers:
        cfg = dataclasses.replace(cfg, name=f"{cfg.name}-{n_layers}l",
                                  n_layers=n_layers)
    return ModelBundle(
        cfg=cfg,
        init_params=lambda key, dtype=jnp.bfloat16: T.init_params(cfg, key, dtype),
        forward=T.forward,
        init_cache=lambda batch, max_len, dtype=jnp.bfloat16: S.init_cache(cfg, batch, max_len, dtype),
        prefill=S.prefill,
        decode_step=S.decode_step,
    )

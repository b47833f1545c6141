"""Model / run configuration system.

Every assigned architecture is expressed as a ``ModelConfig``. Configs are
plain frozen dataclasses so they can be hashed into jit static args and
round-tripped through the launcher CLI.

The same config object drives:
  * parameter init + forward/train/prefill/decode (src/repro/models)
  * sharding rules (which dims are TP-shardable on the 16-way model axis)
  * the dry-run input_specs (src/repro/launch/dryrun.py)
  * the reduced "smoke" variant used by CPU tests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int          # per-expert FFN hidden size
    router_jitter: float = 0.0
    # Capacity factor used when dispatching with fixed-capacity all_to_all.
    capacity_factor: float = 1.25


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder tower for enc-dec models (seamless-m4t). The modality
    frontend (speech feature extractor) is a stub: input_specs provides
    precomputed frame embeddings of shape (batch, n_frames, d_model)."""
    n_layers: int
    n_frames: int          # default encoder sequence length (precomputed frames)


@dataclass(frozen=True)
class VisionConfig:
    """Cross-attention vision adapter for VLMs (llama-3.2-vision). The
    vision tower is a stub: input_specs provides precomputed patch
    embeddings (batch, n_patches, d_model)."""
    cross_attn_every: int  # a cross-attn layer is inserted after every N self-attn layers
    n_patches: int


@dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64
    # interval (tokens) at which the engine checkpoints recurrent state so
    # prefix-cache hits can resume from the nearest boundary (DESIGN.md §4)
    state_ckpt_interval: int = 256


@dataclass(frozen=True)
class RGLRUConfig:
    lru_width: int
    # block pattern: this many recurrent blocks per attention block
    recurrent_per_attn: int = 2
    conv1d_width: int = 4
    state_ckpt_interval: int = 256


# ---------------------------------------------------------------------------
# Main config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # --- attention flavour ---
    attn_kind: str = "global"       # global | swa | local_global | hybrid_rglru | rwkv
    window: Optional[int] = None    # sliding-window size when applicable
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    qk_norm: bool = False
    rope_theta: float = 10000.0
    # share of each head's channels that RoPE rotates (the leading ones);
    # the rest pass through unrotated
    partial_rotary_factor: float = 1.0

    # --- MLP flavour ---
    mlp_act: str = "swiglu"         # swiglu | geglu | sqrelu

    norm: str = "rmsnorm"           # rmsnorm | layernorm
    post_norms: bool = False        # gemma2-style post-attn/post-ffw norms
    embed_scale: bool = False       # gemma-style sqrt(d_model) embedding scale
    tie_embeddings: bool = False

    moe: Optional[MoEConfig] = None
    encoder: Optional[EncoderConfig] = None
    vision: Optional[VisionConfig] = None
    rwkv: Optional[RWKVConfig] = None
    rglru: Optional[RGLRUConfig] = None

    # citation per assignment: [source; verification tier]
    source: str = ""

    # ---------------- derived ----------------
    @property
    def attn_free(self) -> bool:
        return self.attn_kind == "rwkv"

    @property
    def subquadratic(self) -> bool:
        """True when a 500k-token decode context has bounded (or
        mesh-shardable-bounded) attention state: SSM / hybrid / SWA /
        alternating local-global."""
        return self.attn_kind in ("rwkv", "hybrid_rglru", "swa", "local_global")

    @property
    def rotary_dim(self) -> int:
        """Channels of each head that RoPE rotates: head_dim at factor 1.0."""
        rd = int(self.head_dim * self.partial_rotary_factor)
        if rd % 2 or not 0 < rd <= self.head_dim:
            raise ValueError(f"rotary width {rd} of head_dim {self.head_dim} "
                             f"must be even and positive")
        return rd

    @property
    def is_encdec(self) -> bool:
        return self.encoder is not None

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so the vocab dim is cleanly
        TP-shardable on a 16-way model axis (pad logits are masked)."""
        return ((self.vocab_size + 255) // 256) * 256

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer block kind, length n_layers (decoder tower)."""
        kinds = []
        for i in range(self.n_layers):
            if self.attn_kind == "rwkv":
                kinds.append("rwkv")
            elif self.attn_kind == "hybrid_rglru":
                assert self.rglru is not None
                period = self.rglru.recurrent_per_attn + 1
                kinds.append("attn_local" if (i % period == self.rglru.recurrent_per_attn) else "rglru")
            elif self.attn_kind == "local_global":
                kinds.append("attn_local" if i % 2 == 0 else "attn_global")
            elif self.attn_kind == "swa":
                kinds.append("attn_local")
            else:
                kinds.append("attn_global")
        return tuple(kinds)

    def cross_attn_layers(self) -> Tuple[int, ...]:
        if self.vision is None:
            return ()
        k = self.vision.cross_attn_every
        return tuple(i for i in range(self.n_layers) if (i + 1) % k == 0)

    def param_count(self) -> int:
        """Approximate parameter count N (used for MODEL_FLOPS = 6·N·D)."""
        d, f, v = self.d_model, self.d_ff, self.padded_vocab
        qkv = d * self.n_heads * self.head_dim + 2 * d * self.n_kv_heads * self.head_dim
        o = self.n_heads * self.head_dim * d
        n = 0
        for kind in self.layer_kinds():
            if kind == "rwkv":
                # time-mix (r,k,v,g,o + decay/aaa) + channel-mix
                n += 6 * d * d + 2 * d * f + d * f  # rwkv channel mix is k,v,r
            elif kind == "rglru":
                assert self.rglru is not None
                w = self.rglru.lru_width
                n += 2 * d * w + w * d + 2 * w * self.rglru.conv1d_width
                n += self._mlp_params(d, f)
            else:
                n += qkv + o + self._mlp_params(d, f)
        if self.vision is not None:
            for _ in self.cross_attn_layers():
                n += qkv + o
        if self.encoder is not None:
            enc_layer = qkv + o + self._mlp_params(d, f)
            n += self.encoder.n_layers * enc_layer
            # decoder cross-attention
            n += self.n_layers * (qkv + o)
        n += v * d  # embeddings
        if not self.tie_embeddings:
            n += v * d
        return n

    def _mlp_params(self, d: int, f: int) -> int:
        if self.moe is not None:
            e = self.moe
            per = (3 if self.mlp_act in ("swiglu", "geglu") else 2) * d * e.d_expert
            return e.n_experts * per + d * e.n_experts  # + router
        mult = 3 if self.mlp_act in ("swiglu", "geglu") else 2
        return mult * d * f

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top-k experts count)."""
        if self.moe is None:
            return self.param_count()
        e = self.moe
        per = (3 if self.mlp_act in ("swiglu", "geglu") else 2) * self.d_model * e.d_expert
        dead = (e.n_experts - e.top_k) * per * self.n_layers
        return self.param_count() - dead

    # ---------------- TP shardability (16-way model axis) ----------------
    def tp_heads_ok(self, tp: int = 16) -> bool:
        return self.n_heads % tp == 0

    def tp_ff_ok(self, tp: int = 16) -> bool:
        f = self.moe.d_expert if self.moe is not None else self.d_ff
        return f % tp == 0


# ---------------------------------------------------------------------------
# Input shapes (assigned): every LM-family arch is paired with these four.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether an (arch, shape) cell runs, and why not when skipped."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "pure full-attention arch: 500k decode context skipped per assignment"
    return True, ""


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> list:
    _ensure_loaded()
    return sorted(_REGISTRY)


def _ensure_loaded() -> None:
    # import all per-arch modules exactly once
    if _REGISTRY:
        return
    from repro.configs import (  # noqa: F401
        gemma2_9b, nemotron_4_15b, h2o_danube_3_4b, qwen3_8b, rwkv6_1_6b,
        llama_3_2_vision_11b, granite_moe_3b_a800m, mixtral_8x7b,
        seamless_m4t_large_v2, recurrentgemma_2b,
    )


# ---------------------------------------------------------------------------
# Reduced smoke variant — same family/block pattern, tiny dims.
# ---------------------------------------------------------------------------


def smoke_config(cfg: ModelConfig) -> ModelConfig:
    """Shrink a full config to a CPU-runnable variant of the same family."""
    changes: dict = dict(
        name=cfg.name + "-smoke",
        n_layers=min(cfg.n_layers, 4),
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2),
        head_dim=16,
        d_ff=128,
        vocab_size=512,
        window=16 if cfg.window else None,
    )
    if cfg.moe is not None:
        # capacity_factor high enough to be drop-free: chunked prefill /
        # decode / teacher-forced paths then agree bit-for-bit.
        changes["moe"] = MoEConfig(n_experts=4, top_k=min(cfg.moe.top_k, 2),
                                   d_expert=32, capacity_factor=100.0)
    if cfg.encoder is not None:
        changes["encoder"] = EncoderConfig(n_layers=2, n_frames=24)
    if cfg.vision is not None:
        # n_layers must stay divisible by cross_attn_every for the group scan
        changes["vision"] = VisionConfig(cross_attn_every=2, n_patches=16)
    if cfg.rwkv is not None:
        changes["rwkv"] = RWKVConfig(head_dim=16, state_ckpt_interval=8)
        changes["n_kv_heads"] = 4
    if cfg.rglru is not None:
        changes["rglru"] = RGLRUConfig(lru_width=64, recurrent_per_attn=cfg.rglru.recurrent_per_attn,
                                       conv1d_width=4, state_ckpt_interval=8)
        changes["n_layers"] = min(cfg.n_layers, 6)
        changes["n_kv_heads"] = 1
    return dataclasses.replace(cfg, **changes)

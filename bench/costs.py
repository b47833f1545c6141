"""Operations and bytes of the dense GQA decoder, from a configuration
file's sizes alone (``bench/configs/<c>.json``): what the model needs, not
what the program happens to compute (no bucket padding, no padded
vocabulary rows, no repeated reads)."""
from __future__ import annotations

_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def layer_params(c: dict) -> int:
    """Matrix parameters of one decoder layer (norms left out)."""
    d, f, hd = c["hidden_size"], c["intermediate_size"], c["head_dim"]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = d * (nh + 2 * nkv) * hd + nh * hd * d
    mlp = (3 if c["mlp"] == "swiglu" else 2) * d * f
    return attn + mlp


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def param_count(c: dict) -> int:
    """Layers, embedding and (untied) head, norms left out."""
    n = c["num_hidden_layers"] * layer_params(c) + head_params(c)
    return n if c["tie_word_embeddings"] else n + head_params(c)


def dtype_bytes(c: dict) -> int:
    return _BYTES[c["dtype"]]


def kv_bytes_per_token(c: dict) -> int:
    return (c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
            * c["head_dim"] * dtype_bytes(c))


def decode_step_bytes(c: dict, batch: float, mean_context: float) -> float:
    """Least bytes one decode step must read: every layer's matrices, the
    LM head, and the batch's live KV."""
    weights = (c["num_hidden_layers"] * layer_params(c) + head_params(c)) \
        * dtype_bytes(c)
    return weights + batch * mean_context * kv_bytes_per_token(c)


def token_flops(c: dict, context: float, logits: bool) -> float:
    """Model FLOPs of one token at ``context`` keys: 2 per matrix parameter,
    the LM head only where logits are taken, attention (QK and PV) by
    context."""
    attn = 4.0 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * c["head_dim"] * context
    head = 2.0 * head_params(c) if logits else 0.0
    return 2.0 * c["num_hidden_layers"] * layer_params(c) + head + attn

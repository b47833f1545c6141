"""Plain reference for the dense GQA decoder configurations (qwen3_8b_l4,
nemotron4_15b_l4): the forward pass written out in ``jax.numpy`` from the
configuration file alone, with nothing imported from the program.

It also makes the weights. The program under test gets them as its
parameters, so both sides compute with the same numbers; the program
contributes only the layout of its parameter tree (names and shapes, from
``jax.eval_shape`` of its own init), which the values fill by name.

Parameterisation shared with the program's layout: an RMSNorm weight ``w``
scales by ``1 + w``; a LayerNorm has ``scale`` and ``bias``; matrices are
stored (in, out) and stacked over layers under ``blocks``; the vocabulary
rows may be padded past ``vocab_size`` (padding never enters a result).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# the program's ModelConfig fields that must equal the file's numbers
_PROGRAM_FIELDS = {
    "d_model": "hidden_size", "d_ff": "intermediate_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "head_dim": "head_dim", "n_layers": "num_hidden_layers",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "qk_norm": "qk_norm", "tie_embeddings": "tie_word_embeddings",
}
_MLP_ACT = {"swiglu": "swiglu", "sqrelu": "sqrelu"}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from all 64 bits of ``seed`` (``PRNGKey`` alone keeps 32)."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def program_mismatches(c: dict, pcfg) -> list:
    """Fields in which the program's model config departs from the file."""
    out = [f"{f}={getattr(pcfg, f)!r} but {k}={c[k]!r}"
           for f, k in _PROGRAM_FIELDS.items() if getattr(pcfg, f) != c[k]]
    if pcfg.norm != c["norm"]:
        out.append(f"norm={pcfg.norm!r} but {c['norm']!r}")
    if pcfg.mlp_act != _MLP_ACT[c["mlp"]]:
        out.append(f"mlp_act={pcfg.mlp_act!r} but {c['mlp']!r}")
    for f in ("attn_logit_softcap", "final_logit_softcap", "window", "moe"):
        if getattr(pcfg, f, None) is not None:
            out.append(f"{f} is set; this reference has none")
    if pcfg.post_norms or pcfg.embed_scale or pcfg.attn_kind != "global":
        out.append("post_norms, embed_scale or non-global attention set")
    return out


def _leaf_init(c: dict, path: str, shape, key, dtype):
    """Values of one parameter leaf, chosen by its name in the layout."""
    name = path.rsplit("/", 1)[-1]
    normal = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        v = normal
    elif name == "lm_head" or name.startswith("w"):
        v = normal / math.sqrt(shape[-2])          # (…, in, out)
    elif name in ("q_norm", "k_norm"):
        v = 0.1 * normal                            # RMSNorm: 1 + w
    elif name == "scale":
        v = 0.1 * normal + (1.0 if c["norm"] == "layernorm" else 0.0)
    elif name == "bias":
        v = 0.1 * normal
    else:
        raise KeyError(f"no init rule for parameter {path!r}")
    return v.astype(dtype)


def init_weights(c: dict, shapes, seed: int, dtype=jnp.float32):
    """Weights for the program's parameter layout ``shapes`` (a pytree of
    ShapeDtypeStruct), random from ``seed``, made on the default device in
    one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in leaves]
    dims = [tuple(s.shape) for _, s in leaves]

    def make(key):
        return treedef.unflatten([
            _leaf_init(c, path, shape, jax.random.fold_in(key, i), dtype)
            for i, (path, shape) in enumerate(zip(paths, dims))])

    return jax.jit(make)(seed_key(seed))


# ------------------------------------------------------------- forward

def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + w.astype(jnp.float32))


def _ln(x, w, b, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _rope(x, theta):
    """Rotary embedding, halves rotated, positions 0..S-1; x: (S, H, hd)."""
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-np.arange(0, half, dtype=np.float32) * 2.0 / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logit_stats(c: dict, w, tokens, targets, *, dtype=jnp.float32,
                precision=jax.lax.Precision.HIGHEST, chunk: int = 256):
    """Teacher-forced next-token logits over ``tokens`` (S,), reduced per
    position to: the best logit over the real vocabulary, the logit of each
    row of ``targets`` (T, S), and the argmax. ``dtype`` is the type of the
    weights, activations and matmul results (float32 for the reference,
    bfloat16 for the control); softmax and norms run in float32. S must
    be a multiple of ``chunk``."""
    d, hd = c["hidden_size"], c["head_dim"]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    vocab = c["vocab_size"]
    eps = c.get("rms_norm_eps", c.get("layer_norm_eps"))
    s = tokens.shape[0]

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          precision=precision)

    def norm(x, p):
        y = (_ln(x, p["scale"], p["bias"], eps) if c["norm"] == "layernorm"
             else _rms(x, p["scale"], eps))
        return y.astype(dtype)

    x = w["embed"][tokens].astype(dtype)
    causal = jnp.tril(jnp.ones((s, s), bool))
    for li in range(c["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[li], w["blocks"])
        a = p["attn"]
        h = norm(x, p["ln1"])
        q = mm("sd,dh->sh", h, a["wq"]).reshape(s, nh, hd)
        k = mm("sd,dh->sh", h, a["wk"]).reshape(s, nkv, hd)
        v = mm("sd,dh->sh", h, a["wv"]).reshape(s, nkv, hd)
        if c["qk_norm"]:
            q = _rms(q, a["q_norm"], 1e-6)
            k = _rms(k, a["k_norm"], 1e-6)
        q = _rope(q, c["rope_theta"]).astype(dtype)
        k = _rope(k, c["rope_theta"]).astype(dtype)
        g = nh // nkv
        qg = q.reshape(s, nkv, g, hd)
        sc = mm("qhgd,khd->hgqk", qg, k).astype(jnp.float32) / math.sqrt(hd)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = mm("hgqk,khd->qhgd", pr, v).reshape(s, nh * hd)
        x = x + mm("sh,hd->sd", o, a["wo"]).astype(dtype)
        h = norm(x, p["ln2"])
        m = p["mlp"]
        if c["mlp"] == "swiglu":
            up = jax.nn.silu(mm("sd,df->sf", h, m["w_gate"]).astype(
                jnp.float32)) * mm("sd,df->sf", h, m["w_up"]).astype(
                jnp.float32)
        else:
            up = jnp.square(jax.nn.relu(
                mm("sd,df->sf", h, m["w_up"]).astype(jnp.float32)))
        x = x + mm("sf,fd->sd", up.astype(dtype), m["w_down"]).astype(dtype)
    x = norm(x, w["final_norm"])
    head = w["embed"].T if c["tie_word_embeddings"] else w["lm_head"]

    def block(args):
        xb, tb = args                                  # (C, D), (T, C)
        lg = mm("sd,dv->sv", xb, head).astype(jnp.float32)[:, :vocab]
        tl = jnp.take_along_axis(lg[None], tb[..., None], -1)[..., 0]
        return lg.max(-1), tl, jnp.argmax(lg, -1).astype(jnp.int32)

    nb = s // chunk
    best, tl, arg = jax.lax.map(block, (
        x.reshape(nb, chunk, d),
        targets.reshape(targets.shape[0], nb, chunk).transpose(1, 0, 2)))
    return (best.reshape(s), tl.transpose(1, 0, 2).reshape(-1, s),
            arg.reshape(s))

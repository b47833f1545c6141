"""Plain reference for the Nemotron-4 configuration (nemotron4_15b_l4): the
forward pass written out in ``jax.numpy`` from the configuration file
alone, with nothing imported from the program.

One layer, as the file states it (arXiv:2402.16819, Table 1):

- ``h = LN(x)``, a LayerNorm with scale and bias;
- ``q, k, v = h·Wq, h·Wk, h·Wv`` (GQA); the leading ``head_dim ·
  partial_rotary_factor`` channels of each q and k head are rotated —
  channel i with channel i + width/2, at frequency theta^(-2i/width) — and
  the rest pass through;
- ``x += softmax(q·kᵀ/√head_dim, causal)·v·Wo``;
- ``x += relu(LN(x)·Wup)²·Wdown``, no gate.

It also makes the weights. The program under test gets them as its
parameters, so both sides compute with the same numbers; the program
contributes only the layout of its parameter tree (names and shapes, from
``jax.eval_shape`` of its own init), which the values fill by name.
Matrices are stored (in, out) and stacked over layers under ``blocks``; the
vocabulary rows may be padded past ``vocab_size`` (padding never enters a
result).
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
    "partial_rotary_factor": "partial_rotary_factor",
    "qk_norm": "qk_norm", "tie_embeddings": "tie_word_embeddings",
}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from all 64 bits of ``seed`` (``PRNGKey`` alone keeps 32)."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def rotary_width(c: dict) -> int:
    """Channels of each head that the rotary embedding turns."""
    return int(c["head_dim"] * c["partial_rotary_factor"])


def program_mismatches(c: dict, pcfg) -> list:
    """Fields in which the program's model config departs from the file,
    and anything the file states that this reference does not compute."""
    out = [f"{f}={getattr(pcfg, f, None)!r} but {k}={c[k]!r}"
           for f, k in _PROGRAM_FIELDS.items()
           if getattr(pcfg, f, None) != c[k]]
    if (c["norm"], c["mlp"], c["qk_norm"]) != ("layernorm", "sqrelu", False):
        out.append("this reference computes LayerNorm, squared ReLU and no "
                   "qk-norm only")
    if pcfg.norm != c["norm"]:
        out.append(f"norm={pcfg.norm!r} but {c['norm']!r}")
    if pcfg.mlp_act != c["mlp"]:
        out.append(f"mlp_act={pcfg.mlp_act!r} but {c['mlp']!r}")
    for f in ("attn_logit_softcap", "final_logit_softcap", "window", "moe"):
        if getattr(pcfg, f, None) is not None:
            out.append(f"{f} is set; this reference has none")
    if pcfg.post_norms or pcfg.embed_scale or pcfg.attn_kind != "global":
        out.append("post_norms, embed_scale or non-global attention set")
    return out


def _leaf_init(path: str, shape, key, dtype):
    """Values of one parameter leaf, chosen by its name in the layout."""
    name = path.rsplit("/", 1)[-1]
    normal = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        v = normal
    elif name == "lm_head" or name.startswith("w"):
        v = normal / math.sqrt(shape[-2])          # (…, in, out)
    elif name == "scale":
        v = 0.1 * normal + 1.0
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
            _leaf_init(path, shape, jax.random.fold_in(key, i), dtype)
            for i, (path, shape) in enumerate(zip(paths, dims))])

    return jax.jit(make)(seed_key(seed))


# ------------------------------------------------------------- forward

def _ln(x, w, b, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _rope(x, theta, width):
    """Rotary embedding of the leading ``width`` channels, positions
    0..S-1; x: (S, H, hd). Channels ``width``.. pass through."""
    s = x.shape[0]
    half = width // 2
    inv = theta ** (-np.arange(0, half, dtype=np.float32) * 2.0 / width)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:width].astype(jnp.float32)
    rest = x[..., width:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


def _hidden(c: dict, w, tokens, dtype, precision):
    """The final normed hidden states over ``tokens`` (S,): (S, D)."""
    hd = c["head_dim"]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    eps, width = c["layer_norm_eps"], rotary_width(c)
    s = tokens.shape[0]

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          precision=precision)

    def norm(x, p):
        return _ln(x, p["scale"], p["bias"], eps).astype(dtype)

    x = w["embed"][tokens].astype(dtype)
    causal = jnp.tril(jnp.ones((s, s), bool))
    for li in range(c["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[li], w["blocks"])
        a = p["attn"]
        h = norm(x, p["ln1"])
        q = mm("sd,dh->sh", h, a["wq"]).reshape(s, nh, hd)
        k = mm("sd,dh->sh", h, a["wk"]).reshape(s, nkv, hd)
        v = mm("sd,dh->sh", h, a["wv"]).reshape(s, nkv, hd)
        q = _rope(q, c["rope_theta"], width).astype(dtype)
        k = _rope(k, c["rope_theta"], width).astype(dtype)
        g = nh // nkv
        qg = q.reshape(s, nkv, g, hd)
        sc = mm("qhgd,khd->hgqk", qg, k).astype(jnp.float32) / math.sqrt(hd)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = mm("hgqk,khd->qhgd", pr, v).reshape(s, nh * hd)
        x = x + mm("sh,hd->sd", o, a["wo"]).astype(dtype)
        h = norm(x, p["ln2"])
        m = p["mlp"]
        up = jnp.square(jax.nn.relu(
            mm("sd,df->sf", h, m["w_up"]).astype(jnp.float32)))
        x = x + mm("sf,fd->sd", up.astype(dtype), m["w_down"]).astype(dtype)
    return norm(x, w["final_norm"])


def _head(c: dict, w):
    return w["embed"].T if c["tie_word_embeddings"] else w["lm_head"]


def logits(c: dict, w, tokens, *, dtype=jnp.float32,
           precision=jax.lax.Precision.HIGHEST):
    """Next-token logits over the real vocabulary at every position of
    ``tokens`` (S,): (S, vocab_size), float32."""
    x = _hidden(c, w, tokens, dtype, precision)
    lg = jnp.einsum("sd,dv->sv", x, _head(c, w).astype(dtype),
                    precision=precision)
    return lg.astype(jnp.float32)[:, :c["vocab_size"]]


def logit_stats(c: dict, w, tokens, targets, *, dtype=jnp.float32,
                precision=jax.lax.Precision.HIGHEST, chunk: int = 256):
    """Teacher-forced next-token logits over ``tokens`` (S,), reduced per
    position to: the best logit over the real vocabulary, the logit of each
    row of ``targets`` (T, S), and the argmax. ``dtype`` is the type of the
    weights, activations and matmul results (float32 for the reference,
    bfloat16 for the control); softmax and norms run in float32. S must
    be a multiple of ``chunk``."""
    d, vocab = c["hidden_size"], c["vocab_size"]
    s = tokens.shape[0]
    x = _hidden(c, w, tokens, dtype, precision)
    head = _head(c, w)

    def block(args):
        xb, tb = args                                  # (C, D), (T, C)
        lg = jnp.einsum("sd,dv->sv", xb.astype(dtype), head.astype(dtype),
                        precision=precision).astype(jnp.float32)[:, :vocab]
        tl = jnp.take_along_axis(lg[None], tb[..., None], -1)[..., 0]
        return lg.max(-1), tl, jnp.argmax(lg, -1).astype(jnp.int32)

    nb = s // chunk
    best, tl, arg = jax.lax.map(block, (
        x.reshape(nb, chunk, d),
        targets.reshape(targets.shape[0], nb, chunk).transpose(1, 0, 2)))
    return (best.reshape(s), tl.transpose(1, 0, 2).reshape(-1, s),
            arg.reshape(s))

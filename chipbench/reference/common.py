"""Plain float32 decoder forward pass shared by the family references.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: no kernels, no cache, no batching, no paging.  One
sequence at a time, layer by layer, so the reference fits beside nothing
else on one chip.  It imports nothing of the program.  The weights are made
here from the seed's key by the same recipe the configuration's random
weights are defined by (truncated normal at 1/sqrt(fan_in), key tree as
below), rounded to the dtype the configuration states and then computed in
float32.  A family module supplies the feed-forward block
(``ffn_weights`` / ``ffn``).

``quant`` rounds the operands of every matrix product; the identity gives
the reference, :func:`fp8` the low-precision control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def identity(x):
    return x


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (its largest magnitude
    maps to 448, the format's largest finite value) and back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


QUANT = {"f32": identity, "fp8": fp8}


def mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision=HI)


# --- weights ---------------------------------------------------------------

def dtype_of(conf):
    return jnp.dtype(conf["torch_dtype"])


def trunc_normal(key, shape, dtype, scale):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (w * scale).astype(dtype).astype(jnp.float32)


def dense_init(key, d_in, d_out, dtype):
    return trunc_normal(key, (d_in, d_out), dtype, 1.0 / math.sqrt(d_in))


def padded_vocab(conf) -> int:
    return -(-conf["vocab_size"] // 128) * 128


def key_tree(key, n_layers):
    """``(k_embed, [k_layer ...])``: the split of the seed's key into the
    embedding's key and one key per decoder layer."""
    k_emb, k_unit, _, _ = jax.random.split(key, 4)
    layers = jax.random.split(jax.random.fold_in(k_unit, 0), n_layers)
    return k_emb, layers


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_weights(conf_items, key):
    conf = dict(conf_items)
    d, v = conf["hidden_size"], padded_vocab(conf)
    dt = dtype_of(conf)
    k_emb, _ = key_tree(key, conf["num_hidden_layers"])
    w = {"table": trunc_normal(k_emb, (v, d), dt, 1.0 / math.sqrt(d))}
    if not conf["tie_word_embeddings"]:
        w["head"] = dense_init(jax.random.fold_in(k_emb, 1), d, v, dt)
    return w


def embed_weights(conf, key):
    return _embed_weights(_frozen(conf), key)


def attention_weights(conf, key):
    d, h, hkv = (conf["hidden_size"], conf["num_attention_heads"],
                 conf["num_key_value_heads"])
    hd = d // h
    dt = dtype_of(conf)
    ks = jax.random.split(key, 4)
    return {"wq": dense_init(ks[0], d, h * hd, dt),
            "wk": dense_init(ks[1], d, hkv * hd, dt),
            "wv": dense_init(ks[2], d, hkv * hd, dt),
            "wo": dense_init(ks[3], h * hd, d, dt)}


def layer_weights(conf, family, key):
    """One decoder layer's weights from its key: norms, attention, FFN."""
    ks = jax.random.split(key, 4)
    return {"attn": attention_weights(conf, ks[1]),
            "ffn": family.ffn_weights(conf, ks[3])}


# --- forward -----------------------------------------------------------------

def norm(conf, x):
    """The configuration's norm at its initial weights: LayerNorm with unit
    scale and zero bias, or RMSNorm with unit scale."""
    if conf["normalization_function"] == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + conf["layer_norm_eps"])
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + conf["rms_norm_eps"])


def rope(x, theta):
    """Rotary embedding over the whole head (rotate-half form), positions
    0..P-1; ``x [P, H, D]``."""
    p, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(p, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(conf, w, x, quant, q_block=1024):
    """Causal grouped-query self-attention of one sequence ``x [P, d]``."""
    d, h, hkv = (conf["hidden_size"], conf["num_attention_heads"],
                 conf["num_key_value_heads"])
    hd, g, p = d // h, h // hkv, x.shape[0]
    theta = float(conf["rope_theta"])
    q = rope(mm(x, w["wq"], quant).reshape(p, h, hd), theta)
    k = rope(mm(x, w["wk"], quant).reshape(p, hkv, hd), theta)
    v = mm(x, w["wv"], quant).reshape(p, hkv, hd)
    q = q.reshape(p, hkv, g, hd) * conf.get("attention_multiplier",
                                            hd ** -0.5)
    outs = []
    for s in range(0, p, q_block):
        qb = q[s:s + q_block]
        sc = jnp.einsum("qhgd,khd->hgqk", quant(qb), quant(k), precision=HI)
        qpos = jnp.arange(s, s + qb.shape[0])[:, None]
        sc = jnp.where(qpos >= jnp.arange(p)[None, :], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hgqk,khd->qhgd", quant(pr), quant(v),
                               precision=HI))
    out = jnp.concatenate(outs, 0).reshape(p, h * hd)
    return mm(out, w["wo"], quant)


def _frozen(conf):
    """A hashable view of a configuration (a static jit argument)."""
    def freeze(v):
        if isinstance(v, dict):
            return tuple(sorted((k, freeze(x)) for k, x in v.items()))
        if isinstance(v, list):
            return tuple(freeze(x) for x in v)
        return v
    return tuple(sorted((k, freeze(v)) for k, v in conf.items()
                        if k not in ("departures", "assumed", "notes",
                                     "deployment", "published", "source")))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(conf_items, family_name, quant_name, key, x):
    """One whole decoder layer on one sequence, its weights made inside."""
    from chipbench.reference import family_module
    conf, family = dict(conf_items), family_module(family_name)
    quant = QUANT[quant_name]
    w = layer_weights(conf, family, key)
    x = x + attention(conf, w["attn"], norm(conf, x), quant)
    return x + family.ffn(conf, w["ffn"], norm(conf, x), quant)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _scores(conf_items, quant_name, emb, h, tokens):
    """Logits of the rows ``h [R, d]`` (final norm applied here): the best
    logit, the logit of each token set ``tokens [S, R]``, and the argmax."""
    conf = dict(conf_items)
    quant = QUANT[quant_name]
    h = norm(conf, h)
    if conf["tie_word_embeddings"]:
        logits = jnp.matmul(quant(h), quant(emb["table"]).T, precision=HI)
    else:
        logits = mm(h, emb["head"], quant)
    at = jnp.take_along_axis(logits, tokens.T, axis=1).T
    return logits.max(axis=1), at, jnp.argmax(logits, axis=1)


def bucket(n: int, step: int = 512) -> int:
    return -(-n // step) * step


def hidden(conf, key, seqs, precision="f32"):
    """The last layer's output for each token sequence in ``seqs`` (device
    arrays, each padded at the end to a multiple of 512 positions;
    attention is causal, so the padding reaches no earlier position)."""
    from chipbench.reference import family_module
    frozen = _frozen(conf)
    family = conf["family"]
    family_module(family)                      # fail early on a new family
    emb = _embed_weights(frozen, key)
    _, layer_keys = key_tree(key, conf["num_hidden_layers"])
    table = QUANT[precision](emb["table"])
    xs = []
    for s in seqs:
        tok = np.zeros((bucket(len(s)),), np.int32)
        tok[:len(s)] = s
        xs.append(jnp.take(table, jnp.asarray(tok), axis=0))
    for layer in range(conf["num_hidden_layers"]):
        xs = [_layer(frozen, family, precision, layer_keys[layer], x)
              for x in xs]
    return xs


def score(conf, key, xs, rows, token_sets, precision="f32", row_block=256):
    """Score the rows ``rows[i]`` of each hidden state ``xs[i]``: returns
    per sequence numpy ``(best, at, argmax)``, where ``at[j]`` is the logit
    of ``token_sets[j][i]`` at each row."""
    frozen = _frozen(conf)
    emb = _embed_weights(frozen, key)
    out = []
    for i, (x, r) in enumerate(zip(xs, rows)):
        parts = []
        for s in range(0, len(r), row_block):
            n = len(r[s:s + row_block])
            pad = lambda a: np.concatenate(
                [np.asarray(a[s:s + row_block], np.int32),
                 np.zeros(row_block - n, np.int32)])
            toks = np.stack([pad(ts[i]) for ts in token_sets])
            best, at, arg = _scores(frozen, precision, emb,
                                    jnp.take(x, jnp.asarray(pad(r)), axis=0),
                                    jnp.asarray(toks))
            parts.append((np.asarray(best)[:n], np.asarray(at)[:, :n],
                          np.asarray(arg)[:n]))
        out.append((np.concatenate([p[0] for p in parts]),
                    np.concatenate([p[1] for p in parts], axis=1),
                    np.concatenate([p[2] for p in parts])))
    return out

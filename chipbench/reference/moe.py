"""Plain reference of the mixture-of-experts family (GraniteMoe's block):
a float32 router, softmax over the experts, the top-k renormalized, and
every expert a SwiGLU feed-forward block.  Dropless: every token reaches
each of its k experts.  Computed densely, every expert over every token
with a gate of zero where it was not chosen, which is the same sum."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import HI, dense_init, dtype_of, mm


def ffn_weights(conf, key):
    d, f = conf["hidden_size"], conf["intermediate_size"]
    e = conf["num_local_experts"]
    dt = dtype_of(conf)
    ks = jax.random.split(key, 4)
    experts = lambda k, a, b: jax.vmap(lambda kk: dense_init(kk, a, b, dt))(
        jax.random.split(k, e))
    return {"router": dense_init(ks[0], d, e, jnp.float32),
            "gate": experts(ks[1], d, f), "up": experts(ks[2], d, f),
            "down": experts(ks[3], f, d)}


def ffn(conf, w, x, quant):
    e, k = conf["num_local_experts"], conf["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(x, w["router"], quant), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], top_e].set(top_p)      # [P, E]
    qx = quant(x)
    h = jax.nn.silu(jnp.einsum("pd,edf->epf", qx, quant(w["gate"]),
                               precision=HI)) * \
        jnp.einsum("pd,edf->epf", qx, quant(w["up"]), precision=HI)
    y = jnp.einsum("epf,efd->epd", quant(h), quant(w["down"]), precision=HI)
    return jnp.einsum("pe,epd->pd", gates, y, precision=HI)

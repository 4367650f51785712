"""Plain reference of the dense family: a SwiGLU feed-forward block.
The decoder around it is :mod:`chipbench.reference.common`."""

from __future__ import annotations

import jax

from chipbench.reference.common import dense_init, dtype_of, mm


def ffn_weights(conf, key):
    d, f = conf["hidden_size"], conf["intermediate_size"]
    dt = dtype_of(conf)
    ks = jax.random.split(key, 3)
    return {"gate": dense_init(ks[0], d, f, dt),
            "up": dense_init(ks[1], d, f, dt),
            "down": dense_init(ks[2], f, d, dt)}


def ffn(conf, w, x, quant):
    h = jax.nn.silu(mm(x, w["gate"], quant)) * mm(x, w["up"], quant)
    return mm(h, w["down"], quant)

"""Operations and bytes that one prompt's prefill requires, computed from
the configuration's shapes (not from what the program happens to do).

A prefill of ``p`` tokens runs every matrix product of every layer on each
token, with only the experts a token is routed to, attends causally over
the prompt, and computes the logits of its last position alone.  It reads
every weight once (the unembedding's table whole, the embedding's rows
for the prompt's tokens), and writes the key and value of every position
of every layer.
"""

from __future__ import annotations

from chipbench import costs


def prefill_flops(conf, p: int) -> float:
    """2 x active parameters x p for the matrix products, 2 L h hd p^2 for
    causal attention (scores and values, each over half the square), and
    the last position's unembedding."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    layers = conf["num_hidden_layers"]
    _, ffn_used, router = costs.ffn_params(conf)
    active = layers * (costs.attention_params(conf) + ffn_used + router)
    return (2.0 * active * p + 2.0 * layers * h * (d // h) * p * p
            + 2.0 * d * costs.padded_vocab(conf))


def prefill_bytes(conf, p: int) -> float:
    """Every weight once, the prompt's embedding rows, and the key and value
    written for every position of every layer: what a decode step of ``p``
    requests over no cached context reads and writes."""
    return costs.decode_step_bytes(conf, p, 0)


def least_time(conf, p: int, peaks: dict) -> float:
    """Seconds the chip needs at least for one ``p``-token prefill."""
    return costs.least_time(prefill_flops(conf, p), prefill_bytes(conf, p),
                            peaks)[0]

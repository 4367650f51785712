"""Operations and bytes that the work of a decode step requires, computed
from the configuration's shapes (not from what the program happens to do),
and the least time the chip could take for them.

A decode step of ``batch`` requests reads every weight once (the embedding
table only at the rows it looks up), reads each request's cached keys and
values once (``context`` positions summed over the batch), and writes one
new position per request.  Its operations are the matrix products of one
token per request, with the experts a token is routed to, and the
attention over its context.
"""

from __future__ import annotations

ITEM = 2                          # bytes of one bfloat16 element


def padded_vocab(conf) -> int:
    return -(-conf["vocab_size"] // 128) * 128


def _dims(conf):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return d, h, conf["num_key_value_heads"], d // h


def attention_params(conf) -> int:
    d, h, hkv, hd = _dims(conf)
    return 2 * d * h * hd + 2 * d * hkv * hd


def ffn_params(conf) -> tuple:
    """(bf16 parameters of one layer's FFN, of them used per token, f32
    router parameters)."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    if conf["family"] == "moe":
        e, k = conf["num_local_experts"], conf["num_experts_per_tok"]
        return e * 3 * d * f, k * 3 * d * f, d * e
    return 3 * d * f, 3 * d * f, 0


def kv_bytes_per_position(conf) -> int:
    """Key and value of one position in every layer."""
    _, _, hkv, hd = _dims(conf)
    return conf["num_hidden_layers"] * 2 * hkv * hd * ITEM


def frame_bytes(conf) -> int:
    """One burst frame: one position of one layer's key or value."""
    _, _, hkv, hd = _dims(conf)
    return hkv * hd * ITEM


def decode_step_flops(conf, batch: int, context: int) -> float:
    d, h, _, hd = _dims(conf)
    layers = conf["num_hidden_layers"]
    _, ffn_used, router = ffn_params(conf)
    per_token = layers * (attention_params(conf) + ffn_used + router) \
        + d * padded_vocab(conf)
    return 2.0 * per_token * batch + 4.0 * layers * h * hd * context


def decode_step_bytes(conf, batch: int, context: int) -> float:
    d = conf["hidden_size"]
    layers = conf["num_hidden_layers"]
    ffn_all, _, router = ffn_params(conf)
    weights = layers * ((attention_params(conf) + ffn_all) * ITEM
                        + router * 4)
    vocab = padded_vocab(conf) * d * ITEM           # the unembedding
    embed_rows = batch * d * ITEM
    kv = kv_bytes_per_position(conf) * (context + batch)
    return float(weights + vocab + embed_rows + kv)


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, "flops" or "bytes"): the larger of the two bounds."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def burst_kernel_bytes(conf, live_frames: int, batch: int) -> float:
    """Bytes the decode step's burst kernels must move: the key and value
    gathers read every live frame of every layer from the pool and write it
    banked, the two scatters read it banked and write it back (four
    streams, each frame read once and written once); a mixture-of-experts
    step also scatters each of the batch's top-k assignments to its
    expert slot and gathers it back, one ``hidden_size`` row each way, in
    every layer."""
    layers = conf["num_hidden_layers"]
    kv = 4 * 2 * layers * live_frames * frame_bytes(conf)
    moe = 0
    if conf["family"] == "moe":
        rows = batch * conf["num_experts_per_tok"]
        moe = 2 * 2 * layers * rows * conf["hidden_size"] * ITEM
    return float(kv + moe)

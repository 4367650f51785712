"""Chip smoke test: stablelm-1.6b served at full width on a TPU, with the
Medusa burst kernels compiled by Mosaic.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded page pool, four chips

One chip, three phases:

1. kernels — the fused gather, scatter and dense burst kernels, compiled,
   against the pure-jnp oracles bit for bit at N = 32 ports, with sentinel
   rows around a mapped last pool row (the scatter's hazard case);
2. serve — four requests (prompt 128, 32 new tokens) through
   ``ServingEngine``, the path of ``python -m repro.launch.serve --engine``,
   on the default fabric (paged pool sized to the exact reach, fused
   gather, packed bursts, ``word_fold="auto"``); the compiled decode step
   must hold the Pallas kernels (``tpu_custom_call``);
3. reference — the same requests with ``ops.use_kernels(False)``: the
   greedy token streams must be identical, since the bursts only move data.

``--chips 4`` runs only the sharded-pool phase: the same requests at
``pool_shards=4`` under both collectives (``all_to_all``, ``ring``), each
compared token for token with the one-device fused run, one process
driving all four chips.

Parameters are random, made from ``--seed``.  The earlier output lines are
bring-up diagnostics (compile seconds, steady decode-step seconds, tokens/s,
peak device bytes), not benchmark records.  The last line is one JSON
object, printed only when every phase passed.  There is no CPU fallback: on
any other backend, or without the ``repro`` package beside it, the script
exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

try:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.transpose import read_network_oracle, write_network_oracle
    from repro.fabric.scheduler import FRAME_SENTINEL
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import api
    from repro.serving import Request, ServingEngine
except ImportError as e:                  # not beside a checkout of the repo
    sys.exit(f"chip_smoke: cannot import the repro package from "
             f"{os.path.join(ROOT, 'src')}: {e}")

ARCH = "stablelm-1.6b"
SLOTS, PROMPT, NEW, T_MAX = 4, 128, 32, 256


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds spent in backend compiles (cache hits excluded)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def pool_pages(cfg) -> int:
    """Pages for exactly the requests' reach, so the last pool frame is
    mapped (a sentinel landing on it would show in the tokens)."""
    page = cfg.resolved_fabric.page_size
    return SLOTS * -(-(PROMPT + NEW) // page)


def kernel_phase(seed: int) -> None:
    """The compiled burst kernels against their oracles at N = 32."""
    n, w = 32, 32                  # one stablelm frame as 32 u32 words
    rng = np.random.default_rng(seed)
    lines_n, k = 8 * n, 4 * n
    words = lambda shape: jnp.asarray(
        rng.integers(0, 2 ** 32, shape, dtype=np.uint64).astype(np.uint32))
    pool = words((lines_n, n, w))
    # leading, middle and trailing sentinels around a mapped last row
    rows = rng.permutation(lines_n - 1)[: k - 9]
    idx = np.concatenate([[FRAME_SENTINEL] * 3, rows[:60], [FRAME_SENTINEL],
                          rows[60:], [lines_n - 1],
                          [FRAME_SENTINEL] * 4]).astype(np.int32)
    check(idx.shape == (k,), f"bad index list {idx.shape}")
    idx = jnp.asarray(idx)

    got = ops.burst_gather_read(pool, idx, n)
    want = read_network_oracle(
        jnp.take(pool, idx, axis=0, mode="fill", fill_value=0), n)
    check(bool((got == want).all()), "gather burst kernel != oracle")

    banked = words((k // n, n, n, w))
    got = ops.burst_scatter_write(banked, idx, pool, n)
    want = pool.at[idx].set(write_network_oracle(banked, n), mode="drop")
    check(bool((got == want).all()), "scatter burst kernel != oracle")

    for dtype in (jnp.uint32, jnp.bfloat16):
        tile = words((n, n, 4096)).astype(dtype)
        check(bool((ops.burst_read(tile, n)
                    == read_network_oracle(tile, n)[0]).all()),
              f"dense burst kernel != oracle ({jnp.dtype(dtype).name})")
    log("kernels: gather, scatter and dense bursts match the oracles "
        "bit for bit at N=32")


def serve(cfg, params, prompts, label: str, clock: CompileClock,
          want_text: bool = False, **engine_kw):
    """Serve the requests to completion; returns ``(tokens, engine,
    decode_hlo_text)``."""
    compile0 = clock.seconds
    eng = ServingEngine(cfg, params, max_slots=SLOTS, t_max=T_MAX,
                        pool_pages=pool_pages(cfg), **engine_kw)
    reqs = [Request(i, prompts[i], max_new_tokens=NEW)
            for i in range(SLOTS)]
    for r in reqs:
        check(eng.submit(r) == "queued", f"{label}: request {r.rid} shed")
    t0 = time.perf_counter()
    eng.step()                      # admission + first decode (compiles)
    jax.block_until_ready(eng.kv.caches)
    first = time.perf_counter() - t0
    text = eng.decode_step_text() if want_text else ""
    steps = []
    while not eng.drained:
        t = time.perf_counter()
        eng.step()
        jax.block_until_ready(eng.kv.caches)
        steps.append(time.perf_counter() - t)
    total = first + sum(steps)
    tokens = [list(r.generated) for r in reqs]
    check(all(len(t) == NEW for t in tokens),
          f"{label}: token counts {[len(t) for t in tokens]}")
    check(all(0 <= x < cfg.vocab_size for t in tokens for x in t),
          f"{label}: token outside the vocabulary")
    logits = np.asarray(eng.last_logits, np.float32)
    check(logits.shape == (SLOTS, cfg.vocab_size)
          and bool(np.isfinite(logits).all()),
          f"{label}: last logits {logits.shape} not finite")
    step = float(np.median(steps))
    log(f"{label}: compile {clock.seconds - compile0:.2f} s (backend); "
        f"first step {first:.2f} s (admission + prefill + compile); "
        f"steady decode step {step * 1e3:.2f} ms over {len(steps)} steps "
        f"({SLOTS / step:.1f} tok/s steady, "
        f"{SLOTS * NEW / total:.1f} tok/s end to end); "
        f"census of the decode step and the admission wave: "
        f"{eng.fabric_stats.kernel_bursts} kernel bursts, "
        f"{eng.fabric_stats.gather_fused_bursts} sparse bursts")
    return tokens, eng, text


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} bytes"


def logits_gap(ref: np.ndarray, eng) -> str:
    """Diagnostic beside the token check: how far the final step's logits
    sit from the reference run's (0.0 is bit-identical)."""
    got = np.asarray(eng.last_logits, np.float32)
    return f"last-step logits max |diff| {float(np.abs(got - ref).max())}"


def one_chip(cfg, params, prompts, seed: int, clock: CompileClock) -> None:
    kernel_phase(seed)
    on, eng, text = serve(cfg, params, prompts, "kernels on", clock,
                          want_text=True)
    check(eng.fabric_stats.kernel_bursts > 0,
          "kernels on: no burst lowered through the Pallas kernels")
    check("tpu_custom_call" in text,
          "kernels on: compiled decode step holds no tpu_custom_call")
    log(f"kernels on: compiled decode step holds "
        f"{text.count('tpu_custom_call')} tpu_custom_call sites; "
        f"peak device memory {peak_bytes()}")
    ref = np.asarray(eng.last_logits, np.float32)
    del eng
    ops.use_kernels(False)
    try:
        off, eng, _ = serve(cfg, params, prompts, "kernels off", clock)
    finally:
        ops.use_kernels(True)
    check(eng.fabric_stats.kernel_bursts == 0,
          "kernels off: a burst still lowered through a kernel")
    check(on == off, f"token streams differ between kernels on and off: "
          f"{on} vs {off}")
    log(f"tokens identical with kernels on and off "
        f"({SLOTS} requests x {NEW} tokens); {logits_gap(ref, eng)}; "
        f"request 0: {on[0][:8]}...")


def four_chips(cfg, params, prompts, clock: CompileClock) -> None:
    check(len(jax.devices()) >= 4,
          f"--chips 4 needs four devices, found {len(jax.devices())}")
    base, eng, _ = serve(cfg, params, prompts, "1 device fused", clock)
    ref = np.asarray(eng.last_logits, np.float32)
    del eng
    for collective in ("all_to_all", "ring"):
        label = f"pool_shards=4 {collective}"
        toks, eng, text = serve(cfg, params, prompts, label, clock,
                                want_text=True, pool_shards=4,
                                collective=collective)
        mesh_devs = {d.id for d in eng.fabric.mesh.devices.flat}
        check(len(mesh_devs) == 4, f"{label}: pool mesh on {mesh_devs}")
        leaf = eng.kv.caches["unit"][0]["k"]
        spread = {d.id for d in leaf.sharding.device_set}
        check(len(spread) == 4, f"{label}: pool leaf on devices {spread}")
        check("tpu_custom_call" in text,
              f"{label}: no Pallas kernel inside the sharded decode step")
        check(eng.fabric_stats.collective_calls > 0,
              f"{label}: no collective exchange")
        check(toks == base, f"{label}: tokens differ from the 1-device run:"
              f" {toks} vs {base}")
        log(f"{label}: pool on devices {sorted(spread)}; gather/scatter "
            f"kernels compiled inside shard_map; tokens identical to the "
            f"1-device fused run; {logits_gap(ref, eng)}; peak device "
            f"memory {peak_bytes()}")
        del eng


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels + serve + kernels-off reference; "
                         "4: only the sharded page pool over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"chip_smoke: FAIL: JAX found no TPU (platform "
            f"{dev.platform!r}); this check has no CPU fallback")
        return 1
    clock = CompileClock()
    try:
        check(ops.kernels_enabled() and not ops.interpret_mode(),
              "Pallas kernels are not enabled in compiled mode")
        cfg = get_config(ARCH)
        t0 = time.perf_counter()
        params = jax.jit(api.init_params, static_argnums=0)(
            cfg, jax.random.PRNGKey(args.seed))
        jax.block_until_ready(params)
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
        log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
            f"{cfg.n_heads}/{cfg.n_kv_heads} heads, vocab {cfg.vocab_size}; "
            f"{n_params} random parameters (seed {args.seed}) in "
            f"{time.perf_counter() - t0:.2f} s; fabric N="
            f"{cfg.resolved_fabric.n_ports} on {dev.device_kind}")
        prompts = np.random.default_rng(args.seed).integers(
            0, cfg.vocab_size, (SLOTS, PROMPT)).astype(np.int32)
        if args.chips == 4:
            four_chips(cfg, params, prompts, clock)
        else:
            one_chip(cfg, params, prompts, args.seed, clock)
    except SmokeFailure as e:
        log(f"chip_smoke: FAIL: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

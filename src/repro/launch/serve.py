"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Batched request serving: prefill installs the line-major KV caches, the
decode loop reads them through the model's fabric (``cfg.resolved_fabric``;
override with ``--fabric-impl``).  ``--smoke`` runs the reduced config on
CPU with real tokens; ``--engine`` serves through the continuous-batching
:class:`repro.serving.ServingEngine` on the paged KV layout instead of the
one-shot batch generate — its decode step is burst-scheduled (one read +
one write network invocation per dtype per step; ``--pack`` selects the
burst layout, ``--word-fold`` the machine-word lane folding cap,
``--serve-fsdp`` adds the weight stream to the read burst).  KV storage
defaults to the shared physical page pool (``--paged-pool`` /
``--no-paged-pool``, ``--pool-pages`` sizes it): gather-based decode
through the per-slot page table, admission installed as ``prefill/*``
write-burst traffic, retirement reclaims pages.  Under oversubscription the
engine degrades gracefully instead of stalling: ``--priority-classes``
spreads the synthetic load over priority classes, ``--preempt
{swap,recompute,off}`` picks the victim policy (page-level swap over the
fabric's ``swap/*`` streams, or drop + re-prefill), ``--swap-space-pages``
caps the host swap space, and ``--check-pool`` runs the free-list
conservation invariant every step.  ``--aging`` turns on anti-starvation
aging (queued wait boosts effective priority) and ``--max-queue`` bounds
the submit queue with shed-on-overflow backpressure; for production-shaped
traffic with deadlines and per-class latency percentiles use
``python -m repro.launch.loadgen``.  On the medusa fabric with kernels
enabled each burst lowers as one fused Pallas launch.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.models.moe import routes_dropless


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-12b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--kv-layout", "--fabric-impl", dest="kv_layout",
                    default=None,
                    choices=[None, "medusa", "crossbar", "oracle", "fused"])
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV page size in timesteps (0 = fabric default)")
    ap.add_argument("--paged-pool", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="back the engine's full-attention KV in one shared "
                         "physical page pool with gather-based decode "
                         "(default: FabricConfig.paged_pool, on); "
                         "--no-paged-pool keeps the dense per-slot "
                         "reservation (the A/B baseline)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="physical pages in the shared pool (0 = the dense "
                         "reservation's worth: max_slots * pages_per_slot)")
    ap.add_argument("--fused-gather", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="fuse the pool's logical->physical gather into the "
                         "burst contract: the networks move only the frames "
                         "the page table maps (default: FabricConfig."
                         "fused_gather, auto-on with the pool); "
                         "--no-fused-gather keeps the gather-after-burst "
                         "fallback that banks the whole pool")
    ap.add_argument("--engine", action="store_true",
                    help="serve through the paged continuous-batching engine")
    ap.add_argument("--pool-shards", type=int, default=0,
                    help="shard the physical page pool over this many "
                         "devices on a `pool` mesh axis: fused sparse "
                         "bursts lower as per-shard gathers bridged by one "
                         "collective, pages stripe round-robin across "
                         "shards (0 = FabricConfig.pool_shards, off); "
                         "needs XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=<shards> on CPU")
    ap.add_argument("--collective", default=None,
                    choices=[None, "all_to_all", "ring"],
                    help="exchange-hop collective for the sharded pool: "
                         "XLA's all_to_all or the explicit ring of "
                         "ppermute rotations (the butterfly-vs-rotation "
                         "A/B; value-identical)")
    ap.add_argument("--pack", default=None, choices=[None, "packed", "pad"],
                    help="burst layout for the scheduled decode step")
    ap.add_argument("--word-fold", default=None,
                    choices=[None, "auto", "1", "2", "4"],
                    help="machine-word lane folding cap for packed bursts "
                         "(auto = widest the dtype/geometry/x64 allow)")
    ap.add_argument("--serve-fsdp", action="store_true",
                    help="stream ZeRO-1 sharded weights through the decode "
                         "step's read burst (weight_stream ports)")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="spread the synthetic requests over this many "
                         "priority classes (request i gets priority "
                         "i %% P); higher classes preempt lower when the "
                         "pool is oversubscribed")
    ap.add_argument("--preempt", default=None,
                    choices=[None, "swap", "recompute", "off"],
                    help="victim policy when a higher-priority request "
                         "would otherwise wait: swap pages to host over "
                         "the fabric (swap/* streams), drop + re-prefill, "
                         "or off = the head-of-line gate (default: "
                         "FabricConfig.preempt)")
    ap.add_argument("--swap-space-pages", type=int, default=None,
                    help="host swap-space cap in pages; evictions beyond "
                         "it fall back to recompute (default: FabricConfig."
                         "swap_space_pages, 0 = unbounded)")
    ap.add_argument("--check-pool", action="store_true",
                    help="run the pool's free-list conservation invariant "
                         "after every engine step (debug)")
    ap.add_argument("--aging", type=int, default=0,
                    help="anti-starvation aging quantum: each this-many "
                         "steps a queued request waits boosts its "
                         "effective priority one class, in admission rank "
                         "and preemption eligibility both (0 = strict "
                         "priority order, low classes can starve)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded submit queue: submits beyond this depth "
                         "are shed with backpressure "
                         "(SchedulerStats.shed_queue_full; 0 = unbounded)")
    ap.add_argument("--spec-decode-k", type=int, default=0,
                    help="Medusa-heads speculative decoding: k draft heads "
                         "propose a candidate branch per slot each step and "
                         "the engine's verify_step accepts its longest "
                         "matching prefix against the committed argmax "
                         "(token stream identical to k=0; the census "
                         "reports the acceptance rate)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.kv_layout:
        cfg = dataclasses.replace(cfg, kv_layout=args.kv_layout)
        if cfg.fabric is not None:   # explicit fabric: keep the switch single
            cfg = dataclasses.replace(
                cfg, fabric=dataclasses.replace(cfg.fabric,
                                                impl=args.kv_layout))
    if args.page_size:
        cfg = dataclasses.replace(
            cfg, fabric=dataclasses.replace(cfg.resolved_fabric,
                                            page_size=args.page_size))
    if args.pack:
        cfg = dataclasses.replace(
            cfg, fabric=dataclasses.replace(cfg.resolved_fabric,
                                            pack=args.pack))
    if args.word_fold:
        fold = "auto" if args.word_fold == "auto" else int(args.word_fold)
        cfg = dataclasses.replace(
            cfg, fabric=dataclasses.replace(cfg.resolved_fabric,
                                            word_fold=fold))
    if args.serve_fsdp:
        cfg = dataclasses.replace(cfg, serve_fsdp=True)
    if args.paged_pool is not None:
        cfg = dataclasses.replace(
            cfg, fabric=dataclasses.replace(cfg.resolved_fabric,
                                            paged_pool=args.paged_pool))
    if args.fused_gather is not None:
        cfg = dataclasses.replace(
            cfg, fabric=dataclasses.replace(cfg.resolved_fabric,
                                            fused_gather=args.fused_gather))
    if args.spec_decode_k:
        # draft heads are model params: init_params grows the "draft" entry
        cfg = dataclasses.replace(cfg, spec_heads=args.spec_decode_k)
    fab = cfg.resolved_fabric

    data = SyntheticLM(cfg, batch=args.batch,
                       seq=args.prompt_len + (cfg.n_patches or 0))
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
    batch.pop("targets")
    params = api.init_params(cfg, jax.random.PRNGKey(0))

    t_max = args.prompt_len + args.gen_len + (cfg.n_patches or 0)
    print(f"arch={cfg.name} fabric=[impl={fab.impl} N={fab.n_ports} "
          f"W_acc={fab.lane_width} page={fab.page_size} pack={fab.pack} "
          f"fold={fab.word_fold}] "
          f"batch={args.batch} prompt={args.prompt_len} gen={args.gen_len}")
    t0 = time.time()
    if args.engine:
        from repro.serving import Request, ServingEngine
        eng = ServingEngine(cfg, params, max_slots=args.batch, t_max=t_max,
                            pool_pages=args.pool_pages,
                            pool_shards=args.pool_shards,
                            collective=args.collective,
                            preempt=args.preempt,
                            swap_space_pages=args.swap_space_pages,
                            check_pool=args.check_pool,
                            spec_decode_k=args.spec_decode_k,
                            aging=args.aging, max_queue=args.max_queue)
        prompts = np.asarray(batch["tokens"])
        reqs = [Request(i, prompts[i], max_new_tokens=args.gen_len,
                        priority=i % max(args.priority_classes, 1))
                for i in range(args.batch)]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion()
        dt = time.time() - t0
        kv = eng.kv
        print(f"served {args.batch} requests in {dt:.2f}s "
              f"({args.batch * args.gen_len / dt:.1f} tok/s); "
              f"admission moved {kv.tokens_moved} of "
              f"{kv.tokens_moved_dense} dense-splice timesteps")
        if kv.paged:
            pool = kv.pool
            print(f"page pool: {pool.n_pages} physical pages x "
                  f"{pool.page_size} timesteps "
                  f"(dense reservation {kv.dense_reserved_pages} pages); "
                  f"{pool.pages_allocated} allocated, "
                  f"{pool.pages_reclaimed} reclaimed, "
                  f"{pool.pages_in_use} in use at exit; "
                  f"{kv.prefill_bursts} prefill write bursts, "
                  f"{kv.prefill_splices} splice fallbacks")
            fs = eng.fabric_stats
            print(f"preemption[{eng.preempt}]: {fs.preemptions} "
                  f"preemptions; swap {pool.pages_swapped_out} pages out / "
                  f"{pool.pages_swapped_in} back "
                  f"({fs.swap_out_words} words out, {fs.swap_in_words} in "
                  f"over {fs.swap_bursts} swap bursts); "
                  f"{fs.bursts_retried} bursts retried, "
                  f"{fs.faults_recovered} faults recovered")
            print(f"admission: {fs.requests_shed} shed "
                  f"({fs.shed_queue_full} queue-full, "
                  f"{fs.shed_deadline} unmeetable-deadline); "
                  f"SLO misses {fs.slo_missed_served} served late + "
                  f"{fs.slo_missed_shed} shed; "
                  f"{fs.aging_promotions} aging promotions")
        else:
            print("page pool: off (dense per-slot reservation)")
        fs = eng.fabric_stats
        if fs.flushes:
            print(f"fabric per step: {fs.network_calls} network calls for "
                  f"{fs.streams_served} streams over {fs.flushes} bursts "
                  f"({fs.words_moved} words moved, {fs.words_padded} padded, "
                  f"{fs.words_folded} folded into machine words, "
                  f"{fs.kernel_bursts} fused-kernel bursts, "
                  f"{fs.prefill_bursts} prefill bursts)")
            if fs.gather_fused_bursts:
                print(f"fused gather: {fs.words_live} live-frame words "
                      f"through {fs.gather_fused_bursts} sparse-extent "
                      f"bursts (decode traffic scales with live tokens, "
                      f"not pool capacity)")
                if fs.collective_calls:
                    local = fs.words_moved - fs.words_cross_shard
                    print(f"sharded pool: {eng.pool_shards} shards x "
                          f"{eng.fabric.config.collective} — "
                          f"{fs.words_cross_shard} words crossed shards vs "
                          f"{max(local, 0)} local, through "
                          f"{fs.collective_calls} collective exchanges "
                          f"(pages striped "
                          f"{eng.kv.pool.free_pages_by_shard} free/shard)")
            elif eng.paged:
                print("fused gather: off — gather-after-burst fallback "
                      "banks the whole pool each step")
        else:
            print("fabric: decode step unscheduled (geometry fallback)")
        if cfg.moe is not None and routes_dropless(cfg.moe):
            print("moe dispatch: dropless (capacity_factor >= n_experts / "
                  "top_k): grouped expert matmuls over every assignment")
        elif cfg.moe is not None:
            print(f"moe dispatch: {fs.tokens_dropped} token assignments "
                  f"dropped at capacity over the whole run (sentinel rows "
                  f"in the dispatch scatter; residual passed through)")
        if eng.spec_k:
            print(f"speculative decode[k={eng.spec_k}]: "
                  f"{eng.spec_accepted}/{eng.spec_proposed} draft tokens "
                  f"accepted ({eng.spec_acceptance:.1%}), "
                  f"{eng.spec_rejected} rejected; per-step gathered-branch "
                  f"words {fs.words_live} (the k candidate branches share "
                  f"the committed prefix, so the fused page-table gather "
                  f"serves all of them)")
        print("sample:", reqs[0].generated[:16])
    else:
        extra = {k: batch[k] for k in ("patch_embeds", "frames") if k in batch}
        out = api.greedy_generate(params, batch["tokens"], cfg,
                                  steps=args.gen_len, t_max=t_max, extra=extra)
        out = np.asarray(out)
        dt = time.time() - t0
        print(f"generated {out.shape} in {dt:.2f}s "
              f"({args.batch * args.gen_len / dt:.1f} tok/s)")
        print("sample:", out[0][:16].tolist())


if __name__ == "__main__":
    main()

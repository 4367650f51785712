"""Compile a configuration's decode step for a described TPU v5e, no chip
needed, and print what the compiler reports: the bytes of arguments, outputs
and temporaries (``memory_analysis``) and the number of Mosaic kernel call
sites (``tpu_custom_call``).  Run it by hand before spending chip time on a
new configuration or deployment size:

    JAX_PLATFORMS=cpu PYTHONPATH=src python chipbench/aot_check.py \\
        --config stablelm-1.6b --t-max 2048 --slots 1 2 --pool-pages 64

The step is the engine's own jitted decode step (``ServingEngine._decode``,
built by the engine for that deployment), lowered with abstract operands at
the largest live-frame bucket the pool can hold, as set-up warms it.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax                                                    # noqa: E402
from jax.sharding import SingleDeviceSharding                 # noqa: E402

from chipbench import harness, model                          # noqa: E402


def compile_decode_step(cfg, slots: int, t_max: int, pool_pages: int,
                        device):
    """The engine for ``slots`` x ``t_max`` over ``pool_pages`` pages, and
    its decode step compiled for ``device`` at the pool's largest bucket."""
    from repro.kernels import ops
    from repro.models import api
    from repro.serving import ServingEngine

    ops.interpret_mode = lambda: False      # compile the kernels for the chip
    params = jax.eval_shape(lambda k: api.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_slots=slots, t_max=t_max,
                        pool_pages=pool_pages)
    *_, args = harness.decode_args_per_bucket(eng)
    one = SingleDeviceSharding(device)
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), args)
    return eng, args[5].shape[0], eng._decode.lower(*abstract).compile()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--t-max", type=int, required=True)
    ap.add_argument("--slots", type=int, nargs="+", required=True)
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="pages in the shared pool (0: a reach per slot)")
    args = ap.parse_args()
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cfg = model.model_config(model.load(ROOT, args.config))
    for slots in args.slots:
        head = (f"{args.config} slots={slots} t_max={args.t_max} "
                f"pool_pages={args.pool_pages}")
        try:
            eng, live, compiled = compile_decode_step(
                cfg, slots, args.t_max, args.pool_pages, topo.devices[0])
        except Exception as e:          # the compiler's refusal is the finding
            print(f"{head}: does not compile: "
                  f"{str(e).splitlines()[0][:300]}", flush=True)
            continue
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{head} t_alloc={eng.t_alloc} "
              f"pool_pages_as_built={eng.kv.pool.n_pages} "
              f"live_bucket_max={live} "
              f"argument_bytes={m.argument_size_in_bytes} "
              f"output_bytes={m.output_size_in_bytes} "
              f"temp_bytes={m.temp_size_in_bytes} "
              f"alias_bytes={m.alias_size_in_bytes} "
              f"total_bytes={total} "
              f"tpu_custom_call={compiled.as_text().count('tpu_custom_call')}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

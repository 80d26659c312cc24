"""Compile a serving cell's programs for a described TPU v5e chip, without
the chip, and print what ``memory_analysis()`` says each needs.

    JAX_PLATFORMS=cpu python3 bench/rehearse_memory.py \
        --workload stablelm-3b.chat

Compiles the engine's bucketed prefill at the largest bucket the cell's
traffic reaches and its fused decode step over the whole slot pool, both
with the cell's weights and cache dtypes, for one chip of a described
``v5e:2x2``.  The Pallas kernels are compiled, not interpreted.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(_ROOT / "src"), str(_ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro.config import QuantConfig
    from repro.kernels import ops
    from repro.models.registry import build_model
    from repro.serve.engine import prefill_bucket

    jax.config.update("jax_enable_compilation_cache", False)
    ops._interpret_default = lambda: False
    cell = harness.load_cell(args.workload)
    t = harness.traffic_params(cell, False)
    mc = harness.model_config(cell, False)
    model = build_model(mc, QuantConfig(fmt="none", backend=t["backend"]))
    kv = {"kv_fmt": t["kv_fmt"]}
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    params = sds(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    K, S = t["slots"], t["max_seq"]
    bucket = prefill_bucket(t["prompt"]["max"], S)
    cache = sds(model.slot_cache_spec(K, S, **kv))
    tokens = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=chip)
    plen = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    vec = lambda dt: jax.ShapeDtypeStruct((K,), dt, sharding=chip)

    def prefill(p, tok, n):
        return model.prefill(p, {"tokens": tok}, prompt_len=n, **kv)

    def step(p, c, tok, active):
        logits, c = model.decode_slots(p, c, tok, active, **kv)
        return jnp.argmax(logits, -1), c

    progs = {
        f"prefill[1,{bucket}]": jax.jit(prefill).lower(params, tokens, plen),
        f"decode[{K}x{S}]": jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, vec(jnp.int32), vec(jnp.bool_)),
    }
    limit = 16_909_336_064   # bytes_limit one TPU v5 lite chip reports
    for name, lowered in progs.items():
        ma = lowered.compile().memory_analysis()
        need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        print(f"{name}: arguments {ma.argument_size_in_bytes} outputs "
              f"{ma.output_size_in_bytes} aliased {ma.alias_size_in_bytes} "
              f"temp {ma.temp_size_in_bytes} need {need} of {limit}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

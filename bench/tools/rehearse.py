"""Compile a serving cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/tools/rehearse.py --workload internlm2-20b.chat

Lowers the program's longest prefill, its slot write and its decode step at
the cell's sizes (slots, ``max_len``, bf16 weights) for device 0 of a
described ``v5e:2x2`` topology and prints each ``memory_analysis()`` with
the weight and cache bytes, as one JSON line per program. Nothing runs, so
nothing here is a time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--slots", type=int, default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from bench.models import dense_decoder as ref
    from repro.models import transformer
    from repro.serving import Engine

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(ROOT, args.workload)
    cfg, mix = cell["config_data"], cell["traffic_data"]
    slots = args.slots or cell["slots"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    adapter = harness.adapter(ROOT, cfg)
    w = placed(jax.eval_shape(lambda: ref.init_weights(0, cfg)))
    params, mcfg, rt = adapter.program(cfg, w)
    eng = Engine(params, mcfg, rt, max_len=cell["max_len"])
    cache = placed(jax.eval_shape(lambda: transformer.init_cache(
        mcfg, slots, cell["max_len"], mcfg.cdtype)))
    plen = max(mix["prompt_len"]["values"])
    toks = jax.ShapeDtypeStruct((1, plen), jnp.int32, sharding=one)
    last = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)
    pc = placed(jax.eval_shape(eng._prefill, params, toks, last)[1])
    # SlotPool builds this write in its constructor, which would allocate
    # the whole cache here; the same function is compiled on its own
    write_fn = jax.jit(
        lambda c, p, s: jax.tree_util.tree_map(
            lambda big, small: jax.lax.dynamic_update_slice(
                big, small.astype(big.dtype), (0, s) + (0,) * (big.ndim - 2)),
            c, p), donate_argnums=(0,))
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    tok1 = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    wbytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(w))
    cbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(cache))
    for name, fn, fargs in (
            ("prefill", eng._prefill, (params, toks, last)),
            ("write", write_fn, (cache, pc, slot)),
            ("decode", eng._decode, (params, cache, tok1, pos))):
        ma = fn.lower(*fargs).compile().memory_analysis()
        print(json.dumps({
            "program": name, "workload": args.workload, "slots": slots,
            "prompt_len": plen, "weights_bytes": wbytes,
            "cache_bytes": cbytes,
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

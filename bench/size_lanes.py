#!/usr/bin/env python3
"""Size the serve configuration's lanes by compiling for a described v5e.

    JAX_PLATFORMS=cpu python bench/size_lanes.py [--lanes 8 16 32]

For each lane count, compiles the engine's decode step and its largest
prefill bucket (all lanes at once, the longest prompt bucket of the
backlog mix) for one chip of a described ``v5e:2x2`` topology, with the
configuration file's sizes, and prints ``memory_analysis``. The prefill
runs while the engine's cache is resident, so its peak adds the cache's
bytes. No chip is needed; no number here is a device measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

LIMIT = 15.75 * 2**30          # what one v5e chip gives a program


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lanes", type=int, nargs="+", default=[8, 16, 32])
    ap.add_argument("--prompt", type=int, default=2048)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from forms.serve import model_config, serve_config
    from repro.models import decode as D
    from repro.models import transformer as T
    from repro.serve import engine as SE
    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((BENCH / "configs" / "deepseek_7b.json").read_text())
    mcfg = model_config(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    params = on_chip(jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: T.init_params(k, mcfg)[0],
                       jax.random.PRNGKey(0))))
    max_len = conf["serve"]["max_len"]
    for lanes in args.lanes:
        scfg = serve_config(dict(conf["serve"], max_running=lanes,
                                 quantize_impl="kernel"))
        step, pre, demote, _ = SE._compiled_fns(mcfg, scfg, max_len)
        cache = on_chip(jax.eval_shape(
            lambda: D.init_cache(mcfg, scfg, lanes, max_len)))
        cache_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree_util.tree_leaves(cache))
        st = on_chip({k: jax.ShapeDtypeStruct((lanes,), t) for k, t in
                      (("tok", jnp.int32), ("pos", jnp.int32),
                       ("remaining", jnp.int32), ("active", jnp.bool_),
                       ("ref", jnp.bool_))})
        out = {"lanes": lanes, "kv_cache_gib": cache_bytes / 2**30}
        m = step.lower(params, cache, st).compile().memory_analysis()
        out["step"] = _mem(m)
        batch = on_chip({"tokens": jax.ShapeDtypeStruct(
            (lanes, args.prompt), jnp.int32)})
        lens = on_chip(jax.ShapeDtypeStruct((lanes,), jnp.int32))
        m = pre.lower(params, batch, lens).compile().memory_analysis()
        out["prefill"] = _mem(m)
        out["prefill"]["with_cache_gib"] = out["prefill"]["total_gib"] + \
            out["kv_cache_gib"]
        out["fits"] = max(out["step"]["total_gib"],
                          out["prefill"]["with_cache_gib"]) * 2**30 <= LIMIT
        print(json.dumps(out), flush=True)


def _mem(m) -> dict:
    g = 2**30
    d = {"argument_gib": m.argument_size_in_bytes / g,
         "output_gib": m.output_size_in_bytes / g,
         "temp_gib": m.temp_size_in_bytes / g,
         "alias_gib": m.alias_size_in_bytes / g}
    d["total_gib"] = d["argument_gib"] + d["output_gib"] + d["temp_gib"] \
        - d["alias_gib"]
    return d


if __name__ == "__main__":
    main()

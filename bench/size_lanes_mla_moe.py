#!/usr/bin/env python3
"""Size an MLA + MoE serve configuration's lanes by compiling for a
described v5e (``size_lanes.py``'s method, for ``forms/serve_mla_moe.py``).

    JAX_PLATFORMS=cpu python bench/size_lanes_mla_moe.py \
        [--config deepseek_v2_lite] [--lanes 32 64 128]

For each lane count, compiles the engine's decode step and its largest
prefill call (``prefill_budget`` tokens: one row of the largest bucket)
for one chip of a described ``v5e:2x2`` topology, with the configuration
file's sizes, and prints ``memory_analysis``. The prefill runs while the
engine's cache is resident, so its peak adds the cache's bytes. No chip is
needed; no number here is a device measurement.
"""
from __future__ import annotations

import argparse
import json

from size_lanes import BENCH, LIMIT, _mem


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="deepseek_v2_lite")
    ap.add_argument("--lanes", type=int, nargs="+", default=[32, 64, 128])
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from forms.serve_mla_moe import model_config, serve_config
    from repro.models import decode as D
    from repro.models import transformer as T
    from repro.serve import engine as SE
    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((BENCH / "configs" / f"{args.config}.json").read_text())
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
    weights = sum(x.size * 2 for x in jax.tree_util.tree_leaves(params))
    max_len = conf["serve"]["max_len"]
    prompt = conf["serve"]["prefill_budget"]
    for lanes in args.lanes:
        scfg = serve_config(dict(conf["serve"], max_running=lanes,
                                 quantize_impl="kernel"))
        step, pre, _, _ = SE._compiled_fns(mcfg, scfg, max_len)
        cache = on_chip(jax.eval_shape(
            lambda: D.init_cache(mcfg, scfg, lanes, max_len)))
        cache_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree_util.tree_leaves(cache))
        st = on_chip({k: jax.ShapeDtypeStruct((lanes,), t) for k, t in
                      (("tok", jnp.int32), ("pos", jnp.int32),
                       ("remaining", jnp.int32), ("active", jnp.bool_),
                       ("ref", jnp.bool_))})
        out = {"lanes": lanes, "weights_gib": weights / 2**30,
               "kv_cache_gib": cache_bytes / 2**30}
        try:
            out["step"] = _mem(step.lower(params, cache, st).compile()
                               .memory_analysis())
        except jax.errors.JaxRuntimeError as e:   # the compiler's own refusal
            out.update(fits=False, step=str(e).splitlines()[0])
            print(json.dumps(out), flush=True)
            continue
        batch = on_chip({"tokens": jax.ShapeDtypeStruct((1, prompt),
                                                        jnp.int32)})
        lens = on_chip(jax.ShapeDtypeStruct((1,), jnp.int32))
        out["prefill"] = _mem(pre.lower(params, batch, lens).compile()
                              .memory_analysis())
        out["prefill"]["with_cache_gib"] = out["prefill"]["total_gib"] + \
            out["kv_cache_gib"]
        out["fits"] = max(out["step"]["total_gib"],
                          out["prefill"]["with_cache_gib"]) * 2**30 <= LIMIT
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

"""Serving launcher: batched synthetic request workload through the IBEX
paged-KV engine (device-resident batched scheduler by default; ``--serial``
runs the per-lane baseline for comparison).

  PYTHONPATH=src python -m repro.launch.serve --arch llama3_8b --reduced \
      --requests 8 --new-tokens 8
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.common.types import ServeConfig
from repro.configs import describe, get_config, get_reduced
from repro.models import transformer as T
from repro.serve import Engine, SerialEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--vary-prompts", action="store_true",
                    help="mix prompt lengths (exercises length bucketing)")
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--lanes", type=int, default=2)
    ap.add_argument("--kv-bits", type=int, default=8, choices=(4, 8))
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--serial", action="store_true",
                    help="per-lane baseline engine instead of the batched "
                         "scheduler")
    ap.add_argument("--paper-mode", action="store_true",
                    help="promote-then-read instead of fused dequant attn")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="profile the run into DIR (jax.profiler.trace): "
                         "the engine's spans (repro.obs.spans) beside the "
                         "device's programs, for Perfetto or TensorBoard")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    print(describe(cfg))
    scfg = ServeConfig(max_running=args.lanes, hot_window=16, attn_chunk=32,
                       kv_rate_bits=args.kv_bits,
                       fused_dequant_attention=not args.paper_mode)
    params, _ = T.init_params(jax.random.PRNGKey(0), cfg)
    engine_cls = SerialEngine if args.serial else Engine
    eng = engine_cls(cfg, scfg, params, max_len=args.max_len)

    rng = np.random.default_rng(0)
    def plen(i):
        return (8 + 4 * (i % 5)) if args.vary_prompts else args.prompt_len
    rids = [eng.submit(list(rng.integers(1, cfg.vocab_size, plen(i))),
                       args.new_tokens) for i in range(args.requests)]
    profile = (jax.profiler.trace(args.trace) if args.trace
               else contextlib.nullcontext())
    t0 = time.time()
    with profile:
        eng.run_until_done(max_steps=5000)
    dt = time.time() - t0
    done = sum(eng.requests[r].state == "done" for r in rids)
    c = eng.counters
    print(f"served {done}/{len(rids)} requests, "
          f"{c['tokens']} tokens in {dt:.1f}s "
          f"({c['tokens'] / max(dt, 1e-9):.1f} tok/s) "
          f"[{'serial' if args.serial else 'batched'}]")
    print(f"pool: promotions={c['promotions']} demotions={c['demotions']} "
          f"preempt_bytes={c['preempt_bytes']} "
          f"shadow_repreempts={c['shadow_repreempts']}")
    print(f"host: step_syncs={c['step_syncs']}/{c['steps']} steps, "
          f"admit_syncs={c['admit_syncs']}, "
          f"prefill_batches={c['prefill_batches']}")
    mt = eng.modeled_time()
    print(f"modeled (DESIGN.md §12): {mt['modeled_s'] * 1e3:.3f}ms total, "
          f"{mt['modeled_s_per_step'] * 1e6:.2f}us/step "
          f"(sync={mt['sync_s'] * 1e3:.3f}ms, motion bottleneck="
          f"{max(mt['motion_s_per_expander']) * 1e6:.2f}us)")
    if args.trace:
        print(f"trace: profile under {args.trace}")
    for rid in rids[:3]:
        print(f"  req {rid}: {eng.result(rid)}")


if __name__ == "__main__":
    main()

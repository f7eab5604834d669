"""Serving-engine benchmark: per-lane baseline vs device-resident batched
scheduler, same model, same workload.

Measures three things the tentpole claims:

  * **tokens/sec** — the batched engine admits fresh requests through
    bucketed prefill (few compiles, one sync per bucket) and advances lane
    bookkeeping on device (one sync per decode step); the serial baseline
    prefills per request (a compile per distinct prompt length, a sync per
    request) and fetches full logits every step. The workload uses mixed
    prompt lengths so the bucketing difference is visible, and the timed run
    *includes* admission — that is where serving latency actually goes.
  * **host-sync contract** — asserted, not just recorded:
    ``step_syncs == steps`` for the batched engine.
  * **preempt/resume bytes** — both engines quantize the ring on demotion
    and count the compressed payload honestly; the batched engine's shadowed
    lanes pay only for the suffix generated since the last park (the serial
    baseline drops its parked copy on resume and re-pays the full context),
    and a re-preempt of an untouched resumed request moves exactly 0 bytes
    (checked by driving resume→preempt directly).
  * **modeled seconds (DESIGN.md §12)** — the byte and sync counters priced
    through ``simx.time.serve_modeled_time``: serial-vs-batched and a
    fabric-striped (n_expanders=2) run compare in modeled seconds per
    decode step, not just simulator tokens/sec.

Writes ``BENCH_serve.json`` at the repo root.
"""
from __future__ import annotations

import json
import pathlib
import time
from typing import Dict, List

import jax
import numpy as np

from repro.common.types import ServeConfig
from repro.configs import get_reduced
from repro.models import transformer as T
from repro.obs import manifest as run_manifest
from repro.serve import Engine, SerialEngine

JSON_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_serve.json"

ARCH = "llama3_8b"


def _workload(rng, vocab: int, n_requests: int):
    """Mixed prompt lengths (the bucketing story needs length diversity)."""
    lens = [12, 20, 24, 17, 28, 9, 22, 14]
    return [list(rng.integers(1, vocab, lens[i % len(lens)]))
            for i in range(n_requests)]


def _serve(engine_cls, cfg, scfg, params, prompts, new_tokens, max_len):
    eng = engine_cls(cfg, scfg, params, max_len=max_len)
    rids = [eng.submit(p, new_tokens) for p in prompts]
    t0 = time.perf_counter()
    eng.run_until_done(max_steps=4000)
    dt = time.perf_counter() - t0
    assert all(eng.requests[r].state == "done" for r in rids)
    return eng, dt


def _shadow_repreempt_bytes(cfg, scfg, params, prompts, max_len) -> int:
    """Bytes moved by re-preempting an untouched resumed request (must be 0:
    the shadow is re-validated instead)."""
    eng = Engine(cfg, scfg, params, max_len=max_len)
    rid = eng.submit(prompts[0], 10)
    for _ in range(3):
        eng.step()
    eng._preempt(0)
    req = eng.requests[rid]
    eng.queue.remove(rid)
    eng.lane_req[0] = rid
    eng._resume(req, 0)
    before = eng.counters["preempt_bytes"]
    eng._preempt(0)                     # untouched since resume
    assert eng.counters["shadow_repreempts"] == 1
    return eng.counters["preempt_bytes"] - before


def run(quick: bool, seed: int = 0) -> List[Dict]:
    cfg = get_reduced(ARCH)
    scfg = ServeConfig(max_running=2, hot_window=16, attn_chunk=32,
                       kv_rate_bits=8)
    max_len = 128
    n_requests = 6 if quick else 12
    new_tokens = 8 if quick else 16
    params, _ = T.init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = _workload(rng, cfg.vocab_size, n_requests)

    # warm the jit caches with a tiny run of each engine so the timed pass
    # measures steady-state serving of *new* lengths (the serial engine still
    # pays a prefill compile per unseen length inside the timed region — that
    # per-length cost is exactly its handicap in production)
    warm = prompts[:2]
    _serve(SerialEngine, cfg, scfg, params, warm, 2, max_len)
    _serve(Engine, cfg, scfg, params, warm, 2, max_len)

    se, dt_s = _serve(SerialEngine, cfg, scfg, params, prompts, new_tokens,
                      max_len)
    be, dt_b = _serve(Engine, cfg, scfg, params, prompts, new_tokens, max_len)
    tok_s = se.counters["tokens"] / max(dt_s, 1e-9)
    tok_b = be.counters["tokens"] / max(dt_b, 1e-9)

    # host-sync contract: the measured sync count must match the budget
    # Engine.step DECLARES via @sync_contract (one sync per decode step) —
    # not a constant this bench made up
    from repro.common.contracts import verify_sync_counters
    verify_sync_counters(Engine.step, be.counters["steps"],
                         be.counters["step_syncs"], what=str(be.counters))

    # fabric-striped run (lanes across 2 expanders; compiled programs are
    # shared with the single-expander engine — n_expanders is scheduling-
    # only and normalized out of the jit key)
    import dataclasses
    scfg2 = dataclasses.replace(scfg, n_expanders=2)
    fe, dt_f = _serve(Engine, cfg, scfg2, params, prompts, new_tokens,
                      max_len)

    # modeled seconds: serial vs batched vs fabric-striped in one currency
    ms, mb, mf = (e.modeled_time() for e in (se, be, fe))
    # same counters re-priced with the measurement-calibrated engine (no-op
    # fallback to paper constants when BENCH_kernels.json is absent)
    from repro.simx import time as TM
    cal_dev = TM.calibrated_device()
    mb_cal = be.modeled_time(cal_dev)

    shadow_bytes = _shadow_repreempt_bytes(cfg, scfg, params, prompts,
                                           max_len)
    assert shadow_bytes == 0, shadow_bytes

    payload = {
        "meta": {**run_manifest(seed=seed),
                 "arch": ARCH, "lanes": scfg.max_running,
                 "requests": n_requests, "new_tokens": new_tokens,
                 "max_len": max_len, "quick": quick,
                 "unit": "decode tokens/sec, admission included"},
        "serial_tok_per_sec": tok_s,
        "batched_tok_per_sec": tok_b,
        "speedup_batched_over_serial": tok_b / max(tok_s, 1e-9),
        "serial": {k: se.counters[k] for k in
                   ("steps", "tokens", "step_syncs", "admit_syncs",
                    "prefill_batches", "demotions", "preempt_bytes",
                    "resume_bytes", "shadow_repreempts")},
        "batched": {k: be.counters[k] for k in
                    ("steps", "tokens", "step_syncs", "admit_syncs",
                     "prefill_batches", "demotions", "preempt_bytes",
                     "resume_bytes", "shadow_repreempts")},
        "step_syncs_per_step": be.counters["step_syncs"] /
        max(be.counters["steps"], 1),
        "shadow_repreempt_bytes": shadow_bytes,
        # delivered-time accounting (DESIGN.md §12): one currency (seconds)
        # across serial / batched / fabric-striped scheduling
        "modeled": {
            "unit": "modeled seconds from preempt/resume bytes + host "
                    "syncs (simx.time.serve_modeled_time)",
            "serial": ms,
            "batched": mb,
            "fabric_striped_2x": dict(mf, per_expander_stats={
                k: v.tolist() for k, v in fe.expander_stats.items()}),
            "modeled_speedup_batched_over_serial":
                ms["modeled_s_per_step"] / max(mb["modeled_s_per_step"],
                                               1e-18),
            "batched_calibrated": dict(
                mb_cal,
                device={"comp_cycles": cal_dev.comp_cycles,
                        "decomp_cycles": cal_dev.decomp_cycles,
                        "calibrated": cal_dev != TM.DeviceConfig()}),
        },
    }
    JSON_PATH.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")

    return [
        {"name": "serve.serial_tok_per_sec", "us": dt_s * 1e6,
         "derived": f"{tok_s:,.1f}tok/s;prefills={se.counters['prefill_batches']};"
                    f"admit_syncs={se.counters['admit_syncs']}"},
        {"name": "serve.batched_tok_per_sec", "us": dt_b * 1e6,
         "derived": f"{tok_b:,.1f}tok/s;prefills={be.counters['prefill_batches']};"
                    f"admit_syncs={be.counters['admit_syncs']}"},
        {"name": "serve.speedup", "us": 0.0,
         "derived": f"x{tok_b / max(tok_s, 1e-9):.2f};"
                    f"syncs_per_step={payload['step_syncs_per_step']:.0f};"
                    f"shadow_repreempt_bytes={shadow_bytes};"
                    f"json={JSON_PATH.name}"},
        {"name": "serve.modeled_s_per_step", "us": dt_f * 1e6,
         "derived": f"serial={ms['modeled_s_per_step'] * 1e6:.2f}us;"
                    f"batched={mb['modeled_s_per_step'] * 1e6:.2f}us;"
                    f"striped2x={mf['modeled_s_per_step'] * 1e6:.2f}us;"
                    f"modeled_x={payload['modeled']['modeled_speedup_batched_over_serial']:.2f}"},
    ]

"""Per-lane reference serving engine — the batched scheduler's baseline.

This is the pre-batching engine shape, kept deliberately: one prefill
compile+sync per request (no length bucketing), per-lane Python loops in
``step`` with a full-logits fetch every step, and no lane shadowing (resume
drops the parked copy, so every re-preempt pays the full demotion again).
``benchmarks/serve_bench.py`` serves the same workload through this and
through ``serve.engine.Engine`` and records the tokens/sec and preempt-bytes
gap; tests use it as a semantics reference (same model, same decode step —
generations must match).

The correctness fixes are shared with the batched engine (via
``_EngineBase``): prompts are prefilled at their exact length (no
left-padding — short prompts used to attend to garbage KV at the padded
positions), and preemption quantizes the hot ring on device before parking,
counting the compressed bytes honestly.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.engine import (DONE, PREEMPTED, RUNNING, Request,
                                _EngineBase, _lanes_install)


class SerialEngine(_EngineBase):
    """Per-lane host-loop engine (see module docstring)."""

    # -- scheduling ---------------------------------------------------------

    def _admit(self) -> None:
        started = set()
        while self.queue:
            lane = self._free_lane()
            if lane is None:
                break
            started.add(lane)
            self._start(self.queue.pop(0), lane)
        # at most one preemption per engine step (same rule as the batched
        # engine: an unbounded loop would never drain the queue it refills);
        # lanes started this step are not eligible victims — the batched
        # engine's rule, matched here so both engines preempt the same
        # schedule and the token-for-token parity contract holds by
        # construction, not by quantization luck
        if self.queue:
            occupied = np.array([r is not None and i not in started
                                 for i, r in enumerate(self.lane_req)])
            victim, new_ref = self._victim_policy.select_mask(occupied,
                                                              self._ref)
            if victim is not None:
                self._ref = new_ref
                self._preempt(victim)
                self._start(self.queue.pop(0), victim)

    def _start(self, rid: int, lane: int) -> None:
        req = self.requests[rid]
        if req.parked is not None:
            self._resume(req, lane)
            return
        # fresh request: one exact-length prefill per request — a compile
        # per distinct prompt length and a sync per request (the baseline
        # cost the batched engine's bucketing removes)
        S = len(req.prompt)
        batch = {"tokens": jnp.asarray(np.asarray(req.prompt,
                                                  np.int32)[None, :])}
        if self.cfg.frontend != "none":
            batch["embeds"] = jnp.zeros((1, S, self.cfg.d_model), jnp.bfloat16)
        toks, sub = self._prefill_fn(self.params, batch,
                                     jnp.asarray([S], jnp.int32))
        self.cache = _lanes_install(self.cache, jnp.asarray([lane]), sub)
        self.counters["prefill_batches"] += 1
        tok = int(self._fetch(toks, "admit_syncs")[0])   # a sync per request
        req.generated.append(tok)
        req.pos = S
        req.lane = lane
        req.state = RUNNING
        self._ref[lane] = True
        self.lane_req[lane] = rid
        self.counters["promotions"] += 1
        if req.max_new_tokens <= 1 or req.pos >= self.max_len - 1:
            req.state = DONE
            req.lane = -1
            self.lane_req[lane] = None

    def _preempt(self, lane: int) -> None:
        """Demote and park (shared _park_lane). No shadow survives in the
        baseline: parked is dropped on resume, so this always pays the full
        compressed payload."""
        rid = self.lane_req[lane]
        req = self.requests[rid]
        self._park_lane(req, lane)
        self.counters["demotions"] += 1
        req.state = PREEMPTED
        req.lane = -1
        self.lane_req[lane] = None
        self._ref[lane] = False
        self.queue.append(rid)

    def _resume(self, req: Request, lane: int) -> None:
        self._install_parked(req, lane)
        self._drop_park(req)           # no shadow kept: baseline behavior
        req.shadow_pos = 0
        self._ref[lane] = True

    # -- decode step ---------------------------------------------------------

    def step(self) -> bool:
        """One engine iteration, per-lane host bookkeeping: a full-logits
        fetch plus a Python loop with one np.argmax per lane."""
        self._admit()
        active = [(lane, rid) for lane, rid in enumerate(self.lane_req)
                  if rid is not None]
        if not active:
            return bool(self.queue)
        tokens = np.zeros((self.lanes,), np.int32)
        pos = np.zeros((self.lanes,), np.int32)
        for lane, rid in active:
            req = self.requests[rid]
            tokens[lane] = req.generated[-1] if req.generated else 0
            pos[lane] = req.pos
        kwargs = {}
        if self.cfg.frontend != "none":
            kwargs["embeds"] = jnp.zeros((self.lanes, self.cfg.d_model),
                                         jnp.bfloat16)
        logits, self.cache = self._decode_fn(
            self.params, self.cache, jnp.asarray(tokens), jnp.asarray(pos),
            **kwargs)
        self.counters["steps"] += 1
        logits = self._fetch(logits, "step_syncs")   # full-logits host sync
        for lane, rid in active:
            req = self.requests[rid]
            req.pos += 1
            self._ref[lane] = True
            tok = int(np.argmax(logits[lane]))
            req.generated.append(tok)
            self.counters["tokens"] += 1
            if len(req.generated) >= req.max_new_tokens or \
                    req.pos >= self.max_len - 1:
                req.state = DONE
                req.lane = -1
                self.lane_req[lane] = None
        return True

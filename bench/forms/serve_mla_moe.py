"""Serve form for a latent-attention mixture-of-experts model (DeepSeek-V2):
the serve form (``forms/serve.py``) with the model built from the file's
MLA and MoE keys, and its own work, warm-up, reference and parked leaves.

* **Model.** ``model_config`` reads the published keys: latent attention
  without q-LoRA and its YaRN ``rope_scaling``, ``first_k_dense_replace``
  dense layers, then routed experts (softmax, greedy top-k, weights
  renormalised or not) beside shared experts. Keys whose other values the
  program does not implement are refused.
* **Work.** Request lengths are drawn once from the traffic file's
  ``work_seed``; ``--seed`` draws the prompts' token ids and the weights.
* **Warm-up.** A bucket's admission group is split into prefill calls of
  at most ``prefill_budget`` tokens, so the prefill programs are each
  bucket's power-of-two row counts up to the budget, and the admission
  groups that can occur are of 1 to (budget / smallest bucket) rows.
* **Correctness.** As the serve form's, against
  ``bench/reference/deepseek_v2.py``, with the parked leaves the latent
  cache has: ``kv_code_steps`` compares one parked request's 4-bit latent
  codes (``lat_codes``, ``lat_scales``) with the reference's latents.
  The widest logit gap is reported (``program_widest_gap``) and not
  compared: one token's gap is a maximum over a few hundred, and the fp8
  control's lies within three times the sound runs', so ``mean_logit_gap``
  is the logit check that separates them. The control (``--control``)
  computes the reference in the precision the configuration's
  ``limits.control`` names, and in no other.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

import gen
from forms import serve
from reference import deepseek_v2 as ref
from reference import llm

PARKED = ("lat_codes", "lat_scales")


def model_config(c: dict):
    from repro.common.types import (MLAConfig, ModelConfig, MoEConfig,
                                    YaRNConfig)
    fixed = {"scoring_func": "softmax", "topk_method": "greedy",
             "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
             "hidden_act": "silu", "attention_bias": False}
    for k, v in fixed.items():
        if c[k] != v:
            raise ValueError(f"{k}={c[k]!r}: the program implements {v!r}")
    rs = c["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r}: only yarn")
    yarn = YaRNConfig(
        factor=float(rs["factor"]),
        original_max_position_embeddings=int(
            rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]))
    return ModelConfig(
        name=c["name"], family="moe", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), attn_kind="mla",
        first_k_dense=int(c["first_k_dense_replace"]),
        moe=MoEConfig(num_experts=c["n_routed_experts"],
                      top_k=c["num_experts_per_tok"],
                      expert_d_ff=c["moe_intermediate_size"],
                      num_shared_experts=c["n_shared_experts"],
                      norm_topk_prob=bool(c["norm_topk_prob"]),
                      routed_scaling_factor=float(
                          c["routed_scaling_factor"])),
        mla=MLAConfig(q_lora_rank=int(c["q_lora_rank"] or 0),
                      kv_lora_rank=c["kv_lora_rank"],
                      qk_nope_head_dim=c["qk_nope_head_dim"],
                      qk_rope_head_dim=c["qk_rope_head_dim"],
                      v_head_dim=c["v_head_dim"], yarn=yarn),
        dtype=c["torch_dtype"], remat=False)


def serve_config(s: dict):
    return dataclasses.replace(serve.serve_config(s),
                               prefill_budget=int(s["prefill_budget"]))


def _prev_pow2(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


class Form(serve.Form):
    def __init__(self, cell, devices):
        self.t_created = time.perf_counter()
        self.cell = cell
        self.c = cell.config
        self.mix = cell.mix
        self.mcfg = model_config(self.c)
        self.scfg = serve_config(self.c["serve"])
        self.max_len = int(self.c["serve"]["max_len"])
        self.lanes = int(self.c["serve"]["max_running"])
        self.engine_step = None        # a test may wrap Engine.step

    # -- traffic ----------------------------------------------------------------

    def _requests(self, seed: int, n: int):
        """The work's lengths from ``work_seed``; the prompts' id seeds
        from the run's seed."""
        plen, olen, _ = gen.serve_requests(self.mix, n,
                                           int(self.mix["work_seed"]),
                                           self.mcfg.vocab_size)
        pseed = np.random.default_rng(seed).integers(0, 2**31 - 1, size=n)
        return plen, olen, pseed

    # -- set-up -----------------------------------------------------------------

    def _rows_per_call(self, L: int) -> int:
        return _prev_pow2(self.scfg.prefill_budget // L) \
            if self.scfg.prefill_budget else self.lanes

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.serve import Engine
        t0 = time.perf_counter()
        self.setup_parts = {}

        def part(name):
            nonlocal t0
            t1 = time.perf_counter()
            self.setup_parts[f"setup_{name}_s"] = t1 - t0
            t0 = t1
        s_w, s_req, s_warm, s_pick = gen.sub_seeds(self.cell.seed, 4)
        self.s_pick = s_pick
        self.params = serve.make_weights(self.mcfg, s_w)
        jax.block_until_ready(self.params)
        self.eng = Engine(self.mcfg, self.scfg, self.params,
                          max_len=self.max_len)
        if self.engine_step is None:
            self.engine_step = self.eng.step
        self.budget, self.parks = {}, {}
        eng, lanes = self.eng, self.lanes
        self.reqs = self._requests(s_req, int(self.mix["requests"]))
        self.next_req = 0

        # every prefill call the mix can produce: each bucket's power-of-
        # two row counts up to the budget's
        lo, hi = self.mix["prompt"]["lo"], self.mix["prompt"]["hi"]
        lengths = sorted({eng._bucket(n) for n in range(lo, hi + 1)})
        part("weights")
        for L in lengths:
            for B in (1 << i for i in range(
                    self._rows_per_call(L).bit_length())):
                toks, first = eng._prefill_fn(
                    self.params, {"tokens": jnp.zeros((B, L), jnp.int32)},
                    jnp.full((B,), L, jnp.int32))
                jax.block_until_ready(first)
                del toks, first
        part("prefill_programs")
        rng = np.random.default_rng(s_warm)
        # admissions of k requests in one call, k up to the most rows a
        # call takes (the shortest bucket's)
        for k in range(1, min(self._rows_per_call(lengths[0]), lanes) + 1):
            for _ in range(k):
                rid = eng.submit(rng.integers(1, 100, lo).tolist(), 1)
                self.budget[rid], self.parks[rid] = 1, []
            self._step()
        part("admissions")
        # decode, preemption and resume: one more request than lanes
        for _ in range(lanes + 1):
            rid = eng.submit(rng.integers(1, 100, lo).tolist(), 3)
            self.budget[rid], self.parks[rid] = 3, []
        for _ in range(64):
            resumed = any(self.parks[r] and eng.requests[r].state != "preempted"
                          for r in self.parks)
            if resumed:
                break
            self._step()
        else:
            raise RuntimeError("warm-up saw no preemption and resume")
        while self._step():
            pass
        part("preempt_resume")
        # fill the queue and run into steady state
        self._refill()
        for _ in range(4):
            self._step()
            self._refill()
        jax.block_until_ready(eng.cache)
        part("fill")

    # -- the measured window ------------------------------------------------------

    def _facts(self, c0, c1, t0, t1, tokens):
        f = super()._facts(c0, c1, t0, t1, tokens)
        c, m = self.c, self.mcfg
        f.update(first_k_dense=m.first_k_dense, heads=m.num_heads,
                 nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                 v_dim=c["v_head_dim"], kv_lora=c["kv_lora_rank"],
                 experts=c["n_routed_experts"],
                 top_k=c["num_experts_per_tok"],
                 shared=c["n_shared_experts"],
                 expert_d_ff=c["moe_intermediate_size"])
        return f

    # -- correctness --------------------------------------------------------------

    def release(self):
        """Finish the window's answers, keep on the host what the check
        needs (the sample, one parked request's latent codes), and free
        the engine."""
        self._drain()
        eng = self.eng
        done = [eng.requests[r] for r in self.window_rids
                if eng.requests[r].state == "done"]
        self.wrong_count = sum(1 for r in done
                               if len(r.generated) != self.budget[r.rid])
        rng = np.random.default_rng(self.s_pick)
        done.sort(key=lambda r: -len(r.generated))
        pick, n_tok = [], 0
        if done:
            pick.append(done[0])
            n_tok = len(done[0].generated)
            rest = [done[i] for i in rng.permutation(len(done) - 1) + 1]
            for r in rest:
                if n_tok >= self.mix["check_tokens"] or \
                        len(pick) >= self.mix["check_requests"]:
                    break
                pick.append(r)
                n_tok += len(r.generated)
        self.sample = [(list(r.prompt), list(r.generated),
                        list(self.parks[r.rid])) for r in pick]
        parked = [eng.requests[r] for r in sorted(self.parks)
                  if eng.requests[r].state == "preempted"
                  and eng.requests[r].parked is not None]
        self.parked = None
        if parked:
            r = parked[int(rng.integers(len(parked)))]
            self.parked = (list(r.prompt), list(r.generated), r.pos,
                           list(self.parks[r.rid]),
                           {k: np.asarray(r.parked[k]) for k in PARKED})
        self.eng = None
        del eng
        gc.collect()

    def _ref_sequence(self, prompt, generated, parks, mode, keep_kv=False,
                      upto=None):
        seq = prompt + generated[:-1] if upto is None else \
            (prompt + generated)[:upto]
        W = self.c["serve"]["hot_window"]
        cold = llm.cold_boundaries(len(prompt), len(seq), parks, W)
        rows = [len(seq) - 1] if keep_kv else \
            np.arange(len(prompt) - 1, len(seq))
        return ref.forward(self.params, seq, cold, self.c, mode=mode,
                           rows=rows, keep_lat=keep_kv)

    def _kv_steps(self, parks, lats, codes, pos) -> float:
        return max(llm.code_error(codes["lat_codes"][li, :pos],
                                  codes["lat_scales"][li, :pos], lat[:pos])
                   for li, lat in enumerate(lats))

    def check(self, control=False):
        lim = self.c["limits"]
        cmode = lim["control"]
        modes = (cmode,) if control else ()
        gaps = {m: [] for m in ("program",) + modes}
        for prompt, generated, parks in self.sample:
            want, _ = self._ref_sequence(prompt, generated, parks, "f32")
            gaps["program"].append(llm.served_gap(want, np.asarray(generated)))
            for m in modes:
                ctl, _ = self._ref_sequence(prompt, generated, parks, m)
                gaps[m].append(llm.served_gap(want, ctl.argmax(-1)))
        widest = {m: max((float(g.max()) for g in v), default=float("inf"))
                  for m, v in gaps.items()}
        mean = {m: float(np.concatenate(v).mean()) if v else float("inf")
                for m, v in gaps.items()}
        prog_kv = ctl_kv = fault_kv = float("inf")
        if self.parked is not None:
            prompt, generated, pos, parks, codes = self.parked
            parks = [p for p in parks if p < pos]
            _, lats = self._ref_sequence(prompt, generated, parks, "f32",
                                         keep_kv=True, upto=pos)
            prog_kv = self._kv_steps(parks, lats, codes, pos)
            if control:
                _, lats8 = self._ref_sequence(prompt, generated, parks,
                                              cmode, keep_kv=True, upto=pos)
                ctl_kv = self._kv_steps(parks, lats, ref.pack_latents(lats8),
                                        pos)
                fault_kv = self._kv_steps(parks, lats, ref.lose_plane(codes),
                                          pos)
        side = cmode if control else "program"
        checks = {"mean_logit_gap": (mean[side],
                                     float(lim["mean_logit_gap"])),
                  "kv_code_steps": (ctl_kv if control else prog_kv,
                                    float(lim["kv_code_steps"])),
                  "token_count_wrong": (self.wrong_count, 0)}
        self.extra = {"sample_tokens": int(sum(g.size for g in
                                               gaps["program"])),
                      "sample_requests": len(self.sample),
                      "drain_steps": self.drain_steps}
        self.extra.update(self.setup_parts)
        if control:
            self.extra.update(program_kv_code_steps=prog_kv,
                              fault_kv_code_steps=fault_kv)
        for m, v in gaps.items():
            if v:
                a = np.concatenate(v)
                self.extra.update({f"{m}_widest_gap": widest[m],
                                   f"{m}_flip_share": float((a > 0).mean()),
                                   f"{m}_mean_gap": mean[m]})
        self.params = None
        return checks

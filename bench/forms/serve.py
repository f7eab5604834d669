"""Serve form: ``repro.serve.Engine`` (``submit``, ``step``) with a
compressed-KV cache, driven by a backlog: the queue always holds at least
as many waiting requests as there are lanes, and the window counts the
output tokens of every step that ends inside it.

Set-up builds the model configuration from its file (every size written
out there), makes the weights on the device in one jitted call from the
seed, in bfloat16, builds the engine and warms every shape the mix uses:
each prefill bucket (rows x length) its lengths can produce, every
admission group size up to the lane count, the decode step, a preemption
and a resume.

``correct`` is decided after the window, with the engine freed:

* ``widest_logit_gap`` and ``mean_logit_gap``: a sample of finished
  requests (the longest among them) is run through the plain reference
  (``bench/reference/llm.py``) over prompt and served tokens; the widest
  and the mean, over served tokens, of the gap by which a served token's
  logit lies below the reference's best;
* ``kv_code_steps``: one preempted request's parked 4-bit codes and
  scales (the lane demotion's output) against the reference's keys and
  values, the largest error in quantization steps;
* ``token_count_wrong``: finished requests without exactly their budget.

The control (``--control``) puts the reference, computed with its linear
layers in the lower precision that the configuration's ``limits.control``
names, in the program's place for the first two; it also reports the
program's own readings, both lower precisions' (int8, fp8) gaps, and ``kv_code_steps`` of the parked codes with
one of eight planes of every token's codes zeroed (a planted fault: the
code loss a miscompiled pack produced on the chip before).
"""
from __future__ import annotations

import gc
import time

import numpy as np

import gen
from reference import llm


def model_config(c: dict):
    from repro.common.types import ModelConfig
    d, h = c["hidden_size"], c["num_attention_heads"]
    return ModelConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=d, num_heads=h, num_kv_heads=c["num_key_value_heads"],
        head_dim=d // h, d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), attn_kind="gqa",
        dtype=c["torch_dtype"], remat=False)


def serve_config(s: dict):
    from repro.common.types import ServeConfig
    return ServeConfig(
        max_running=s["max_running"], kv_rate_bits=s["kv_rate_bits"],
        hot_window=s["hot_window"], attn_chunk=s["attn_chunk"],
        fused_dequant_attention=s["fused_dequant_attention"],
        n_expanders=s["n_expanders"], quantize_impl=s["quantize_impl"])


def make_weights(mcfg, seed: int):
    """Every leaf of the program's parameter tree, made on the device in
    one jitted call from the seed: projections normal / sqrt(fan_in), the
    embedding normal, norm scales 1 + 0.1 normal; all bfloat16."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    shapes = jax.eval_shape(lambda k: T.init_params(k, mcfg)[0],
                            jax.random.PRNGKey(0))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    leaves, tdef = jax.tree_util.tree_flatten(shapes)

    @jax.jit
    def make(key):
        out = []
        for i, (path, s) in enumerate(zip(paths, leaves)):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, s.shape, jnp.bfloat16)
            if "norm" in path or path.endswith("['ln1']") or \
                    path.endswith("['ln2']"):
                a = 1.0 + 0.1 * z
            elif "tok_embed" in path:
                a = z
            else:
                a = z * jnp.bfloat16(1.0 / np.sqrt(s.shape[-2]))
            out.append(a.astype(jnp.bfloat16))
        return jax.tree_util.tree_unflatten(tdef, out)

    return make(jax.random.PRNGKey(seed))


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


class Form:
    LABELS = {
        "serve.step": "serve.step", "serve.submit": "serve.submit",
        "engine.py:step": "serve.decode",
        "engine.py:_start_fresh": "serve.prefill",
        "engine.py:_preempt": "serve.park",
        "engine.py:_park_lane": "serve.park",
        "engine.py:_resume": "serve.resume",
    }

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
        return gen.serve_requests(self.mix, n, seed, self.mcfg.vocab_size)

    def _submit(self, i: int, plen, olen, pseed) -> int:
        toks = gen.prompt_tokens(pseed[i], plen[i], self.mcfg.vocab_size)
        rid = self.eng.submit(toks, int(olen[i]))
        self.budget[rid] = int(olen[i])
        self.parks[rid] = []
        return rid

    def _step(self) -> bool:
        """One engine step; records which requests were preempted (and at
        which position) by it."""
        eng = self.eng
        before = {rid for rid in eng.lane_req if rid is not None}
        more = self.engine_step()
        for rid in before:
            req = eng.requests[rid]
            if req.state == "preempted":
                self.parks[rid].append(req.pos)
        return more

    # -- set-up -----------------------------------------------------------------

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.serve import Engine
        s_w, s_req, s_warm, s_pick = gen.sub_seeds(self.cell.seed, 4)
        self.s_pick = s_pick
        self.params = make_weights(self.mcfg, s_w)
        self.eng = Engine(self.mcfg, self.scfg, self.params,
                          max_len=self.max_len)
        if self.engine_step is None:
            self.engine_step = self.eng.step
        self.budget, self.parks = {}, {}
        eng, lanes = self.eng, self.lanes
        self.reqs = self._requests(s_req, int(self.mix["requests"]))
        self.next_req = 0

        # every prefill bucket the mix's lengths can produce, every group
        # size up to the lane count
        lo, hi = self.mix["prompt"]["lo"], self.mix["prompt"]["hi"]
        lengths = sorted({min(max(_next_pow2(n), 8), self.max_len)
                          for n in range(lo, hi + 1)})
        rows = [1 << i for i in range(lanes.bit_length()) if 1 << i <= lanes]
        for L in lengths:
            for B in rows:
                toks, first = eng._prefill_fn(
                    self.params, {"tokens": jnp.zeros((B, L), jnp.int32)},
                    jnp.full((B,), L, jnp.int32))
                jax.block_until_ready(first)
                del toks, first
        rng = np.random.default_rng(s_warm)
        for k in range(1, lanes + 1):       # admissions of k requests at once
            for _ in range(k):
                rid = eng.submit(rng.integers(1, 100, lo).tolist(), 1)
                self.budget[rid], self.parks[rid] = 1, []
            self._step()
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
        # fill the queue and run into steady state: a preemption every
        # step once the queue is never empty
        self._refill()
        for _ in range(4):
            self._step()
            self._refill()
        jax.block_until_ready(eng.cache)

    def _refill(self):
        plen, olen, pseed = self.reqs
        while len(self.eng.queue) < self.lanes:
            self._submit(self.next_req % len(plen), plen, olen, pseed)
            self.next_req += 1

    # -- the measured window ------------------------------------------------------

    def window(self, seconds, trace_dir):
        return self._window_backlog(seconds, trace_dir)

    def _generated(self) -> int:
        return sum(len(r.generated) for r in self.eng.requests.values())

    def _snapshot_counters(self):
        return dict(self.eng.counters)

    def _facts(self, c0, c1, t0, t1, tokens):
        d = {k: c1[k] - c0[k] for k in c1}
        cfg = self.c
        return {"window_s": t1 - t0, "tokens": tokens,
                "steps": d["steps"], "decode_tokens": d["tokens"],
                "prefill_batches": d["prefill_batches"],
                "preempt_bytes": d["preempt_bytes"],
                "resume_bytes": d["resume_bytes"],
                "demotions": d["demotions"],
                "lanes": self.lanes, "max_len": self.max_len,
                "layers": cfg["num_hidden_layers"],
                "d_model": cfg["hidden_size"],
                "heads": cfg["num_attention_heads"],
                "kv_heads": cfg["num_key_value_heads"],
                "d_ff": cfg["intermediate_size"],
                "vocab": cfg["vocab_size"],
                "hot_window": cfg["serve"]["hot_window"],
                "kv_bits": cfg["serve"]["kv_rate_bits"]}

    def _flop_facts(self, facts, live_before):
        """Prompt tokens prefilled and the context of every decode token in
        the window, for the model-FLOP count."""
        eng = self.eng
        prompts, ctx = [], []
        for rid, req in eng.requests.items():
            n0 = live_before.get(rid, 0)
            n1 = len(req.generated)
            if n1 == n0:
                continue
            if n0 == 0:
                prompts.append(len(req.prompt))
            first_decode = max(n0, 1)
            for j in range(first_decode, n1):
                ctx.append(len(req.prompt) + j)
        facts["prefill_lens"] = prompts
        facts["decode_ctx"] = ctx

    def _window_backlog(self, seconds, trace_dir):
        import jax
        from run import Window, traced
        if trace_dir is not None:
            seconds = min(seconds, float(self.mix["trace_seconds"]))
        eng = self.eng
        c0 = self._snapshot_counters()
        live0 = {rid: len(r.generated) for rid, r in eng.requests.items()}
        g0 = self._generated()
        with traced(trace_dir):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                with jax.profiler.TraceAnnotation("serve.submit"):
                    self._refill()
                with jax.profiler.TraceAnnotation("serve.step"):
                    self._step()
            t1 = time.perf_counter()
            jax.block_until_ready(eng.cache)
        self.steps_at_close = int(eng.counters["steps"])
        tokens = self._generated() - g0
        facts = self._facts(c0, self._snapshot_counters(), t0, t1, tokens)
        self._flop_facts(facts, live0)
        self.window_rids = [rid for rid, r in eng.requests.items()
                            if len(r.generated) > live0.get(rid, 0)]
        n_done = sum(1 for rid in self.window_rids
                     if eng.requests[rid].state == "done")
        return Window(e2e={"tokens_per_s": tokens / (t1 - t0)},
                      attempted=len(self.window_rids), failed=0,
                      facts=dict(facts, finished=n_done))

    # -- correctness --------------------------------------------------------------

    def _drain(self):
        """After the window: submit nothing more and step the engine until
        the requests served in the window that have finished hold
        ``check_tokens`` served tokens, or for ``drain_steps`` steps. A
        backlog request needs some hundreds of steps, so few finish inside
        the window itself; the drain waits for answers that were due, on
        the same engine and programs, and is not timed."""
        eng = self.eng
        for _ in range(int(self.mix["drain_steps"])):
            served = sum(len(eng.requests[r].generated)
                         for r in self.window_rids
                         if eng.requests[r].state == "done")
            if served >= self.mix["check_tokens"] or not self._step():
                break
        self.drain_steps = int(eng.counters["steps"]) - self.steps_at_close

    def release(self):
        """Finish the window's answers, keep on the host what the check
        needs, and free the engine."""
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
                           {k: np.asarray(r.parked[k])
                            for k in ("k_codes", "k_scales", "v_codes",
                                      "v_scales")})
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
        logits, kv = llm.forward(self.params, seq, cold, self.c, mode=mode,
                                 rows=rows, keep_kv=keep_kv)
        return logits, kv

    def _kv_steps(self, parks, kv, codes, pos) -> float:
        err = 0.0
        for li, (k, v) in enumerate(kv):
            for name, x in (("k", k), ("v", v)):
                err = max(err, llm.code_error(
                    codes[f"{name}_codes"][li, :pos],
                    codes[f"{name}_scales"][li, :pos], x[:pos]))
        return err

    def check(self, control=False):
        lim = self.c["limits"]
        cmode = lim["control"]
        modes = ("int8", "fp8") if control else ()
        gaps = {m: [] for m in ("program",) + modes}
        for prompt, generated, parks in self.sample:
            ref, _ = self._ref_sequence(prompt, generated, parks, "f32")
            gaps["program"].append(llm.served_gap(ref, np.asarray(generated)))
            for m in modes:
                ctl, _ = self._ref_sequence(prompt, generated, parks, m)
                gaps[m].append(llm.served_gap(ref, ctl.argmax(-1)))
        widest = {m: max((float(g.max()) for g in v), default=float("inf"))
                  for m, v in gaps.items()}
        mean = {m: float(np.concatenate(v).mean()) if v else float("inf")
                for m, v in gaps.items()}
        prog_kv = ctl_kv = fault_kv = float("inf")
        if self.parked is not None:
            prompt, generated, pos, parks, codes = self.parked
            parks = [p for p in parks if p < pos]
            _, kv = self._ref_sequence(prompt, generated, parks, "f32",
                                       keep_kv=True, upto=pos)
            prog_kv = self._kv_steps(parks, kv, codes, pos)
            if control:
                _, kv8 = self._ref_sequence(prompt, generated, parks,
                                            cmode, keep_kv=True, upto=pos)
                ctl_kv = self._kv_steps(parks, kv, llm.pack_codes(kv8), pos)
                fault_kv = self._kv_steps(parks, kv, llm.lose_plane(codes),
                                          pos)
        side = cmode if control else "program"
        checks = {"widest_logit_gap": (widest[side],
                                       float(lim["widest_logit_gap"])),
                  "mean_logit_gap": (mean[side],
                                     float(lim["mean_logit_gap"])),
                  "kv_code_steps": (ctl_kv if control else prog_kv,
                                    float(lim["kv_code_steps"])),
                  "token_count_wrong": (self.wrong_count, 0)}
        self.extra = {"sample_tokens": int(sum(g.size for g in
                                               gaps["program"])),
                      "sample_requests": len(self.sample),
                      "drain_steps": self.drain_steps}
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

    def readings(self) -> dict:
        """Numbers reported beside the checks but not compared: the sample's
        size, the share of its tokens off the reference's argmax and their
        widest and mean gaps; in a control run also the program's own readings, both
        lower precisions' and the planted fault's."""
        return dict(getattr(self, "extra", {}))

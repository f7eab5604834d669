"""Continuous-batching serving engine with IBEX-managed KV residency.

The engine is the request-granular face of the paper's pool:

  * running requests occupy decode *lanes* (batch slots of the jit'd
    decode_step) — their recent tokens sit uncompressed in the hot ring
    (promoted region), older tokens in the quantized region;
  * a **preempted** request is *demoted*: its hot ring is quantized into the
    codes region on device (always a clean demotion — KV is append-only) and
    only the compressed codes + scales are parked on the host;
  * **resume** is a promotion — the lane adopts the parked codes (cold_len =
    full length, empty ring) and decode reads them directly through the fused
    dequant attention: zero KV bytes are ever dequantized on promotion;
  * **shadowed lanes** (§4.5 at request granularity): the parked copy is
    *kept* after resume, and — because KV is append-only — its prefix stays
    valid forever. ``Request.shadow_pos`` records how many tokens it covers;
    a re-preempt moves only the suffix generated since the last park
    (``pos - shadow_pos`` tokens), and an untouched resumed request moves
    **zero bytes**: the shadow is simply re-validated, like ``shadow_valid``
    pages in ``core/engine/ops.py``. Demotion cost is proportional to new
    tokens, never to context length;
  * **the copy moves a token range, not the lane.** A park fetches positions
    ``[shadow_pos, pos)`` of each leaf with a token axis (``[0, pos)`` at a
    request's first park) and writes them into the request's host buffer:
    one token-major array per leaf, made full-length and zero at the first
    park, which ``Request.parked`` shows as ``[layers, max_len, ...]``. A
    resume uploads the prefix ``[0, pos)`` and installs it into the lane in
    place (the cache is donated); positions past ``cold_len`` keep what the
    lane held before, which attention masks. Range lengths are rounded up a
    ladder of powers of two (``_ladder``), so each is one program, and the
    engine compiles every rung when it is built;
  * victim selection is the §4.4 second-chance sweep over lanes (reference
    bit = "generated a token since last sweep"), vectorized over all lanes
    in one pass (``SecondChanceLanes.select_mask``).

**Host-sync contract.** Lane bookkeeping (last token, position, reference
bit, active mask, remaining budget) lives in device arrays and is advanced
*inside* the jitted engine step — argmax, position advance, done detection
and reference-bit updates all happen on device. The host performs exactly
ONE device sync per decode step (``counters["step_syncs"]``): a single
``device_get`` of the (tokens, done, ref) triple that drives per-request
Python bookkeeping. Admission-path syncs (one per prefill bucket, one per
demotion fetch) are counted separately in ``counters["admit_syncs"]``.

**Telemetry.** The engine's phases are host spans on the profiler's clock
(``repro.obs.spans``: step, admit, prefill, preempt, park and its demote
and fetch, resume, decode, fetch), written only while a profiler runs;
``counters`` holds the counts, among them the bytes a park really fetches
and a resume really uploads.

**Prefill batching.** Fresh requests admitted in the same engine step are
prefilled together, grouped into power-of-two length buckets (right-padded;
``models/decode.prefill``'s ``lens`` argument keeps padded positions out of
the cache's valid range, so a padded row decodes identically to an unpadded
one). Attention-family models bucket freely; ssm/hybrid models group by
exact length only (right-padding would pollute the recurrent state). A
``prefill_budget`` caps the prompt tokens of one call: a bucket's group is
then split into calls of at most that many tokens, so that long prompts do
not cap the lane count at (all lanes x the largest bucket).

Scheduling: FIFO admission, at most one preemption per engine step. All
cache motion is counted in ``self.counters`` (bytes and events): parked
bytes are the *compressed* payload
(codes + scales) of the moved tokens — the bf16 hot ring is quantized
before parking, never moved raw.

``serve.serial.SerialEngine`` keeps the per-lane host-loop implementation
(per-request prefill, one sync per lane per step, no shadow) as the
tests' scheduling oracle; both engines share ``_EngineBase``.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.contracts import sync_contract
from repro.common.types import ModelConfig, ServeConfig
from repro.common.utils import next_pow2 as _next_pow2
from repro.core.compressor import quantize_blocks_fast
from repro.core.engine.policy import SecondChanceLanes
from repro.models import decode as D
from repro.models import transformer as T
from repro.obs import spans

WAITING, RUNNING, PREEMPTED, DONE = "waiting", "running", "preempted", "done"

# bf16 hot-ring leaves: quantized into the codes region on demotion, zeroed
# on resume — never parked, never moved
HOT_KEYS = ("k_hot", "v_hot", "lat_hot")
# leaves with a token axis (axis 1 of a lane's slice): a park fetches, and a
# resume uploads, a range of their positions; ``cold_len`` and the ssm
# state have none and move whole
TOKEN_KEYS = ("k_codes", "k_scales", "v_codes", "v_scales", "lat_codes",
              "lat_scales")
# the shortest token range a park or a resume moves
MIN_RANGE = 64


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    state: str = WAITING
    generated: List[int] = field(default_factory=list)
    lane: int = -1
    pos: int = 0                      # next position to write
    # demoted KV: codes and scales as [layers, max_len, ...] views of the
    # request's token-major host buffers, cold_len, any ssm state
    parked: Optional[Dict[str, Any]] = None
    # shadow coverage (§4.5): tokens [0, shadow_pos) of ``parked`` match the
    # device KV bit-for-bit — KV is append-only, so the prefix never goes
    # stale. A preempt at pos == shadow_pos moves zero bytes; at
    # pos > shadow_pos it moves only the (pos - shadow_pos)-token suffix.
    shadow_pos: int = 0
    # fabric: expander whose pool region holds the parked payload/shadow
    # (-1 = never parked). A resume onto a lane striped to a different
    # expander moves the payload across the fabric (counted).
    expander: int = -1


# ---------------------------------------------------------------------------
# Device-side engine ops (jitted once per (cfg, scfg, max_len) via
# _compiled_fns; shared by every Engine/SerialEngine instance).
# ---------------------------------------------------------------------------

def _engine_step_impl(params, cache, state, embeds=None, *, cfg: ModelConfig,
                      scfg: ServeConfig, max_len: int):
    """One decode step over all lanes, lane bookkeeping advanced on device.

    state: {tok,pos,remaining int32[lanes]; active,ref bool[lanes]}.
    Returns (cache, new_state, done[lanes]) — the host fetches
    (new_state.tok, done, new_state.ref) in one sync."""
    logits, cache = D.decode_step(params, cache, state["tok"], state["pos"],
                                  cfg, scfg, embeds)
    active = state["active"]
    tok = jnp.where(active, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    state["tok"])
    pos = state["pos"] + active
    remaining = state["remaining"] - active
    done = active & ((remaining <= 0) | (pos >= max_len - 1))
    new_state = {"tok": tok, "pos": pos, "remaining": remaining,
                 "active": active & ~done, "ref": state["ref"] | active}
    return cache, new_state, done


def _prefill_impl(params, batch, lens, *, cfg: ModelConfig, scfg: ServeConfig,
                  max_len: int):
    """Bucketed prefill: (first tokens int32[B], cache). argmax happens on
    device so admission costs one fetch of B scalars per bucket."""
    logits, cache = D.prefill(params, batch, cfg, scfg, max_len, lens=lens)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


def _ring_to_codes(codes, scales, hot, cold_len, pos, W: int, bits: int,
                   impl: str = "auto"):
    """Quantize the live ring tokens (positions [max(cold_len, pos-W), pos))
    into the codes region — the device half of a lane demotion. Mirrors the
    streaming eviction in ``models/decode._evict_to_codes`` but for the whole
    ring at once. codes [Lyr,T,...], scales [Lyr,T,...], hot [Lyr,W,...,D].
    ``impl`` routes the quantize through the Pallas qpack kernel on TPU."""
    T_ = codes.shape[1]
    D_ = hot.shape[-1]
    c, s = quantize_blocks_fast(hot.astype(jnp.float32), bits, D_, impl=impl)
    t = jnp.arange(T_)
    sel = (t[None, :] >= cold_len[:, None]) & (t[None, :] >= pos - W) & \
        (t[None, :] < pos)                                     # [Lyr, T]
    slot = t % W
    gc = jnp.take(c, slot, axis=1)                 # slot content per position
    gs = jnp.take(s[..., 0], slot, axis=1)
    selc = sel.reshape(sel.shape + (1,) * (codes.ndim - 2))
    sels = sel.reshape(sel.shape + (1,) * (scales.ndim - 2))
    return jnp.where(selc, gc, codes), jnp.where(sels, gs, scales)


def _demote_lane_impl(lane_cache, pos, *, scfg: ServeConfig):
    """Clean-demote one lane's cache slice: every ring token is quantized
    into the codes region and cold_len advances to ``pos``; the hot ring
    becomes dead weight (dropped by the host before parking). SSM state has
    no compressed form and passes through raw (counted honestly)."""
    W, bits = scfg.hot_window, scfg.kv_rate_bits
    impl = getattr(scfg, "quantize_impl", "auto")
    out = dict(lane_cache)
    if "k_codes" in out:
        out["k_codes"], out["k_scales"] = _ring_to_codes(
            out["k_codes"], out["k_scales"], out["k_hot"], out["cold_len"],
            pos, W, bits, impl)
        out["v_codes"], out["v_scales"] = _ring_to_codes(
            out["v_codes"], out["v_scales"], out["v_hot"], out["cold_len"],
            pos, W, bits, impl)
    if "lat_codes" in out:
        out["lat_codes"], out["lat_scales"] = _ring_to_codes(
            out["lat_codes"], out["lat_scales"], out["lat_hot"],
            out["cold_len"], pos, W, bits, impl)
    if "cold_len" in out:
        out["cold_len"] = jnp.maximum(out["cold_len"], pos)
    return out


@functools.lru_cache(maxsize=32)
def _compiled_fns(cfg: ModelConfig, scfg: ServeConfig, max_len: int):
    """Engine-shared jitted fns, cached on the hashable configs so
    constructing N engines (tests, replicas) compiles once. Each is a named
    function, so that its device module is ``jit_serve_<name>`` in a
    profile (a jitted ``functools.partial`` is ``jit__unknown``)."""
    def serve_decode_step(params, cache, state, embeds=None):
        return _engine_step_impl(params, cache, state, embeds, cfg=cfg,
                                 scfg=scfg, max_len=max_len)

    def serve_prefill(params, batch, lens):
        return _prefill_impl(params, batch, lens, cfg=cfg, scfg=scfg,
                             max_len=max_len)

    def serve_demote_lane(cache, lane, pos):
        demoted = _demote_lane_impl(_lane_slice(cache, lane), pos, scfg=scfg)
        return {k: v for k, v in demoted.items() if k not in HOT_KEYS}

    def serve_decode(params, cache, tokens, pos, embeds=None):
        return D.decode_step(params, cache, tokens, pos, cfg, scfg, embeds)

    return (jax.jit(serve_decode_step), jax.jit(serve_prefill),
            jax.jit(serve_demote_lane), jax.jit(serve_decode))


# ---------------------------------------------------------------------------
# Lane slice/install (batch axis 1; hybrid ssm leaves carry a period axis
# before batch, so the ssm subtree is sliced on its own axis), and the token
# ranges a park fetches and a resume installs.
# ---------------------------------------------------------------------------

def _ssm_batch_axis(cache) -> int:
    return 2 if "k_codes" in cache else 1     # hybrid: [G, period, B, ...]


def _lane_slice(cache, lane):
    ax = _ssm_batch_axis(cache)
    return {k: (jax.tree_util.tree_map(lambda a: jnp.take(a, lane, axis=ax),
                                       v) if k == "ssm" else v[:, lane])
            for k, v in cache.items()}


def _lanes_install(cache, lanes: jnp.ndarray, sub_cache):
    """Install a prefilled sub-batch (rows aligned with ``lanes``) into the
    engine cache in one batched scatter per leaf."""
    ax = _ssm_batch_axis(cache)
    out = {}
    for k, v in cache.items():
        if k == "ssm":
            out[k] = jax.tree_util.tree_map(
                lambda a, s: jnp.moveaxis(
                    jnp.moveaxis(a, ax, 0).at[lanes].set(
                        jnp.moveaxis(s.astype(a.dtype), ax, 0)), 0, ax),
                v, sub_cache[k])
        else:
            out[k] = v.at[:, lanes].set(sub_cache[k].astype(v.dtype))
    return out


def _to_words(x):
    """``x``'s bytes in row-major order as one vector of 32-bit words. On
    the device a lane's leaves keep the token axis minor-most and bytes
    interleaved in tiles, so a range that crosses in its own shape is laid
    out anew by the host; a word vector's device layout is linear, and it
    crosses as a plain copy."""
    if x.dtype != jnp.uint8:
        x = jax.lax.bitcast_convert_type(x, jnp.uint8)
    return jax.lax.bitcast_convert_type(x.reshape(-1, 4), jnp.uint32)


def _from_words(w, shape, dtype):
    """The array of ``shape`` and ``dtype`` whose bytes ``_to_words`` gave."""
    x = jax.lax.bitcast_convert_type(w, jnp.uint8)
    size = jnp.dtype(dtype).itemsize
    if size > 1:
        x = jax.lax.bitcast_convert_type(x.reshape(-1, size), dtype)
    return x.reshape(shape)


def serve_park_range(kept, start, *, n: int):
    """Positions ``[start, start + n)`` of each token leaf of a demoted lane,
    token-major (the host buffer's layout) and as words (``_to_words``);
    other leaves whole. ``dynamic_slice`` clamps a start past
    ``max_len - n``: the caller passes it clamped already."""
    return {k: (_to_words(jnp.moveaxis(
        jax.lax.dynamic_slice_in_dim(v, start, n, 1), 1, 0))
        if k in TOKEN_KEYS else v) for k, v in kept.items()}


def _put_lane(a, lane, x, ax: int = 1):
    """Write ``x`` (lane ``lane``'s slice of ``a`` without batch axis ``ax``,
    or a prefix of it along the next axis) into ``a`` at that lane."""
    start = [0] * a.ndim
    start[ax] = lane
    return jax.lax.dynamic_update_slice(
        a, jnp.expand_dims(x.astype(a.dtype), ax), start)


def _prefix(v, words):
    """A token leaf's parked prefix, from its words, as ``[layers, n, ...]``
    for the cache leaf ``v`` (``[layers, lanes, max_len, ...]``)."""
    row = (v.shape[0],) + v.shape[3:]
    n = words.size * 4 // (int(np.prod(row)) * v.dtype.itemsize)
    return jnp.moveaxis(_from_words(words, (n,) + row, v.dtype), 0, 1)


def serve_install_lane(cache, lane, parked):
    """Promotion in place: each token leaf's parked prefix (token-major
    words, ``_to_words``) written over the lane's first ``n`` positions,
    its hot ring zeroed, its ``cold_len`` and ssm state set. Positions from
    ``n`` on keep the previous occupant's values, past ``cold_len``."""
    ax = _ssm_batch_axis(cache)

    def put(a, x, axis=1):
        return _put_lane(a, lane, x, axis)

    return {k: (jax.tree_util.tree_map(lambda a, s: put(a, s, ax), v,
                                       parked[k]) if k == "ssm"
                else put(v, jnp.zeros(v.shape[:1] + v.shape[2:], v.dtype))
                if k in HOT_KEYS
                else put(v, _prefix(v, parked[k])) if k in TOKEN_KEYS
                else put(v, parked[k]))
            for k, v in cache.items()}


# the lane copies depend on shapes alone, so one jitted function each serves
# every engine; in a profile they are jit_serve_park_range and
# jit_serve_install_lane
_park_range_fn = jax.jit(serve_park_range, static_argnames="n")
_install_fn = jax.jit(serve_install_lane, donate_argnums=0)


def _ladder(max_len: int) -> tuple:
    """Token range lengths a park fetches and a resume uploads: powers of
    two from ``MIN_RANGE``, then ``max_len``."""
    out, n = [], MIN_RANGE
    while n < max_len:
        out.append(n)
        n *= 2
    return tuple(out) + (max_len,)


def _token_major(leaf: np.ndarray) -> np.ndarray:
    """The token-major host buffer behind a parked leaf's
    ``[layers, max_len, ...]`` view (a view of it, writable)."""
    return np.moveaxis(leaf, 1, 0)


def _leaf_bytes(tree) -> int:
    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(tree))


def _moved_bytes(parked: Dict[str, Any], n_tokens: int, max_len: int) -> int:
    """Bytes a park/restore moves in the model: the compressed payload
    (codes + scales) of ``n_tokens`` tokens, plus raw recurrent state for
    ssm/hybrid in full (it has no compressed form and no append-only
    prefix). The counter is the modeled CXL traffic of the motion; what
    really crosses between host and device, token ranges rounded up the
    ladder, is ``park_fetch_bytes`` and ``resume_upload_bytes``."""
    total = 0
    for k, v in parked.items():
        if k == "ssm":
            total += sum(int(a.nbytes)
                         for a in jax.tree_util.tree_leaves(v))
        elif k == "cold_len":
            continue
        else:
            total += (int(v.nbytes) // max_len) * min(int(n_tokens), max_len)
    return total


# ---------------------------------------------------------------------------
# Shared engine chassis: request/queue/lane bookkeeping, park/restore
# mechanics, sync counting. Subclasses decide scheduling + decode structure.
# ---------------------------------------------------------------------------

class _EngineBase:
    def __init__(self, cfg: ModelConfig, scfg: ServeConfig, params,
                 max_len: int = 2048, seed: int = 0):
        self.cfg, self.scfg = cfg, scfg
        self.params = params
        self.max_len = max_len
        self.lanes = scfg.max_running
        self.cache = D.init_cache(cfg, scfg, self.lanes, max_len)
        self.lane_req: List[Optional[int]] = [None] * self.lanes
        self.requests: Dict[int, Request] = {}
        self.queue: List[int] = []
        self._next_rid = 0
        # victim selection goes through the same §4.4 policy shape as the
        # pool's clock engine, vectorized over all lanes (engine/policy.py)
        self._victim_policy = SecondChanceLanes(self.lanes)
        self._ref = np.zeros((self.lanes,), bool)
        self.counters = {"promotions": 0, "demotions": 0, "preempt_bytes": 0,
                         "resume_bytes": 0, "steps": 0, "tokens": 0,
                         "step_syncs": 0, "admit_syncs": 0,
                         "shadow_repreempts": 0, "prefill_batches": 0,
                         "cross_expander_resumes": 0,
                         # what really crosses between host and device: the
                         # bytes of the ranges a park fetches and a resume
                         # uploads (preempt_bytes / resume_bytes are the
                         # modeled CXL payload), the tokens a park fetches
                         # (rounded up the ladder), and the parks that
                         # started past a shadow-covered prefix
                         "park_fetch_bytes": 0, "resume_upload_bytes": 0,
                         "park_fetch_tokens": 0, "parked_suffix_fetches": 0}
        # fabric-aware serving: lanes stripe across the expander pool fabric;
        # preempted payloads park on (and are charged to) their lane's
        # expander, and victim selection balances parked load across
        # expanders (see SecondChanceLanes.select_mask groups)
        self.n_expanders = max(int(getattr(scfg, "n_expanders", 1)), 1)
        self.lane_expander = np.arange(self.lanes) % self.n_expanders
        self.expander_stats = {
            "parked": np.zeros((self.n_expanders,), np.int64),
            "preempt_bytes": np.zeros((self.n_expanders,), np.int64),
            "resume_bytes": np.zeros((self.n_expanders,), np.int64),
        }
        # n_expanders is scheduling-only (never read by the jitted model
        # code): normalize it out of the compile key so a fabric-striped
        # engine shares compiled programs with the single-expander one
        (self._step_fn, self._prefill_fn, self._demote_fn,
         self._decode_fn) = _compiled_fns(
            cfg, dataclasses.replace(scfg, n_expanders=1), max_len)
        # ssm models have no token axis: their parks move no range
        self._ladder = _ladder(max_len) if \
            any(k in TOKEN_KEYS for k in self.cache) else (0,)
        self._warm_lane_copies()

    # -- client API ---------------------------------------------------------

    def submit(self, prompt: List[int], max_new_tokens: int = 16) -> int:
        if not 1 <= len(prompt) <= self.max_len - 1:
            raise ValueError(f"prompt length {len(prompt)} outside "
                             f"[1, {self.max_len - 1}]")
        rid = self._next_rid
        self._next_rid += 1
        self.requests[rid] = Request(rid, list(prompt), max_new_tokens)
        self.queue.append(rid)
        return rid

    def result(self, rid: int) -> List[int]:
        return self.requests[rid].generated

    def run_until_done(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.step():
                return

    def step(self) -> bool:
        raise NotImplementedError

    # -- host <-> device ----------------------------------------------------

    def _fetch(self, tree, kind: str):
        """The ONLY place device values cross to the host. Each call is one
        blocking sync, counted per path (step vs admission)."""
        self.counters[kind] += 1
        return jax.device_get(tree)

    # -- delivered-time accounting (DESIGN.md §12) ---------------------------

    def modeled_time(self, devices=None) -> Dict[str, Any]:
        """Convert the engine's preempt/resume byte and host-sync counters
        into modeled seconds (simx.time.serve_modeled_time): per-expander
        payload motion priced by each expander's own DeviceConfig
        (bottleneck across the fabric stripe), plus one CXL round trip per
        host sync. ``modeled_s_per_step`` is the figure of merit —
        serial-vs-batched and fabric-striped serving compare in seconds,
        not just tokens/sec."""
        from repro.simx import time as TM
        devs = TM.resolve_fleet(devices, self.n_expanders)
        return TM.serve_modeled_time(self.counters, self.expander_stats,
                                     devs)

    # -- shared mechanics ---------------------------------------------------

    def _rung(self, need: int) -> int:
        """The shortest range length of the ladder that holds ``need``
        tokens (0 where no leaf has a token axis)."""
        return next((n for n in self._ladder if n >= need), 0)

    def _warm_lane_copies(self) -> None:
        """Run the demotion and, for every range length of the ladder, the
        park's range and the resume's install once on lane 0 while the cache
        is still empty (the install writes back the zeros it read): no
        length a request reaches later lowers a program while serving."""
        kept = self._demote_fn(self.cache, np.int32(0), np.int32(0))
        for n in self._ladder:
            got = jax.device_get(_park_range_fn(kept, np.int32(0), n=n))
            self.cache = _install_fn(self.cache, np.int32(0),
                                     jax.device_put(got))

    def _free_lane(self) -> Optional[int]:
        for i, r in enumerate(self.lane_req):
            if r is None:
                return i
        return None

    def _drop_park(self, req: Request) -> None:
        """Release a request's parked payload/shadow (done, or baseline
        resume) and its expander's park slot."""
        if req.parked is not None and req.expander >= 0:
            self.expander_stats["parked"][req.expander] -= 1
        req.parked = None

    def _park_lane(self, req: Request, lane: int) -> None:
        """Demote the lane on device (quantize ring -> codes) and park the
        compressed payload on the lane's expander: only the positions the
        request's shadow does not hold cross to the host, and only they are
        charged."""
        with spans.span(spans.PARK, rid=req.rid, lane=lane, tokens=req.pos):
            covered = req.shadow_pos if req.parked is not None else 0
            exp = int(self.lane_expander[lane])
            if req.parked is None or req.expander != exp:
                if req.parked is not None and req.expander >= 0:
                    self.expander_stats["parked"][req.expander] -= 1
                self.expander_stats["parked"][exp] += 1
            n = self._rung(req.pos - covered)
            # a range that would run past max_len starts earlier: the
            # positions below the shadow are equal on both sides
            start = min(covered, self.max_len - n)
            with spans.span(spans.PARK_DEMOTE):
                kept = self._demote_fn(self.cache, np.int32(lane),
                                       np.int32(req.pos))
                got = _park_range_fn(kept, np.int32(start), n=n)
            with spans.span(spans.PARK_FETCH):
                got = self._fetch(got, "admit_syncs")
            self.counters["park_fetch_bytes"] += _leaf_bytes(got)
            self.counters["park_fetch_tokens"] += n
            self.counters["parked_suffix_fetches"] += covered > 0
            if req.parked is None:
                # one buffer per leaf for the request's life, full length;
                # pages never written cost nothing and read as zero
                req.parked = {k: np.moveaxis(np.zeros(
                    (self.max_len, c.shape[0]) + c.shape[3:], c.dtype), 0, 1)
                    for k, c in self.cache.items() if k in TOKEN_KEYS}
            for k, v in got.items():
                if k in TOKEN_KEYS:
                    tm = _token_major(req.parked[k])
                    tm[start:start + n] = v.view(tm.dtype).reshape(
                        (n,) + tm.shape[1:])
                else:
                    req.parked[k] = v
            req.shadow_pos = req.pos
            req.expander = exp
            moved = _moved_bytes(req.parked, req.pos - covered, self.max_len)
            self.counters["preempt_bytes"] += moved
            self.expander_stats["preempt_bytes"][exp] += moved

    def _install_parked(self, req: Request, lane: int) -> None:
        """Promotion: upload the parked prefix that holds the request's
        tokens and install it into the lane in place (empty ring, full
        cold_len); no decompression happens (fused attention reads codes
        directly) — zero KV bytes dequantized."""
        n = self._rung(req.pos)
        up = {k: (_token_major(a)[:n].reshape(-1).view(np.uint32)
                  if k in TOKEN_KEYS else a) for k, a in req.parked.items()}
        self.counters["resume_upload_bytes"] += _leaf_bytes(up)
        self.cache = _install_fn(self.cache, np.int32(lane),
                                 jax.device_put(up))
        moved = _moved_bytes(req.parked, req.pos, self.max_len)
        self.counters["resume_bytes"] += moved
        exp = int(self.lane_expander[lane])
        self.expander_stats["resume_bytes"][exp] += moved
        cross = req.expander >= 0 and req.expander != exp
        if cross:
            # the parked payload crosses the fabric to the new lane's
            # expander; the shadow follows it (its prefix stays valid —
            # append-only KV does not care which expander holds it)
            self.counters["cross_expander_resumes"] += 1
            self.expander_stats["parked"][req.expander] -= 1
            self.expander_stats["parked"][exp] += 1
            req.expander = exp
        self.counters["promotions"] += 1
        req.lane = lane
        req.state = RUNNING
        self.lane_req[lane] = req.rid


class Engine(_EngineBase):
    """Device-resident batched scheduler (module docstring has the design)."""

    def __init__(self, cfg: ModelConfig, scfg: ServeConfig, params,
                 max_len: int = 2048, seed: int = 0):
        super().__init__(cfg, scfg, params, max_len, seed)
        # device-resident lane bookkeeping, advanced inside the jitted step
        self.state = {
            "tok": jnp.zeros((self.lanes,), jnp.int32),
            "pos": jnp.zeros((self.lanes,), jnp.int32),
            "remaining": jnp.zeros((self.lanes,), jnp.int32),
            "active": jnp.zeros((self.lanes,), bool),
            "ref": jnp.zeros((self.lanes,), bool),
        }
        # ssm/hybrid recurrent state cannot tolerate right-padding: group by
        # exact length instead of power-of-two buckets
        self._bucketed = cfg.family not in ("ssm", "hybrid")

    def _set_lane_state(self, lane: int, tok: int, pos: int, remaining: int
                        ) -> None:
        st = self.state
        self.state = {
            "tok": st["tok"].at[lane].set(tok),
            "pos": st["pos"].at[lane].set(pos),
            "remaining": st["remaining"].at[lane].set(remaining),
            "active": st["active"].at[lane].set(True),
            "ref": st["ref"].at[lane].set(True),
        }
        self._ref[lane] = True

    def _clear_lane_state(self, lane: int) -> None:
        st = self.state
        self.state = dict(st, active=st["active"].at[lane].set(False),
                          ref=st["ref"].at[lane].set(False))
        self._ref[lane] = False

    # -- scheduling ---------------------------------------------------------

    def _bucket(self, n: int) -> int:
        if not self._bucketed:
            return n
        return min(max(_next_pow2(n), 8), self.max_len)

    def _admit(self) -> None:
        fresh, resumed = [], []

        def claim(rid: int, lane: int) -> None:
            self.lane_req[lane] = rid
            req = self.requests[rid]
            (resumed if req.parked is not None else fresh).append((rid, lane))

        while self.queue:
            lane = self._free_lane()
            if lane is None:
                break
            claim(self.queue.pop(0), lane)
        # time-slicing: at most ONE preemption per engine step — the evicted
        # request rejoins the queue tail and waits its turn. (An unbounded
        # preempt-while-queue-nonempty loop never terminates: every
        # preemption re-fills the queue it is trying to drain.) Lanes claimed
        # this step are not eligible victims (their KV is not installed yet).
        if self.queue:
            claimed = {lane for _, lane in fresh + resumed}
            occupied = np.array([r is not None and i not in claimed
                                 for i, r in enumerate(self.lane_req)])
            # fabric-aware balancing: among sweep candidates prefer the
            # lane whose expander holds the fewest parked payloads, so
            # preemptions spread across the expander fabric
            groups = self.lane_expander if self.n_expanders > 1 else None
            load = (self.expander_stats["parked"]
                    if self.n_expanders > 1 else None)
            victim, new_ref = self._victim_policy.select_mask(
                occupied, self._ref, groups=groups, group_load=load)
            if victim is not None:
                self._ref = new_ref
                self.state = dict(self.state, ref=jnp.asarray(new_ref))
                self._preempt(victim)
                claim(self.queue.pop(0), victim)
        for rid, lane in resumed:
            self._resume(self.requests[rid], lane)
        if fresh:
            self._start_fresh(fresh)

    def _prefill_calls(self, L: int, grp: list) -> list:
        """A bucket's admission group as prefill calls: one call, or where
        ``prefill_budget`` is set, calls of at most that many prompt tokens
        (a power of two of rows, at least one)."""
        budget = self.scfg.prefill_budget
        if not budget:
            return [grp]
        rows = 1 << (max(budget // L, 1).bit_length() - 1)
        return [grp[i:i + rows] for i in range(0, len(grp), rows)]

    def _start_fresh(self, items) -> None:
        """Batched prefill of all fresh admissions, grouped into length
        buckets — one compile and one host sync per bucket (per call of the
        prefill budget) instead of one per request."""
        groups: Dict[int, list] = {}
        for rid, lane in items:
            L = self._bucket(len(self.requests[rid].prompt))
            groups.setdefault(L, []).append((rid, lane))
        for L, bucket_grp in sorted(groups.items()):
            for grp in self._prefill_calls(L, bucket_grp):
                self._prefill_group(L, grp)

    def _prefill_group(self, L: int, grp: list) -> None:
        """One prefill call of ``grp``'s prompts, right-padded to ``L``."""
        with spans.span(spans.PREFILL, bucket=L, rows=len(grp)):
            k = len(grp)
            Bp = _next_pow2(k)      # pad rows too: fewer compiled shapes
            tokens = np.zeros((Bp, L), np.int32)
            lens = np.ones((Bp,), np.int32)
            for i, (rid, _) in enumerate(grp):
                p = self.requests[rid].prompt
                tokens[i, :len(p)] = p
                lens[i] = len(p)
            batch = {"tokens": jnp.asarray(tokens)}
            if self.cfg.frontend != "none":
                batch["embeds"] = jnp.zeros((Bp, L, self.cfg.d_model),
                                            jnp.bfloat16)
            toks, sub = self._prefill_fn(self.params, batch,
                                         jnp.asarray(lens))
            lanes_arr = jnp.asarray([lane for _, lane in grp])
            ax = _ssm_batch_axis(self.cache)
            real = {kk: (jax.tree_util.tree_map(
                        lambda a: jax.lax.slice_in_dim(a, 0, k, axis=ax),
                        vv) if kk == "ssm" else vv[:, :k])
                    for kk, vv in sub.items()}
            self.cache = _lanes_install(self.cache, lanes_arr, real)
            toks_h = self._fetch(toks[:k], "admit_syncs")
            self.counters["prefill_batches"] += 1
            for i, (rid, lane) in enumerate(grp):
                req = self.requests[rid]
                req.generated.append(int(toks_h[i]))
                req.pos = int(lens[i])
                req.lane = lane
                req.state = RUNNING
                self.counters["promotions"] += 1
                remaining = req.max_new_tokens - 1
                if remaining <= 0 or req.pos >= self.max_len - 1:
                    req.state = DONE
                    req.lane = -1
                    self.lane_req[lane] = None
                else:
                    self._set_lane_state(lane, int(toks_h[i]), req.pos,
                                         remaining)

    def _preempt(self, lane: int) -> None:
        """Demote the lane. A shadow still covering every token short-
        circuits the whole thing: zero bytes move, the shadow is re-validated
        (§4.5); a partially-covering shadow pays only for the uncovered
        suffix (_park_lane). The zero-byte branch is the N=0 limit of the
        suffix charge — in this engine's own loop a resumed lane always
        decodes before it can be re-selected, so the limit case fires only
        when a caller (scheduler churn, tests) preempts between
        resume and decode; the organic payoff is the suffix-only charge."""
        rid = self.lane_req[lane]
        req = self.requests[rid]
        with spans.span(spans.PREEMPT, rid=rid, lane=lane):
            if req.parked is not None and req.shadow_pos >= req.pos:
                self.counters["shadow_repreempts"] += 1
            else:
                self._park_lane(req, lane)
        self.counters["demotions"] += 1
        req.state = PREEMPTED
        req.lane = -1
        self.lane_req[lane] = None
        self._clear_lane_state(lane)
        self.queue.append(rid)

    def _resume(self, req: Request, lane: int) -> None:
        """Promotion; the parked copy stays behind as a shadow — its prefix
        (append-only KV) stays valid no matter how many tokens follow."""
        with spans.span(spans.RESUME, rid=req.rid, lane=lane):
            self._install_parked(req, lane)
            self._set_lane_state(lane, req.generated[-1], req.pos,
                                 req.max_new_tokens - len(req.generated))

    # -- decode step ---------------------------------------------------------

    @sync_contract(syncs_per="step", fetches=1)
    def step(self) -> bool:
        """One engine iteration. Returns False when no work remains.
        Exactly one host sync per call once lanes are running — declared
        above and checked both by the R5 lint and by tests/test_spans.py via
        ``verify_sync_counters`` (step_syncs == steps)."""
        with spans.span(spans.STEP, step=self.counters["steps"]):
            with spans.span(spans.ADMIT):
                self._admit()
            active = [(lane, rid) for lane, rid in enumerate(self.lane_req)
                      if rid is not None]
            if not active:
                return bool(self.queue)
            kwargs = {}
            if self.cfg.frontend != "none":
                kwargs["embeds"] = jnp.zeros((self.lanes, self.cfg.d_model),
                                             jnp.bfloat16)
            with spans.span(spans.DECODE):
                self.cache, self.state, done = self._step_fn(
                    self.params, self.cache, self.state, **kwargs)
            self.counters["steps"] += 1
            # ONE fused fetch
            with spans.span(spans.FETCH):
                tok_h, done_h, ref_h = self._fetch(
                    (self.state["tok"], done, self.state["ref"]),
                    "step_syncs")
            self._ref = np.array(ref_h, bool, copy=True)
            for lane, rid in active:
                req = self.requests[rid]
                req.pos += 1
                req.generated.append(int(tok_h[lane]))
                self.counters["tokens"] += 1
                if done_h[lane]:
                    req.state = DONE
                    req.lane = -1
                    self._drop_park(req)   # free the shadow's host memory
                    self.lane_req[lane] = None
        return True

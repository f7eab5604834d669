"""The program's spans and scopes (``repro.obs.spans``).

  * the serve engine, under ``jax.profiler.trace``, writes every declared
    host span, nested as declared, with the request's ``rid`` and ``lane``
    and the step's ``step`` as stats;
  * with the profiler on, the engine still syncs once per decode step and
    decodes the same tokens as with it off;
  * ``park_fetch_bytes`` and ``resume_upload_bytes`` count the bytes that
    really cross: a park's token range past the shadow and a resume's
    prefix, each rounded up the ladder of range lengths, plus ``cold_len``;
    nothing on a re-preempt the shadow covers;
  * a built engine parks and resumes at every range length without
    lowering a program;
  * the replay window program carries the four phase scopes, and is the
    same program without them.
"""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.common.contracts import verify_sync_counters
from repro.common.types import PoolConfig, ServeConfig
from repro.configs import get_reduced
from repro.core.engine import batch as B
from repro.core.engine import state as S
from repro.core.engine.policy import POLICIES
from repro.models import transformer as T
from repro.obs import spans
from repro.serve.engine import TOKEN_KEYS, Engine

CFG = get_reduced("llama3_8b")
SCFG = ServeConfig(max_running=2, hot_window=16, attn_chunk=32,
                   kv_rate_bits=8)

# each serve span's enclosing program span (None: a root)
PARENT = {spans.STEP: None, spans.ADMIT: spans.STEP,
          spans.PREFILL: spans.ADMIT, spans.PREEMPT: spans.ADMIT,
          spans.PARK: spans.PREEMPT, spans.PARK_DEMOTE: spans.PARK,
          spans.PARK_FETCH: spans.PARK, spans.RESUME: spans.ADMIT,
          spans.DECODE: spans.STEP, spans.FETCH: spans.STEP}
ARGS = {spans.STEP: {"step"}, spans.PREFILL: {"bucket", "rows"},
        spans.PREEMPT: {"rid", "lane"}, spans.PARK: {"rid", "lane", "tokens"},
        spans.RESUME: {"rid", "lane"}}


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)[0]


def _prompt(seed, n=20):
    return list(np.random.default_rng(seed).integers(
        1, CFG.vocab_size, size=n))


def _serve(params):
    """4 requests through 2 lanes: prefill, preemption, park and resume."""
    eng = Engine(CFG, SCFG, params, max_len=128)
    rids = [eng.submit(_prompt(i), max_new_tokens=6) for i in range(4)]
    eng.run_until_done(max_steps=400)
    return eng, [eng.result(r) for r in rids]


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    """(engine, tokens, program spans) of one engine run under the
    profiler; spans as (name, start_ns, end_ns, stats)."""
    _serve(params)                                  # compile off the trace
    d = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(d)):
        eng, out = _serve(params)
    path, = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)
    evs = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name in spans.SPANS]
    return eng, out, evs


def _parent(evs, i):
    """The innermost other span that encloses span ``i``, or None."""
    _, s, e, _ = evs[i]
    enclosing = [o for j, o in enumerate(evs)
                 if j != i and o[1] <= s and e <= o[2]]
    return max(enclosing, key=lambda o: (o[1], -o[2]), default=None)


def test_serve_spans_nest_as_declared_with_their_args(traced):
    eng, _, evs = traced
    assert {e[0] for e in evs} == set(spans.SPANS)
    decoded = []
    for i, (name, _, _, stats) in enumerate(evs):
        assert ARGS.get(name, set()) <= set(stats), (name, stats)
        parent = _parent(evs, i)
        assert (parent[0] if parent else None) == PARENT[name], (name, parent)
        if name == spans.PARK:           # a park is its preempt's request
            assert (parent[3]["rid"], parent[3]["lane"]) == \
                (stats["rid"], stats["lane"])
        if name == spans.DECODE:
            decoded.append(parent[3]["step"])
    assert sorted(decoded) == list(range(eng.counters["steps"]))
    n = {k: sum(1 for e in evs if e[0] == k) for k in spans.SPANS}
    assert n[spans.DECODE] == n[spans.FETCH] == eng.counters["steps"]
    assert n[spans.PREEMPT] == eng.counters["demotions"]
    assert n[spans.PREFILL] == eng.counters["prefill_batches"]


def test_profiled_engine_syncs_once_per_step_and_decodes_the_same(
        traced, params):
    eng_on, out_on, _ = traced
    eng_off, out_off = _serve(params)
    assert out_on == out_off, "the profiler changed decoded tokens"
    assert eng_on.counters == eng_off.counters
    assert eng_on.counters["step_syncs"] == eng_on.counters["steps"]
    verify_sync_counters(Engine.step, eng_on.counters["steps"],
                         eng_on.counters["step_syncs"], what="profiled")


def _per_token_and_whole(eng):
    """Bytes of one token of a lane's token leaves, and of its ``cold_len``
    (the leaves a park moves whole)."""
    lanes = eng.lanes
    per_tok = sum(int(eng.cache[k].nbytes) // (lanes * eng.max_len)
                  for k in TOKEN_KEYS if k in eng.cache)
    return per_tok, int(eng.cache["cold_len"].nbytes) // lanes


def test_park_and_resume_count_the_bytes_that_cross(params):
    eng = Engine(CFG, SCFG, params, max_len=128)     # ranges of 64 and 128
    rid = eng.submit(_prompt(0, n=80), 10)
    for _ in range(3):
        eng.step()
    req = eng.requests[rid]
    c = eng.counters
    per_tok, whole = _per_token_and_whole(eng)

    def delta(before):
        return (c["park_fetch_bytes"] - before[0],
                c["resume_upload_bytes"] - before[1])

    def mark():
        return c["park_fetch_bytes"], c["resume_upload_bytes"]

    def requeue():
        eng.queue.remove(rid)
        eng.lane_req[0] = rid

    assert 64 < req.pos <= 128
    m = mark()
    eng._preempt(0)               # first park: [0, pos) rounded up to 128
    assert delta(m) == (128 * per_tok + whole, 0)
    assert c["park_fetch_tokens"] == 128 and c["parked_suffix_fetches"] == 0
    m = mark()
    requeue()
    eng._resume(req, 0)           # resume: the prefix [0, 128)
    assert delta(m) == (0, 128 * per_tok + whole)
    m = mark()
    eng._preempt(0)               # shadow hit
    assert c["shadow_repreempts"] == 1 and delta(m) == (0, 0)
    requeue()
    eng._resume(req, 0)
    eng.step()
    eng.step()
    m = mark()
    eng._preempt(0)               # a 2-token suffix: a range of 64
    assert delta(m) == (64 * per_tok + whole, 0)
    assert c["park_fetch_tokens"] == 128 + 64
    assert c["parked_suffix_fetches"] == 1
    # the logical charge is the suffix alone
    assert c["preempt_bytes"] == (req.pos) * per_tok


def test_parks_and_resumes_across_the_ladder_lower_no_program(params):
    """An engine compiles the park's and the resume's programs of every
    range length when it is built: once serving has warmed its own shapes,
    parks and resumes at range lengths it has not used yet lower nothing
    (the benchmark refuses a window that lowers a program)."""
    from jax._src import dispatch
    eng = Engine(CFG, SCFG, params, max_len=256)   # ranges 64, 128, 256
    short = eng.submit(_prompt(1, n=20), 40)
    long_ = eng.submit(_prompt(2, n=150), 40)
    for _ in range(2):
        eng.step()
    lane = {r: eng.requests[r].lane for r in (short, long_)}

    def cycle(rid):
        eng._preempt(lane[rid])
        eng.queue.remove(rid)
        eng.lane_req[lane[rid]] = rid
        eng._resume(eng.requests[rid], lane[rid])

    cycle(short)                   # the first park and resume: 64 tokens
    eng.step()
    events = {dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
              dispatch.BACKEND_COMPILE_EVENT}
    lowered = []

    def listen(event, duration, **_):
        if event in events:
            lowered.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        cycle(long_)               # the first park of 152 tokens: 256
        cycle(long_)               # a shadow hit: nothing
        eng.step()
        eng.step()
        cycle(long_)               # a suffix: 64
        cycle(short)               # a suffix: 64
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    c = eng.counters
    assert c["park_fetch_tokens"] == 64 + 256 + 64 + 64
    assert c["shadow_repreempts"] == 1 and c["parked_suffix_fetches"] == 2
    assert lowered == []


def _pool_case():
    cfg = PoolConfig(n_pages=64, n_pchunks=16, n_cchunks=1024,
                     mcache_sets=4, store_payload=False)
    rng = np.random.default_rng(0)
    n_win, w = 4, B.DEFAULT_WINDOW
    pool = S.make_pool(cfg, seed=0)
    xs = (jnp.asarray(rng.integers(0, 64, (n_win, w)), jnp.int32),
          jnp.asarray(rng.random((n_win, w)) < 0.2),
          jnp.asarray(rng.integers(0, 4, (n_win, w)), jnp.int32))
    return cfg, pool, xs


def _optimized_hlo(cfg, pool, xs):
    jax.clear_caches()                   # trace afresh
    return B._replay_windows.lower(pool, cfg, POLICIES["ibex"],
                                   *xs).compile().as_text()


def normalized(hlo: str) -> str:
    """An optimized HLO module's text without what names and scopes alone
    change: the ``metadata={...}`` of each instruction, the debug sections
    (source files, functions, locations, stack frames) and the numbers in
    identifiers (renumbered in order of first appearance: named scopes shift
    the numbering the lowering hands out before the compiler's clean-up)."""
    hlo = re.sub(r",?\s*metadata=\{[^}]*\}", "", hlo)
    hlo = re.sub(r"(?m)^(FileNames|FunctionNames|FileLocations|StackFrames"
                 r"|\d+ [{\"].*)\n", "", hlo)
    seen = {}
    return re.sub(r"%[A-Za-z_][\w\-.]*",
                  lambda m: seen.setdefault(m.group(), f"%v{len(seen)}"), hlo)


def test_replay_window_scopes_leave_the_program_unchanged(monkeypatch):
    cfg, pool, xs = _pool_case()
    scoped = _optimized_hlo(cfg, pool, xs)
    for name in spans.SCOPES:
        assert f"/{name}/" in scoped, name
    monkeypatch.setattr(spans, "scope", lambda name: contextlib.nullcontext())
    bare = _optimized_hlo(cfg, pool, xs)
    assert not any(name in bare for name in spans.SCOPES)
    assert normalized(scoped) == normalized(bare)


def _v2lite_step_hlo():
    from repro.models import decode as D
    cfg = get_reduced("deepseek_v2_lite")
    scfg = ServeConfig(max_running=2, hot_window=8, attn_chunk=32)
    params = jax.eval_shape(lambda k: T.init_params(k, cfg)[0],
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: D.init_cache(cfg, scfg, 2, 64))
    vec = jax.ShapeDtypeStruct((2,), jnp.int32)
    jax.clear_caches()
    return jax.jit(lambda p, c, t, pos: D.decode_step(
        p, c, t, pos, cfg, scfg)).lower(params, cache, vec,
                                        vec).compile().as_text()


def test_serve_scopes_leave_the_decode_step_unchanged(monkeypatch):
    """The expert layer's and the latent attention's scopes in the decode
    step of an MLA + MoE model: present, and nothing else changes."""
    scoped = _v2lite_step_hlo()
    for name in spans.SERVE_SCOPES:
        assert f"/{name}/" in scoped, name
    monkeypatch.setattr(spans, "scope", lambda name: contextlib.nullcontext())
    bare = _v2lite_step_hlo()
    assert not any(name in bare for name in spans.SERVE_SCOPES)
    assert normalized(scoped) == normalized(bare)

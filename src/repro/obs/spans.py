"""Span and scope names the program emits on the profiler's clock.

This module is the one place where they are declared; each name carries
the per-layer metric it is for (``bench/metrics/``). The scopes are read
by ``bench/hlo_scopes.py`` (the replay window's, ``SCOPES``) and
``bench/decode_scopes.py`` (the serve model's, ``SERVE_SCOPES``); the
benchmark's trace reduction does not read the serve spans yet, so the
serve metrics named below have no reader.

* **Scopes** (``scope``) are ``jax.named_scope``s inside jitted code. They
  change only the HLO ``op_name`` metadata of the ops traced under them, so
  a device op in a profiler trace can be charged to its phase; the compiled
  program is the same with or without them (tests/test_spans.py).
* **Spans** (``span``) are host ``jax.profiler.TraceAnnotation``s. They are
  written only while a profiler is running, on the same clock as the
  device planes; off, one costs about a microsecond. Keyword arguments
  become the span's stats (``rid``, ``lane``, ``step``, ...), so that the
  spans of one request share its ``rid``; nesting records the span that
  caused a span.
"""
from __future__ import annotations

import jax

# -- device scopes: the replay window step's phases (core/engine/batch.py) --
TOPUP = "ibex.topup"          # replay.topup_us_per_access
CLASSIFY = "ibex.classify"    # replay.classify_us_per_access
MCACHE = "ibex.mcache"        # replay.mcache_us_per_access
DRAIN = "ibex.drain"          # replay.drain_us_per_access

SCOPES = (TOPUP, CLASSIFY, MCACHE, DRAIN)   # read by bench/hlo_scopes.py

# -- device scopes: the serve model's expert layer and latent attention -----
MOE_ROUTE = "moe.route"       # router, sort by expert and combine
MOE_EXPERTS = "moe.experts"   # serve.moe_roofline.v2lite (grouped products)
MOE_SHARED = "moe.shared"     # the shared experts
MLA_ATTEND = "mla.attend"     # serve.mla_attn_roofline.v2lite (decode step)

# read by bench/decode_scopes.py, for the decode-step program
SERVE_SCOPES = (MOE_ROUTE, MOE_EXPERTS, MOE_SHARED, MLA_ATTEND)

# -- host spans: the serve engine (serve/engine.py) --------------------------
STEP = "engine.step"          # root of every serve span path (span_s, span_n)
ADMIT = "engine.admit"        # parent of preempt, resume and prefill paths
PREFILL = "engine.prefill"    # span_programs of a bucket's prefill
PREEMPT = "engine.preempt"    # parent of park: a shadow hit parks nothing
PARK = "engine.park"          # serve.park_idle_share.backlog (no reader)
PARK_DEMOTE = "engine.park.demote"  # park's dispatch share of PARK
PARK_FETCH = "engine.park.fetch"    # park's blocking copy share of PARK
RESUME = "engine.resume"      # serve.resume_programs.backlog (no reader)
DECODE = "engine.decode"      # span_programs of the decode step
FETCH = "engine.fetch"        # idle under the step's one contracted fetch

SPANS = (STEP, ADMIT, PREFILL, PREEMPT, PARK, PARK_DEMOTE, PARK_FETCH,
         RESUME, DECODE, FETCH)


def span(name: str, **args):
    """A host span ``name`` with ``args`` as its stats (a context manager)."""
    return jax.profiler.TraceAnnotation(name, **args)


def scope(name: str):
    """A device scope ``name`` over the ops traced inside it."""
    return jax.named_scope(name)

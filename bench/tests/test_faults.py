"""The harness drives a whole run (the look for a chip skipped) with the
timed path broken underneath, and ``correct`` must come out false; and
unbroken, true. One case per fault the cell can have: a step that returns
its state unchanged, half of the batch left out, an answer altered where
it is produced. (One chip: no exchange between chips to leave out.)"""
import pytest

from small import run_small


def _replay_hook(kind):
    def hook(form):
        from repro.core.engine import batch as B

        def broken(pool, cfg, policy, ospns, writes, blocks, window=32):
            if kind == "unchanged":
                return pool
            if kind == "half":
                n = ospns.shape[0] // 2
                return B.replay_trace(pool, cfg, policy, ospns[:n],
                                      writes[:n], blocks[:n], window=window)
            out = B.replay_trace(pool, cfg, policy, ospns, writes, blocks,
                                 window=window)
            return out._replace(counters=out.counters.at[14].add(1))

        form.replay_trace = B.replay_trace   # population stays sound
        form_setup = form.setup

        def setup():
            form_setup()
            form.replay_trace = broken
        form.setup = setup
    return hook


def _serve_hook(kind):
    def hook(form):
        form_setup = form.setup

        def setup():
            form_setup()
            eng = form.eng
            step_fn = eng._step_fn

            def broken_step_fn(params, cache, state, **kw):
                cache2, state2, done = step_fn(params, cache, state, **kw)
                if kind == "unchanged":
                    return cache, dict(state2, tok=state["tok"]), done
                if kind == "half":
                    keep = (jnp.arange(state["tok"].shape[0]) % 2) == 0
                    return (cache2, dict(state2, tok=jnp.where(
                        keep, state2["tok"], state["tok"])), done)
                return cache2, dict(state2, tok=(state2["tok"] + 1)
                                    % form.mcfg.vocab_size), done

            import jax.numpy as jnp
            eng._step_fn = broken_step_fn
        form.setup = setup
    return hook


REPLAY = ["replay.ibex.mcf", "replay.ibex.parest"]


@pytest.mark.parametrize("cell", REPLAY)
@pytest.mark.parametrize("sets", [4, 128])
def test_replay_sound_run_is_correct(sets, cell):
    # 128 sets x 16 ways is the cell's cache: larger than this small pool,
    # so the clock's random fallback runs too
    out = run_small(cell, mcache_sets=sets)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell", REPLAY)
@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_replay_fault_is_not_correct(kind, cell):
    out = run_small(cell, form_hook=_replay_hook(kind))
    assert not out["correct"], out["checks"]


def test_serve_sound_run_is_correct():
    out = run_small("serve.deepseek_7b.backlog", seconds=3.0)
    assert out["correct"], out["checks"]
    assert out["checks"]["compiles_in_window"]["value"] == 0
    assert out["checks"]["kv_code_steps"]["value"] is not None


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_serve_fault_is_not_correct(kind):
    out = run_small("serve.deepseek_7b.backlog", seconds=3.0,
                    form_hook=_serve_hook(kind))
    assert not out["correct"], out["checks"]

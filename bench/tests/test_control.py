"""The controls, at a size a test run can hold: the reference in the
program's place with one stated guarantee broken (replay: shadowed
promotion in mcf, the metadata cache's ways in parest) or computed a precision lower (serve: fp8 linear layers) must
come out as not correct. The chip runs at the cells' own sizes are in
PERF.md."""
import pytest

import run
from small import run_small, small_cell


@pytest.mark.parametrize("cell", ["replay.ibex.mcf", "replay.ibex.parest"])
def test_replay_control_fails(cell):
    out = run_small(cell, control=True)
    assert out["checks"]["pool_diff"]["value"] > 0
    assert not out["correct"]


def test_serve_control_fails():
    # wide enough that the lower precision moves the argmax, and a window
    # long enough that the sample holds some hundreds of tokens
    cell = small_cell("serve.deepseek_7b.backlog", d_model=256)
    cell.mix.update(check_tokens=400, check_requests=24)
    # a limit set from readings at this size: sound runs read 6.5e-5 to
    # 4.3e-4 and the fp8 control 5.7e-3 to 9.3e-3 (CPU, nine seeds); the
    # cell's own limit is set from the chip's readings at the cell's size
    # (8 layers), where the control reads 0.24 and more
    cell.config["limits"]["mean_logit_gap"] = 0.003
    ctl = run.run_cell(cell, 6.0, False, require_chip=False, control=True)
    gap = ctl["checks"]["mean_logit_gap"]
    assert ctl["info"]["program_mean_gap"] < gap["limit"] < gap["value"]
    assert not ctl["correct"]


def test_serve_lost_code_slice_reads_above_the_limit():
    ctl = run_small("serve.deepseek_7b.backlog", seconds=3.0, control=True)
    limit = ctl["checks"]["kv_code_steps"]["limit"]
    assert ctl["info"]["program_kv_code_steps"] < limit
    assert ctl["info"]["fault_kv_code_steps"] > limit

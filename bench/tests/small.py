"""Cells of the benchmark cut to a size a CPU test run can hold: the same
forms, generators, references and checks, smaller pools and models."""
import json

import run

# cells by (configuration, traffic)
CELLS = {"replay.ibex.mcf": ("ibex_expander_128mib", "table2_mcf"),
         "replay.ibex.parest": ("ibex_expander_128mib", "table2_parest"),
         "serve.deepseek_7b.backlog": ("deepseek_7b", "serve_backlog")}


def small_cell(name: str, seed: int = 20240611, d_model: int = 128,
               mcache_sets: int = 4):
    run.check_program()
    config, traffic = CELLS[name]
    cell = run.Cell(
        name, 1,
        json.loads((run.BENCH / "configs" / f"{config}.json").read_text()),
        json.loads((run.BENCH / "traffic" / f"{traffic}.json").read_text()),
        seed, end_to_end=[], per_layer=[])
    if cell.config["form"] == "replay":
        cell.config["pool"].update(n_pages=512, n_pchunks=128,
                                   n_cchunks=8192,
                                   mcache_sets=mcache_sets)
        cell.mix.update(trace_accesses=16384, chunk=1024, check_chunks=2,
                        trace_seconds=1)
    else:
        heads = d_model // 64
        cell.config.update(hidden_size=d_model, intermediate_size=2 * d_model,
                           num_attention_heads=heads,
                           num_key_value_heads=heads,
                           vocab_size=512, num_hidden_layers=2)
        cell.config["serve"].update(max_running=4, max_len=256,
                                    hot_window=32, attn_chunk=128)
        p, o = cell.mix["prompt"], cell.mix["output"]
        p.update(lo=min(p["lo"], 40), hi=min(p["hi"], 120),
                 median=min(p["median"], 60))
        o.update(lo=4, hi=min(o["hi"], 24), median=10)
        cell.mix.update(trace_seconds=1, check_tokens=40, check_requests=2)
    return cell


def run_small(name: str, seconds: float = 2.0, d_model: int = 128,
              mcache_sets: int = 4, **kw) -> dict:
    return run.run_cell(small_cell(name, d_model=d_model,
                                   mcache_sets=mcache_sets),
                        seconds, False, require_chip=False, **kw)

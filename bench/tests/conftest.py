"""Small cells for the CPU: a cell of BENCHMARK.json with its depth and
its experts' width cut down (config and program alike) and its traffic
shrunk, so a whole run of ``bench/run.py`` fits in a minute.  The model
width stays as published: the gaps that decide ``correct`` grow with it,
and the tests hold the control and the faults to the cells' own limits."""
import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: the small sizes, per reference family: (config keys, program fields)
SMALL = {
    "moe_attention": (
        {"hidden_size": 1536, "num_hidden_layers": 4,
         "num_attention_heads": 24, "num_key_value_heads": 8,
         "intermediate_size": 64},
        {"n_layers": 4, "d_model": 1536, "n_heads": 24, "n_kv_heads": 8,
         "head_dim": 64, "moe_d_ff": 64, "d_ff": 64}),
}


def small_cell(workload: str, check_requests: int = 8):
    """(cell, program) for ``workload`` at the small size."""
    from bench import program as program_mod, spec as spec_mod
    from repro import configs

    cell = spec_mod.load(workload)
    keys, fields = SMALL[cell.config["family"]]
    cell.config = spec_mod.Spec(dict(cell.config, **keys))
    cfg = dataclasses.replace(configs.get(cell.config["program"]["arch"]),
                              **fields)
    batches = []
    for b in cell.traffic["batches"]:
        b = dict(b, S=b["S"] // 16)
        if "tokens" in b:
            b["tokens"] //= 32
        else:
            b["B"] = 4
        batches.append(b)
    cell.traffic = dict(cell.traffic, batches=batches,
                        gen=min(cell.traffic["gen"], 32),
                        check_requests=check_requests, pool_cycles=1)
    return cell, program_mod.load(cell.config, cfg=cfg)


@pytest.fixture
def small():
    return small_cell

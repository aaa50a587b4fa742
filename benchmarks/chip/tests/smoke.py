"""Small versions of the cells for the CPU tests: the configuration's
family at the program's smoke sizes, a cluster of the cell's layout with
small slots, reference kernels, and a short window."""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = {"family": "ssm", "n_layers": 2, "d_model": 128, "d_state": 16,
         "d_conv": 4, "expand": 2, "ssm_head_dim": 32, "n_groups": 1,
         "vocab_size": 512, "ssd_chunk": 16}


def overrides(cell_name: str, dtype: str = "bfloat16"):
    """(program config, cell override) for ``run.run_cell``."""
    import run
    from repro.configs import get_smoke_config
    cell = run.load_cell(cell_name)
    run_cfg = dict(SMALL, dtype=dtype, attn_impl="reference")
    cfg = get_smoke_config(cell.config["program"]).replace(dtype=dtype)
    mix = {"prompt": dict(cell.mix["prompt"], max=96),
           "output": dict(cell.mix["output"], max=24, median=12)}
    cc = {"rate": 1.0, "capacity": 128, "chunk_tokens": 64, "n_slots": 4,
          "drain_s": 30,
          "check": dict(cell.cell["check"], sample_tokens=10, max_requests=2)}
    return cfg, {"config": {"run": run_cfg}, "mix": mix, "cell": cc}

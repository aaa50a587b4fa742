"""What the harness knows of one program family, one file each:
``families/<family>.py``, found by the ``family`` of a configuration's
``run`` block, as ``run.py`` finds references and metric readers.

A family module gives

- ``layout(c)``: ``{path tuple: (shape, dtype name)}`` of the program's
  parameters for the run block ``c``;
- ``leaf(key, path, shape, dtype)``: the initial values of the family's own
  leaves, or ``None`` where ``weights.py``'s generic rules hold;
- ``widths(cfg)``: the program config's values under the run block's keys,
  which ``run.py`` compares with the run block;
- ``matmul_params(c)``, ``token_flops(c, ctx)``, ``prefill_flops(c,
  offset, length)``, and the ``(ops, bytes)`` of each kernel that the
  family runs, under the kernel's name, for ``flops.py`` and the metric
  readers.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

DIR = Path(__file__).resolve().parent
ROOT = DIR.parents[2]

_loaded: dict = {}


def load(name: str):
    """The module of family ``name``; exits naming the file to add where
    there is none."""
    path = DIR / f"{name}.py"
    mod = _loaded.get(path)
    if mod is None:
        if not path.is_file():
            shown = path.relative_to(ROOT) if path.is_relative_to(ROOT) else path
            raise SystemExit(f"no program family {name!r}: add {shown}")
        spec = importlib.util.spec_from_file_location(f"family_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return mod

"""Distribution-layer tests: sharding rules (pure), input specs, and one
subprocess dry-run on a small forced-device-count mesh (the full 16x16 and
2x16x16 sweeps run via launch/dryrun.py; results land in benchmarks/results)."""
import json
import os
import subprocess
import sys

import pytest

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.distributed.steps import batch_specs, cache_capacity, supports

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ----------------------------------------------------------- pure rules


def test_param_specs_divisible():
    """Every sharded dim in every arch's param specs divides the axis size."""
    import jax
    from repro.distributed.sharding import param_spec
    from repro.models import build_model
    msize = 16
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

        def walk(node, prefix=""):
            if isinstance(node, dict):
                for k, v in node.items():
                    walk(v, f"{prefix}{k}/")
                return
            spec = param_spec(prefix[:-1], node.shape, msize)
            for dim, s in zip(node.shape, spec):
                if s == "model":
                    assert dim % msize == 0, (arch, prefix, node.shape, spec)

        walk(shapes)


def test_vocab_padding_multiple_of_256():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        assert cfg.padded_vocab % 256 == 0
        assert cfg.padded_vocab >= cfg.vocab_size


def test_supported_matrix():
    """39 of 40 combos supported; whisper long_500k is the documented skip."""
    total = supported = 0
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in INPUT_SHAPES.values():
            total += 1
            supported += supports(cfg, shape)
    assert total == 40
    assert supported == 39
    assert not supports(get_config("whisper-medium"), INPUT_SHAPES["long_500k"])


def test_long_context_capacity_is_subquadratic():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        shape = INPUT_SHAPES["long_500k"]
        if not supports(cfg, shape):
            continue
        if cfg.family in ("ssm",):
            continue                       # O(1) state, no KV cache
        cap = cache_capacity(cfg, shape)
        assert cap <= 4096, (arch, cap)    # ring buffer, not 524288


def test_batch_specs_all_combos():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in INPUT_SHAPES.values():
            specs = batch_specs(cfg, shape)
            assert specs, (arch, shape.name)
            for v in specs.values():
                assert all(d > 0 for d in v.shape)


# ------------------------------------------------------ subprocess dry-run


@pytest.mark.parametrize("arch,shape", [("qwen3-1.7b", "decode_32k"),
                                        ("mamba2-370m", "train_4k")])
def test_dryrun_small_mesh_subprocess(arch, shape):
    """lower+compile on a forced 8-device (4x2) mesh inside a fresh process
    (device count must be set before jax initialises)."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import INPUT_SHAPES, get_config
from repro.distributed.steps import build_dryrun
mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = get_config({arch!r}).replace(n_layers=2)
if cfg.family == "hybrid":
    cfg = cfg.replace(n_layers=3)
shape = INPUT_SHAPES[{shape!r}]
with mesh:
    fn, args = build_dryrun(cfg, shape, mesh)
    compiled = fn.lower(*args).compile()
    c = compiled.cost_analysis()
    assert c.get("flops", 0) > 0
print("OK", c.get("flops"))
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_variant_numerics_match_baseline_subprocess():
    """§Perf variants (act_shard / seq_attn / kv_seq_shard) are sharding-only:
    outputs must be bit-comparable to the unconstrained baseline on a real
    8-device mesh."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import build_model

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_smoke_config("qwen3-1.7b").replace(dtype="float32")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
with mesh:
    base, _ = jax.jit(lambda p, b: model.prefill(p, b, cache_capacity=32))(
        params, {"tokens": tokens})
    cfg2 = cfg.replace(act_batch_axes=("data",), attn_seq_axis="model")
    model2 = build_model(cfg2)
    opt, _ = jax.jit(lambda p, b: model2.prefill(p, b, cache_capacity=32))(
        params, {"tokens": tokens})
np.testing.assert_allclose(np.asarray(base), np.asarray(opt), rtol=2e-5, atol=2e-5)
print("OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_dryrun_results_schema_if_present():
    """Validate any sweep records already produced by launch/dryrun.py."""
    from repro.launch.dryrun import RESULTS_DIR
    if not RESULTS_DIR.exists():
        pytest.skip("no dry-run records yet")
    files = list(RESULTS_DIR.glob("*.json"))
    if not files:
        pytest.skip("no dry-run records yet")
    for f in files:
        rec = json.loads(f.read_text())
        assert rec["status"] in ("ok", "skipped", "error"), f.name
        if rec["status"] == "ok":
            assert rec["flops"] > 0
            assert rec["memory"]["argument_bytes"] > 0
        assert rec["status"] != "error", (f.name, rec.get("error"))

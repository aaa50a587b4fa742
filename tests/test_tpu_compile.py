"""The Pallas kernels compile for a TPU v5e chip at the widths the engine
serves. The chip is described, not attached: the TPU compiler installed
with JAX compiles for it and refuses what the chip's compiler would refuse
(tiling alignment, unsupported primitives, VMEM limits). Interpret mode, in
which tests/test_kernels.py checks the numbers, catches none of that.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under several test
workers only the worker given this file should."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.flash_prefill.flash_prefill import flash_prefill_dyn
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rglru_scan.rglru_scan import rglru_scan
from repro.kernels.ssd_scan.ssd_scan import ssd_scan

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
# qwen3-1.7b attention widths
H, HK, D = 16, 8, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without the chip: keep the cache off
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; return the compiled HLO."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("S", [32, 512])
def test_flash_prefill_compiles(one_chip, S):
    text = compiled_text(
        lambda q, k, v: flash_prefill(q, k, v, bq=min(S, 128),
                                      bk=min(S, 128), interpret=False),
        one_chip, ((1, H, S, D), BF16), ((1, HK, S, D), BF16),
        ((1, HK, S, D), BF16))
    assert "tpu_custom_call" in text


def test_flash_prefill_dyn_compiles(one_chip):
    """A 256-token prefill chunk against a 2048-token slot."""
    text = compiled_text(
        lambda q, k, v, off: flash_prefill_dyn(q, k, v, off, interpret=False),
        one_chip, ((1, H, 256, D), BF16), ((1, HK, 2048, D), BF16),
        ((1, HK, 2048, D), BF16), ((), I32))
    assert "tpu_custom_call" in text


def test_paged_attention_compiles(one_chip):
    """The decode batch: 8 slots of 2048 tokens in 128-token pages."""
    B, C, page = 8, 2048, 128
    MP = C // page
    text = compiled_text(
        lambda q, k, v, pt, ln: paged_attention(q, k, v, pt, ln,
                                                interpret=False),
        one_chip, ((B, H, D), BF16), ((B * MP, page, HK, D), BF16),
        ((B * MP, page, HK, D), BF16), ((B, MP), I32), ((B,), I32))
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles(one_chip):
    """mamba2-370m: 32 heads of P=64, state N=128, chunk Q=128."""
    B, Hs, nc, Q, P, N = 1, 32, 4, 128, 64, 128
    text = compiled_text(
        lambda x, la, b, c, h0: ssd_scan(x, la, b, c, h0, interpret=False),
        one_chip, ((B, Hs, nc, Q, P), F32), ((B, Hs, nc, Q), F32),
        ((B, Hs, nc, Q, N), F32), ((B, Hs, nc, Q, N), F32),
        ((B, Hs, P, N), F32))
    assert "tpu_custom_call" in text


def test_rglru_scan_compiles(one_chip):
    """recurrentgemma-9b's RG-LRU width 4096."""
    B, S, W = 1, 512, 4096
    text = compiled_text(
        lambda la, g, h0: rglru_scan(la, g, h0, interpret=False),
        one_chip, ((B, S, W), F32), ((B, S, W), F32), ((B, W), F32))
    assert "tpu_custom_call" in text

"""The program family as a file of the benchmark: the Mamba-2 family's
weights, counts and widths as they were before they moved into
``families/ssm.py``; a toy family planted in another directory that the
weights, the counts and the width check reach with no edit to the
harness; the message for a family with no file; and the decode-state
transfer shapes that set-up warms, by the exported state's size.

    python -m pytest benchmarks/chip/tests
"""
from __future__ import annotations

import dataclasses
import json
import sys
import textwrap
import zlib
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE / "tests"))
sys.path.insert(0, str(HERE))

import families  # noqa: E402
import flops  # noqa: E402
import smoke  # noqa: E402
import weights  # noqa: E402

MAMBA = json.loads((HERE / "configs" / "mamba2-370m.json").read_text())["run"]


def leaves(tree, path=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from leaves(v, path + (k,))
    else:
        yield path, tree


# ------------------------------------------ Mamba-2, pinned on the parent

def test_ssm_layout_pinned():
    lay = weights.layout(MAMBA)
    assert len(lay) == 11
    assert sum(int(np.prod(s)) for s, _ in lay.values()) == 368_494_080


@pytest.mark.parametrize("count, want", [
    (lambda c: flops.matmul_params(c), 367_632_384),
    (lambda c: flops.token_flops(c, 100), 810_762_240),
    (lambda c: flops.prefill_flops(c, 128, 172), 139_451_105_280),
    (lambda c: flops.ssd_scan(c, 256), (22_624_075_776, 209_190_912)),
    (lambda c: flops.ssd_scan(c, 300), (25_422_594_048, 227_844_096)),
], ids=["matmul_params", "token_flops", "prefill_flops", "ssd_scan_256",
        "ssd_scan_300"])
def test_ssm_counts_pinned(count, want):
    assert count(MAMBA) == want


def test_ssm_weights_pinned():
    crc = 0
    made = weights.make(dict(smoke.SMALL, dtype="float32"), 7)
    for _, arr in sorted(leaves(made)):
        crc = zlib.crc32(np.asarray(arr).tobytes(), crc)
    assert crc == 2569737617


def test_flops_family_is_the_module():
    assert flops.family(MAMBA) is families.load("ssm")


# ------------------------------------------------- a family from new files

TOY = textwrap.dedent('''
    import jax.numpy as jnp


    def layout(c):
        return {("tok", "embed"): ((c["vocab_size"], c["d_model"]), c["dtype"]),
                ("w", "bias"): ((c["d_model"],), "float32")}


    def leaf(key, path, shape, dtype):
        if path[-1] == "bias":
            return jnp.full(shape, 0.5, jnp.float32)
        return None


    def widths(cfg):
        return {"d_model": cfg.d_model, "vocab_size": cfg.vocab_size}


    def matmul_params(c):
        return c["vocab_size"] * c["d_model"]


    def token_flops(c, ctx):
        return 2.0 * matmul_params(c) + ctx


    def prefill_flops(c, offset, length):
        return sum(token_flops(c, offset + i + 1) for i in range(length))
''')


@pytest.fixture
def toy(tmp_path, monkeypatch):
    (tmp_path / "toy.py").write_text(TOY)
    monkeypatch.setattr(families, "DIR", tmp_path)
    return {"family": "toy", "d_model": 16, "vocab_size": 40,
            "dtype": "bfloat16"}


@dataclasses.dataclass(frozen=True)
class ToyConfig:
    d_model: int
    vocab_size: int
    attn_impl: str = "xla"

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


def toy_cell(run_block):
    import run
    return run.Cell("toy.mix", {}, {"name": "toy.mix", "config": "toy"}, {},
                    {"program": "toy", "run": run_block}, {})


def test_toy_family_weights(toy):
    w = weights.make(toy, 3)
    assert sorted(p for p, _ in leaves(w)) == [("tok", "embed"), ("w", "bias")]
    emb = np.asarray(w["tok"]["embed"], np.float32)
    assert w["tok"]["embed"].dtype == np.dtype("bfloat16")
    assert emb.shape == (40, 16)
    assert 0.8 < emb.std() / (4 / 16 ** 0.5) < 1.25     # the generic rule
    np.testing.assert_array_equal(np.asarray(w["w"]["bias"]),
                                  np.full((16,), 0.5, np.float32))


def test_toy_family_counts(toy):
    assert flops.matmul_params(toy) == 640
    assert flops.token_flops(toy, 5) == 1285.0
    assert flops.prefill_flops(toy, 2, 2) == 1283.0 + 1284.0


def test_toy_family_width_check(toy, monkeypatch):
    import run
    import repro.configs
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda name: ToyConfig(d_model=16, vocab_size=40))
    cfg = run.program_config(toy_cell(dict(toy, attn_impl="pallas")))
    assert cfg == ToyConfig(16, 40, "pallas")
    with pytest.raises(SystemExit, match="is not the cell's"):
        run.program_config(toy_cell(dict(toy, d_model=32, attn_impl="pallas")))
    lacking = {k: v for k, v in toy.items() if k != "vocab_size"}
    with pytest.raises(SystemExit, match="lacks \\['vocab_size'\\]"):
        run.program_config(toy_cell(dict(lacking, attn_impl="pallas")))


@pytest.mark.parametrize("use", [
    lambda c: weights.make(c, 1),
    lambda c: flops.token_flops(c, 1),
], ids=["weights", "flops"])
def test_unknown_family_names_the_file(use):
    with pytest.raises(SystemExit,
                       match="add benchmarks/chip/families/hybrid.py"):
        use(dict(MAMBA, family="hybrid"))


# -------------------------------------------------- transfer shapes warmed

class Slots:
    """An instance's export side: a payload of ``per_token`` bytes per
    token of context on top of ``fixed`` bytes."""

    def __init__(self, fixed, per_token):
        self.kv = type("KV", (), {})()
        self.kv.len_of = {}
        self.last_token, self.generated, self.held = {}, {}, set()
        self.fixed, self.per_token = fixed, per_token
        self.exported = []

    def alloc_slot(self, rid):
        assert rid not in self.held
        self.held.add(rid)

    def export_state(self, rid):
        n = self.kv.len_of[rid]
        self.exported.append(n)
        payload = [np.zeros(self.fixed, np.uint8),
                   np.zeros(n * self.per_token, np.uint8)]
        return payload, n, self.last_token[rid], self.generated[rid]

    def drop(self, rid):
        self.held.discard(rid)
        self.kv.len_of.pop(rid, None)


class Importer:
    def __init__(self):
        self.imported, self.held = [], set()

    def import_state(self, rid, payload, L, last, gen, checksum=None):
        from repro.engine.instance import state_checksum
        assert checksum == state_checksum(payload)
        self.held.add(rid)
        self.imported.append((L, sum(p.nbytes for p in payload)))
        return True

    def drop(self, rid):
        self.held.discard(rid)


@pytest.mark.parametrize("per_token, imported, exported", [
    (0, [(32, 64)], [32, 512]),
    (8, [(32, 64 + 256), (96, 64 + 768), (512, 64 + 4096)], [32, 512, 96]),
], ids=["state_of_fixed_size", "state_grows_with_length"])
def test_transfer_shapes_by_size(per_token, imported, exported):
    import run
    src, dst = Slots(64, per_token), Importer()
    assert run.warm_transfers(src, dst, [32, 96, 512]) == len(imported)
    assert dst.imported == imported
    assert src.exported == exported
    assert not src.held and not dst.held

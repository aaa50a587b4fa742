"""The launcher's engine path and the chip smoke run's serving function, on
the CPU at the smoke config (Pallas kernels in interpret mode)."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core import Request
from repro.launch import serve
from repro.models import build_model

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config("qwen3-1.7b").replace(attn_impl="pallas")
    return cfg, build_model(cfg).init(jax.random.PRNGKey(0))


def test_chip_smoke_serving_phase_on_cpu(chip_smoke, smoke):
    cfg, params = smoke
    cluster, report, errors = chip_smoke.serve_phase(
        cfg, params, prompt_lens=(96, 96, 48, 48, 24, 24), out_len=4,
        capacity=128, timeout=300.0)
    assert errors == []
    assert report.n_finished == report.n_total == 6
    assert report.unfinished == []
    assert len(cluster.migration_log) >= 1
    assert all(m["bytes"] > 0 for m in cluster.migration_log)


def test_chip_smoke_logits_parity_on_cpu(chip_smoke, smoke):
    cfg, params = smoke
    res = chip_smoke.logits_parity(cfg, params, prompt_len=64)
    for rel, same in res.values():
        assert rel <= chip_smoke.LOGIT_TOL
        assert same


@pytest.mark.parametrize("lens,expect", [
    ([(100, 20), (30, 2)], 128),
    ([(129, 0)], 256),
    ([(1 << 20, 8)], 32768),          # capped at qwen3-1.7b's max_seq_len
])
def test_slot_capacity_follows_the_requests(lens, expect):
    trace = [Request(rid=i, arrival=0.0, input_len=a, output_len=b)
             for i, (a, b) in enumerate(lens)]
    assert serve.slot_capacity(trace, get_config("qwen3-1.7b")) == expect


@pytest.mark.parametrize("flags,expect", [
    ([], get_config("qwen3-1.7b")),
    (["--smoke"], get_smoke_config("qwen3-1.7b")),
])
def test_engine_mode_serves_published_widths_unless_smoke(monkeypatch, flags,
                                                          expect):
    seen = {}

    def fake_serve_engine(cfg, trace, **kw):
        seen["cfg"] = cfg
        raise SystemExit(0)

    monkeypatch.setattr(serve, "serve_engine", fake_serve_engine)
    with pytest.raises(SystemExit):
        serve.run_engine(serve.build_parser().parse_args(
            ["--mode", "engine", "--attn-impl", "pallas"] + flags))
    assert seen["cfg"] == expect.replace(attn_impl="pallas")


def test_launcher_fails_when_requests_are_left_unfinished(monkeypatch,
                                                          capsys):
    """A drain timeout far too short to finish must fail the run and name
    the unfinished rids, not print a partial report and exit 0."""
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    with pytest.raises(SystemExit) as exc:
        serve.main(["--mode", "engine", "--smoke", "--requests", "3",
                    "--timeout", "0.001"])
    assert exc.value.code != 0
    assert "unfinished: rids [0, 1, 2]" in str(exc.value.code)
    assert "finished 0/3" in capsys.readouterr().out

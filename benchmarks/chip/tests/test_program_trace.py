"""The readers of the program's own spans and stamps on hand-made event
lists, and one traced run at a small size on the CPU, where the stamps
and host spans give numbers and the reader that needs a device gives
none.

    python -m pytest benchmarks/chip/tests
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE / "tests"))
sys.path.insert(0, str(HERE))

import program_trace  # noqa: E402

HOST, DEV, DEV1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
PROGRAM_READERS = ("decode_rows_pct", "migrate_checksum_pct",
                   "idle_host_pct")
STAMP_READERS = ("prefill_queue_ms_mean", "decode_queue_ms_mean")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, start, dur, **args):
    return (HOST, "python3", name, start, dur, args)


def op(start, dur, name="%fusion.1 = f32[8] fusion(f32[8] %a)", plane=DEV):
    return (plane, "XLA Ops", name, start, dur, {})


def traced(events, t0=0.0, t1=1000e-9, reqs=()):
    """A run whose program trace is ``events``; its window is in seconds,
    the events in ns."""
    return SimpleNamespace(cell=SimpleNamespace(name="hand-made"), t0=t0,
                           t1=t1, reqs=list(reqs),
                           program_trace=program_trace.from_events(events))


# ---------------------------------------------------------------- idle

def test_idle_host_counts_nested_spans_once_and_leaves_out_device_time():
    ev = [span("arrow.pass", 0, 100),
          span("arrow.dispatch", 10, 30),          # nested in the pass
          span("arrow.migrate", 90, 60),           # overlaps the pass's end
          op(0, 5, name="%while.2 = (f32[2]) while(f32[2] %a)"),
          op(20, 10), op(50, 10),
          op(300, 10)]                             # a gap outside any span
    # host in a span over [0, 150]; the device busy 25 ns of it
    assert reader("idle_host_pct")(traced(ev)) == pytest.approx(12.5)


def test_idle_host_averages_devices():
    ev = [span("arrow.pass", 0, 100), op(0, 100), op(0, 50, plane=DEV1)]
    assert reader("idle_host_pct")(traced(ev)) == pytest.approx(2.5)


def test_minus_of_disjoint_and_covering_intervals():
    assert program_trace.minus([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert program_trace.minus([(0, 10)], [(-5, 15)]) == 0
    assert program_trace.minus([(0, 10)], []) == 10
    assert program_trace.minus([(0, 10), (2, 4)], [(20, 30)]) == 10


# ------------------------------------------------------------- migration

def test_migrate_checksum_pct_of_known_spans():
    ev = [span("arrow.migrate", 0, 200, rid=1, src=0, dst=2, bytes=64),
          span("arrow.migrate.land", 0, 20),
          span("arrow.migrate.export", 20, 40),
          span("arrow.migrate.checksum", 60, 40),
          span("arrow.migrate.import", 100, 90),
          span("arrow.migrate.checksum", 110, 40),  # verify at import
          span("arrow.migrate", 1000, 100, rid=2, src=1, dst=3, bytes=64),
          span("arrow.migrate.checksum", 1010, 20),
          span("arrow.migrate.checksum", 5000, 90)]  # outside any transfer
    # (40 + 40 + 20) over (200 + 100)
    got = reader("migrate_checksum_pct")(traced(ev))
    assert got == pytest.approx(100 * 100 / 300)


# ------------------------------------------------------------ model step

def test_decode_rows_pct_reads_steps_with_decode_rows():
    ev = [span("arrow.dispatch", 0, 5, iid=0),      # an empty plan
          span("arrow.dispatch", 10, 5, iid=0, step=1, rows=1, slots=8),
          span("arrow.dispatch", 20, 5, iid=1, step=1, rows=3, slots=8),
          span("arrow.dispatch", 30, 5, iid=2, step=1, rows=0, slots=8)]
    assert reader("decode_rows_pct")(traced(ev)) == pytest.approx(25.0)


# ------------------------------------------------------------ the stamps

def req(arrival, prefill, first, migrate=None):
    r = SimpleNamespace(arrival=arrival, prefill_start_time=prefill,
                        first_token_time=first, migrate_start_time=migrate)
    return SimpleNamespace(handle=SimpleNamespace(req=r))


def test_queue_readers_read_the_stamps():
    reqs = [req(1.0, 1.010, 1.1, 1.105), req(2.0, 2.030, 2.2),
            req(3.0, None, None),          # never started
            SimpleNamespace(handle=None)]  # never submitted
    run = traced([], reqs=reqs)
    assert reader("prefill_queue_ms_mean")(run) == pytest.approx(20.0)
    assert reader("decode_queue_ms_mean")(run) == pytest.approx(5.0)


# ------------------------------------------------ a program without them

@pytest.mark.parametrize("name", PROGRAM_READERS + STAMP_READERS)
def test_nothing_to_read_gives_none(name):
    plain = SimpleNamespace(handle=SimpleNamespace(req=SimpleNamespace(
        arrival=0.0, first_token_time=0.1)))
    ev = [span("bench.dispatch", 0, 50), op(0, 10)]
    assert reader(name)(traced(ev, reqs=[plain])) is None


def test_no_trace_gives_none(tmp_path, monkeypatch):
    monkeypatch.setattr(program_trace, "ROOT", tmp_path)
    run = SimpleNamespace(cell=SimpleNamespace(name="absent"), t0=0.0,
                          t1=1.0, reqs=[])
    assert all(reader(n)(run) is None for n in PROGRAM_READERS)


# ---------------------------------------------- a traced run on the CPU

def test_traced_rehearsal_reads_stamps_and_no_device():
    import run
    import smoke
    cell = run.load_cell("mamba2-370m.chat-burst").name
    cfg, ov = smoke.overrides(cell)
    res = run.run_cell(["--workload", cell, "--seed", "3141592653",
                        "--seconds", "8", "--trace", "1"], allow_cpu=True,
                       cfg_override=cfg, cell_override=ov)
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    for name in STAMP_READERS + ("decode_rows_pct", "migrate_checksum_pct"):
        assert m[name]["value"] >= 0, name
    assert 0 < m["decode_rows_pct"]["value"] <= 100
    # the CPU run has no device plane
    assert "idle_host_pct" not in m
    tr = res["run"].program_trace
    assert tr.named("arrow.migrate") and not tr.devices

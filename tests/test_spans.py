"""The engine's own measurement: host spans written into the profiler's
trace, the ``sample`` scope on the sampler's device ops, and the queue
stamps on each request.

Spans are ``jax.profiler.TraceAnnotation``s, so they exist only while a
profiler runs; these tests run a small cluster (two instances, one per
pool, so every request migrates) under ``jax.profiler.trace`` and read the
``.xplane.pb`` it writes. Everything asserted is a name, an argument, a
nesting or an order, never a duration.
"""
import re
import warnings
from dataclasses import dataclass
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core import Request, SLO
from repro.core.serving import replay_trace
from repro.engine import ArrowEngineCluster
from repro.engine import fused_step as fs
from repro.models import build_model
from repro.sim import Simulator
from repro.traces import TRACE_PRESETS, load_trace

DRAIN_TIMEOUT = 300.0

# span -> (the span it nests in, the arguments it carries)
SPANS = {
    "arrow.pass": (None, {"inflight"}),
    "arrow.arrivals": ("arrow.pass", {"n"}),
    "arrow.dispatch": ("arrow.pass", {"iid"}),
    "arrow.dispatch.plan": ("arrow.dispatch", set()),
    "arrow.dispatch.launch": ("arrow.dispatch", set()),
    "arrow.fetch": ("arrow.pass", {"iid", "step"}),
    "arrow.fetch.wait": ("arrow.fetch", set()),
    "arrow.fetch.emit": ("arrow.fetch", set()),
    "arrow.block": ("arrow.pass", {"iid"}),
    "arrow.tick": ("arrow.pass", set()),
    "arrow.migrate": ("arrow.pass", {"rid", "src", "dst", "bytes"}),
    "arrow.migrate.land": ("arrow.migrate", set()),
    "arrow.migrate.export": ("arrow.migrate", set()),
    "arrow.migrate.checksum": ("arrow.migrate", set()),
    "arrow.migrate.import": ("arrow.migrate", set()),
}
# what a dispatch that launched a step adds to its ``iid``
LAUNCH_ARGS = {"step", "rows", "slots", "chunks", "chunk_tokens", "entry"}


@dataclass
class Span:
    name: str
    thread: str
    start: float
    end: float
    args: dict

    def inside(self, other: "Span") -> bool:
        return (self.thread == other.thread and other.start <= self.start
                and self.end <= other.end)


def host_spans(profile_dir) -> list:
    from jax.profiler import ProfileData
    path = sorted(Path(profile_dir).rglob("*.xplane.pb"))[-1]
    out = []
    with warnings.catch_warnings():
        # the reader's stat type warns that it has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        data = ProfileData.from_file(str(path))
        for plane in data.planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("arrow."):
                        out.append(Span(e.name, f"{plane.name}/{line.name}",
                                        e.start_ns, e.start_ns + e.duration_ns,
                                        dict(e.stats)))
    return out


def serve(cfg, params):
    """Four greedy requests through a 1 prefill + 1 decode cluster; returns
    the handles and the cluster."""
    cluster = ArrowEngineCluster(cfg, n_instances=2, n_prefill=1, n_slots=4,
                                 capacity=128, slo=SLO(5.0, 2.0),
                                 params=params, chunk_tokens=16)
    rng = np.random.default_rng(11)
    handles = [cluster.submit(
        Request(rid=i, arrival=0.02 * i, input_len=24, output_len=6),
        prompt=rng.integers(1, cfg.vocab_size, size=24).astype(np.int32))
        for i in range(4)]
    report = cluster.drain(timeout=DRAIN_TIMEOUT)
    assert report.n_finished == 4
    return handles, cluster


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The same requests served with the profiler off, then on."""
    cfg = get_smoke_config("qwen3-1.7b")
    params = build_model(cfg).init(jax.random.PRNGKey(7))
    plain, _ = serve(cfg, params)
    d = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(d)):
        traced, cluster = serve(cfg, params)
    assert cluster.migration_log, "the layout must migrate every request"
    return plain, traced, host_spans(d)


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_carries_its_args_inside_its_parent(runs, name):
    _, _, spans = runs
    parent, args = SPANS[name]
    mine = [s for s in spans if s.name == name]
    assert mine, f"no {name} span in the trace"
    for s in mine:
        assert args <= set(s.args), (name, s.args)
        if parent is not None:
            assert any(s.inside(p) for p in spans if p.name == parent), \
                f"{name} at {s.start} lies outside every {parent}"


def test_dispatch_and_fetch_of_a_step_share_iid_and_step(runs):
    _, _, spans = runs
    launched = [s for s in spans
                if s.name == "arrow.dispatch" and "step" in s.args]
    assert launched
    for s in launched:
        assert LAUNCH_ARGS <= set(s.args), s.args
        assert s.args["entry"] in ("mixed_step", "decode_only", "chunks_only")
        assert 0 <= s.args["rows"] <= s.args["slots"] == 4
    dispatched = {(s.args["iid"], s.args["step"]) for s in launched}
    fetched = {(s.args["iid"], s.args["step"]) for s in spans
               if s.name == "arrow.fetch"}
    assert dispatched == fetched
    assert len(dispatched) == len(launched)       # a step number is unique


def test_each_migration_checksums_at_export_and_inside_import(runs):
    _, _, spans = runs
    migrations = [s for s in spans if s.name == "arrow.migrate"]
    imports = [s for s in spans if s.name == "arrow.migrate.import"]
    for m in migrations:
        sums = [s for s in spans
                if s.name == "arrow.migrate.checksum" and s.inside(m)]
        inside_import = [s for s in sums if any(s.inside(i) for i in imports)]
        assert len(sums) == 2 and len(inside_import) == 1
        assert m.args["bytes"] > 0 and m.args["src"] != m.args["dst"]


def test_stamps_in_order_on_the_engine(runs):
    _, traced, _ = runs
    for h in traced:
        r = h.req
        assert r.migrate_start_time is not None, r.rid
        assert (r.arrival <= r.prefill_start_time <= r.first_token_time
                <= r.migrate_start_time <= r.decode_start_time
                <= r.finish_time), r


def test_stamps_in_order_on_the_simulator():
    trace = load_trace("azure_code", rate_scale=2.0, seed=0, duration=20)
    p = TRACE_PRESETS["azure_code"]
    sim = Simulator(get_config("gemma-2b"), n_instances=4, n_prefill=2,
                    policy="arrow", slo=SLO(p.slo_ttft, p.slo_tpot))
    handles = replay_trace(sim, trace)
    sim.drain()
    migrated = [h.req for h in handles if h.req.migrate_start_time is not None]
    assert migrated
    for r in migrated:
        assert (r.arrival <= r.prefill_start_time <= r.first_token_time
                <= r.migrate_start_time <= r.decode_start_time), r
    for h in handles:
        assert h.req.prefill_start_time is not None, h.req.rid


def test_greedy_streams_equal_with_the_profiler_on(runs):
    plain, traced, _ = runs
    assert [h.tokens for h in plain] == [h.tokens for h in traced]
    assert all(len(h.tokens) == 6 for h in traced)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mamba2-370m",
                                  "recurrentgemma-9b"])
def test_fused_step_names_the_sample_scope(arch):
    cfg = get_smoke_config(arch)
    from repro.engine.state_slots import make_state_slots
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    slabs = make_state_slots(cfg, 2, 64).slabs()
    B, N, S = 2, 1, 32
    i32, f32 = np.int32, np.float32
    dec = (np.zeros((B, 1), i32), np.zeros((B,), i32), np.zeros((B,), f32),
           np.ones((B,), f32), np.zeros((B,), i32), np.zeros((B,), i32))
    if cfg.family != "dense":
        dec += (np.ones((B,), bool),)
    chunk = (np.zeros((N, S), i32), np.zeros((N,), i32), np.zeros((N,), i32),
             np.full((N,), S, i32), np.zeros((N,), f32), np.ones((N,), f32),
             np.zeros((N,), i32), np.zeros((N,), i32))
    op = fs.ops_for(cfg.family).mixed_step
    text = op.lower(cfg, params, *slabs, *dec, *chunk).as_text(
        debug_info=True)
    # the decode rows reach the sampler through vmap, the chunks through
    # the scan's body, whose locations nest under the loop's
    assert re.search(r"vmap\(sample\)/", text)
    assert re.search(r'["/]sample/', text)

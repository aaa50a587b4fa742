"""Serve one benchmark cell on the chip and print its result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``'s ``workloads``) is a configuration
(``configs/<name>.json``) under a traffic mix (``traffic/<name>.json``),
served by the cluster that ``cells/<cell>.json`` lays out. The harness
reads those files by name; a per-layer metric is read by
``metrics/<metric name>.py``, and what it knows of the configuration's
program family by ``families/<family>.py``. Nothing here is specific to
one cell or one family.

One run:

1. set-up: weights made on the device from the seed; the Arrow cluster
   (``repro.engine.ArrowEngineCluster``) built with them; every program
   shape that the cell's traffic can reach run once;
2. the window: the seeded requests are submitted through the
   ``ServingSystem`` API as they fall due, and ``step()`` drives the
   cluster, for ``--seconds``; with ``--trace 1`` under the profiler;
3. after the window: the requests due in it are served to their end (the
   drain), so that every one has its latencies;
4. the check: the program's state is freed, the weights are made again
   from the seed, and the plain reference (``references/<family>.py``)
   scores every served token of the finished greedy requests.

Standard error gets the set-up split, the schedule, the window's counts
and, as its last lines, each compared number beside its limit. The last
line of standard output is the result object. With no TPU, or fewer chips
than the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

T_PROCESS = time.perf_counter()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

import families  # noqa: E402
import traffic  # noqa: E402

NO_CHIP = 3


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise SystemExit(f"missing {path}")
    return json.loads(path.read_text())


def percentile(vals, q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default); None when empty."""
    return float(np.percentile(np.asarray(vals, float), q)) if len(vals) else None


class Frozen(dict):
    """A dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


# ----------------------------------------------------------------- the cell

@dataclass
class Cell:
    name: str
    bench: dict
    spec: dict           # BENCHMARK.json's workloads entry
    cell: dict           # cells/<name>.json
    config: dict         # configs/<config>.json
    mix: dict            # traffic/<traffic>.json

    @property
    def run_cfg(self) -> dict:
        return self.config["run"]

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[dict]:
        names = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in names)]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = read_json(bench_path)
    spec = next((w for w in bench["workloads"] if w["name"] == name), None)
    if spec is None:
        raise SystemExit(f"no workload {name!r} in {bench_path}")
    return Cell(name, bench, spec,
                read_json(HERE / "cells" / f"{name}.json"),
                read_json(HERE / "configs" / f"{spec['config']}.json"),
                traffic.load_mix(spec["traffic"]))


# ------------------------------------------------------------- device check

def check_device(chips: int, allow_cpu: bool):
    import jax
    devs = jax.devices()
    if not allow_cpu and (devs[0].platform != "tpu" or len(devs) < chips):
        log(f"needs {chips} TPU chip(s); JAX finds {len(devs)} "
            f"{devs[0].platform} device(s): no result")
        sys.exit(NO_CHIP)
    return devs


def enable_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program cached,
    however quick its compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileLog:
    """JAX's tracing, lowering, compiling and cache loads, by phase."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile",
              "/jax/compilation_cache/cache_retrieval_time_sec": "load"}

    def __init__(self):
        import jax
        self.secs: Dict[str, float] = {}
        self.window: List[str] = []
        self.in_window = False

        def on_duration(event, secs, **kw):
            kind = self.EVENTS.get(event)
            if kind is None:
                return
            self.secs[kind] = self.secs.get(kind, 0.0) + secs
            if self.in_window and kind in ("compile", "load", "lower"):
                self.window.append(f"{kind}:{kw.get('fun_name', '?')}")

        jax.monitoring.register_event_duration_secs_listener(on_duration)


# ------------------------------------------------------------------ records

@dataclass
class Step:
    iid: int
    t0: float                 # dispatch start (cluster clock)
    t1: float                 # dispatch end
    decode_ctx: List[int]     # live context of each active decode row
    chunks: List[tuple]       # (offset, length) of each prefill chunk
    t_ready: Optional[float] = None   # finalize end


@dataclass
class ReqLog:
    req: "traffic.Req"
    due: float                # cluster clock
    submitted: float = math.nan
    first: Optional[float] = None
    last: Optional[float] = None
    tokens: List[int] = field(default_factory=list)
    handle: object = None

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.req.out_len


@dataclass
class Run:
    """Everything a per-layer metric reader may read."""

    cell: Cell
    t0: float
    t1: float
    reqs: List[ReqLog]
    steps: List[Step]
    migrations: List[tuple]   # (t0, t1, bytes)
    flips: int
    peak: dict
    trace: Optional[dict] = None

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    @property
    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.in_window(s.t0)]


class Probe:
    """The benchmark's spans around the cluster's calls into each layer:
    ``bench.dispatch`` (plan and launch of one instance's fused step),
    ``bench.finalize`` (its token fetch and host bookkeeping) and
    ``bench.migrate`` (one decode-state transfer). With tracing on each is
    also a profiler annotation, so the trace can name idle gaps."""

    def __init__(self, cluster, annotate: bool):
        import jax
        self.cluster = cluster
        self.steps: List[Step] = []
        self.migrations: List[tuple] = []
        self.refused = 0          # transfers begun and turned back
        self._open: Dict[int, Step] = {}
        self.annotate = annotate
        self._ann = jax.profiler.TraceAnnotation
        clock = cluster.clock.now
        dispatch = cluster._dispatch_instance
        finalize = cluster._finalize_instance_step
        transfer = cluster._begin_transfer

        def span(name, fn, *a):
            if not self.annotate:
                return fn(*a)
            with self._ann(name):
                return fn(*a)

        def on_dispatch(iid, inst):
            t0 = clock()
            ctx = span("bench.dispatch", dispatch, iid, inst)
            if ctx is not None:
                pending, chunks = ctx[0], ctx[1]
                dec = [inst.kv.len_of[r] + 1 for r in pending.decode_rids]
                st = Step(iid, t0, clock(), dec,
                          [(c.offset, c.length) for c in chunks])
                self.steps.append(st)
                self._open[id(pending)] = st
            return ctx

        def on_finalize(iid, inst, ctx):
            out = span("bench.finalize", finalize, iid, inst, ctx)
            st = self._open.pop(id(ctx[0]), None)
            if st is not None:
                st.t_ready = clock()
            return out

        def on_transfer(rid, dst, kv, rem):
            t0 = clock()
            n = len(cluster.migration_log)
            ok = span("bench.migrate", transfer, rid, dst, kv, rem)
            moved = sum(m["bytes"] for m in cluster.migration_log[n:])
            if moved:
                self.migrations.append((t0, clock(), moved))
            elif not ok:
                self.refused += 1
            return ok

        cluster._dispatch_instance = on_dispatch
        cluster._finalize_instance_step = on_finalize
        cluster._begin_transfer = on_transfer


# ------------------------------------------------------------------- set-up

def program_config(cell: Cell, override=None):
    from repro.configs import get_config
    c = cell.run_cfg
    cfg = override if override is not None else get_config(cell.config["program"])
    cfg = cfg.replace(attn_impl=c["attn_impl"])
    if override is None:
        got = families.load(c["family"]).widths(cfg)
        lacking = sorted(set(got) - set(c))
        if lacking:
            raise SystemExit(f"the run block of {cell.spec['config']!r} lacks "
                             f"{lacking}, which its family compares")
        want = {k: c[k] for k in got}
        if got != want:
            raise SystemExit(f"program config {got} is not the cell's {want}")
    return cfg


def check_layout(cfg, params):
    """The benchmark's weight layout must be the program's, leaf by leaf."""
    import jax
    from repro.models import build_model
    want = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    a = {jax.tree_util.keystr(k): (v.shape, str(v.dtype))
         for k, v in jax.tree_util.tree_leaves_with_path(want)}
    b = {jax.tree_util.keystr(k): (v.shape, str(v.dtype))
         for k, v in jax.tree_util.tree_leaves_with_path(params)}
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))[:6]
        raise SystemExit(f"weight layout differs from the program's: {diff}")


def chunk_shapes(budget: int, n_slots: int):
    """Every (width, count) group of prefill chunks that one fused step can
    carry when at most ``budget`` tokens are planned per step: widths are
    32-token buckets, a group of width W holds chunks of W-31..W tokens
    (1..32 for the first), and each chunk holds a slot. Returns the set of
    (entry, width, count) programs and the set of the step's token-array
    concatenations (part lengths), which several groups make."""
    widths = list(range(32, budget + 1, 32))
    least = {w: 1 if w == 32 else w - 31 for w in widths}
    programs, concats = set(), set()

    def grow(groups, toks, n):
        if groups:
            for dec in (False, True):
                if n > n_slots - dec:
                    continue
                w0, n0 = groups[0]
                programs.add(("mixed" if dec else "chunks", w0, n0))
                for w, k in groups[1:]:
                    programs.add(("chunks", w, k))
                if len(groups) > 1:
                    concats.add((n0 + (n_slots if dec else 0),)
                                + tuple(k for _, k in groups[1:]))
        for w in widths:
            if any(w == g[0] for g in groups):
                continue
            for k in range(1, n_slots + 1):
                if toks + k * least[w] > budget or n + k > n_slots:
                    break
                grow(groups + [(w, k)], toks + k * least[w], n + k)

    grow([], 0, 0)
    return programs, concats


def drive_programs(inst, programs, n_slots: int) -> None:
    """Dispatch and finalize, on slots that are then released, one fused
    step of each (entry, width, count) in ``programs`` and one decode-only
    step, through the instance's own ``dispatch_step``."""
    from repro.core import SamplingParams
    from repro.engine.instance import ChunkWork
    samp = SamplingParams(temperature=0.8, top_p=0.95)
    fake = [-1000]

    def take(decoding: bool) -> int:
        fake[0] -= 1
        inst.alloc_slot(fake[0])
        if decoding:
            inst.kv.len_of[fake[0]] = 8
            inst.last_token[fake[0]] = 1
            inst.generated[fake[0]] = [1]
        else:
            inst.set_sampling(fake[0], samp)
        return fake[0]

    for entry, width, count in sorted(programs) + [("decode", 0, 0)]:
        dec = [take(True)] if entry in ("mixed", "decode") else []
        rids = [take(False) for _ in range(count)]
        toks = np.ones((width,), np.int32)
        chunks = [ChunkWork(r, 0, width, toks, width) for r in rids]
        inst.finalize_step(inst.dispatch_step(dec, chunks))
        for r in rids + dec:
            inst.drop(r)


def precompile(inst, programs, concats, n_slots: int,
               workers: int = max(2, (os.cpu_count() or 4) - 1)):
    """Compile the fused-step programs and the token-array concatenations
    in parallel threads, into JAX's persistent cache, so that ``warm_up``
    only loads them. The step calls are first recorded, not run: the
    instance's entry points are swapped for stand-ins that keep the
    arguments and hand back the state unchanged."""
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor
    from types import SimpleNamespace
    real = inst._ops
    n_state = len(inst.kv.slabs())
    calls = []

    def stand_in(name):
        fn = getattr(real, name)

        def record(cfg, params, *args):
            calls.append((fn, cfg, params, args))
            n = n_slots if name != "chunks_only" else 0
            if name != "decode_only":
                n += args[-8].shape[0]          # the chunks' token rows
            return (jnp.zeros((n,), jnp.int32),) + tuple(args[:n_state])
        return record

    inst._ops = SimpleNamespace(**{k: stand_in(k) for k in
                                   ("decode_only", "chunks_only", "mixed_step")})
    try:
        drive_programs(inst, programs, n_slots)
    finally:
        inst._ops = real

    def compile_step(c):
        fn, cfg, params, args = c
        fn.lower(cfg, params, *args).compile()

    def compile_concat(parts):
        np.asarray(jnp.concatenate([jnp.zeros((n,), jnp.int32) for n in parts]))

    with ThreadPoolExecutor(workers) as ex:
        jobs = [ex.submit(compile_step, c) for c in calls]
        jobs += [ex.submit(compile_concat, p) for p in sorted(concats)]
        for j in jobs:
            j.result()


def warm_up(cluster, cell: Cell, prompt_lens, clog: "CompileLog"):
    """Run once every program the window can reach, through the instances'
    own step and transfer calls, on slots that are then released: the
    fused step at each chunk group shape, with and without a decode batch;
    the decode-only step; the step's token-array concatenations; and a
    decode-state transfer at each prompt length's bucket."""
    import jax.numpy as jnp
    cc = cell.cell
    a, b = cluster.instances[0], cluster.instances[1]
    n_slots = cc["n_slots"]
    programs, concats = chunk_shapes(cc["chunk_tokens"], n_slots)
    t, c0 = time.perf_counter(), dict(clog.secs)
    precompile(a, programs, concats, n_slots)
    t1, c1 = time.perf_counter(), dict(clog.secs)
    drive_programs(a, programs, n_slots)
    c2 = dict(clog.secs)

    def spent(x, y):
        return {k: round(y.get(k, 0.0) - x.get(k, 0.0), 3) for k in y}
    log(f"precompile: {t1 - t:.1f} s wall, {spent(c0, c1)}; then the "
        f"programs run once: {time.perf_counter() - t1:.1f} s wall, "
        f"{spent(c1, c2)}")
    for parts in sorted(concats):
        np.asarray(jnp.concatenate([jnp.zeros((n,), jnp.int32) for n in parts]))
    lens = sorted({min(-(-n // 32) * 32, cc["capacity"]) for n in prompt_lens})
    return len(programs) + 1, len(concats), warm_transfers(a, b, lens)


def warm_transfers(src, dst, lens: List[int]) -> int:
    """Move a fake slot from ``src`` to ``dst`` through the instances' own
    export and import at each length in ``lens``, or at the first alone
    where the exported state has the same size at the first and the last
    length: a state that does not grow with the context has one transfer
    shape. Returns the number of shapes moved."""
    from repro.engine.instance import state_checksum
    fake = -1

    def export(n):
        src.alloc_slot(fake)
        src.kv.len_of[fake] = n
        src.last_token[fake] = 1
        src.generated[fake] = [1]
        out = src.export_state(fake)
        src.drop(fake)
        return out

    def nbytes(exported):
        return sum(p.nbytes for p in exported[0])

    ends = {lens[0]: export(lens[0]), lens[-1]: export(lens[-1])}
    if nbytes(ends[lens[0]]) == nbytes(ends[lens[-1]]):
        lens = lens[:1]
    for n in lens:
        payload, L, last, gen = ends.pop(n, None) or export(n)
        dst.import_state(fake, payload, L, last, gen,
                         checksum=state_checksum(payload))
        dst.drop(fake)
    return len(lens)


# ------------------------------------------------------------------- window

def serve(cluster, reqs: List["traffic.Req"], cell: Cell, seconds: float,
          probe: Probe, trace_dir: Optional[str], drain_s: float):
    """Drive the window; return (t0, t1, logs, flips in window, trace events
    or None)."""
    import jax
    from repro.core import Request, SamplingParams
    s = cell.mix["sampling"]
    samp = SamplingParams(temperature=s["temperature"], top_p=s["top_p"])
    logs: List[ReqLog] = []

    def on_token(handle, tok, t):
        lg = logs[handle.rid]
        if lg.first is None:
            lg.first = t
        lg.last = t
        lg.tokens.append(int(tok))

    clock = cluster.clock
    flips0 = cluster.flip_counts()["total"]
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t0 = clock.now() + 0.05
    t1 = t0 + seconds
    for r in reqs:
        logs.append(ReqLog(r, t0 + r.due_s))
    nxt = 0
    ann = jax.profiler.TraceAnnotation
    while True:
        now = clock.now()
        if now >= t1:
            break
        while nxt < len(logs) and logs[nxt].due <= now:
            lg = logs[nxt]
            r = lg.req
            req = Request(rid=nxt, arrival=lg.due, input_len=len(r.prompt),
                          output_len=r.out_len,
                          sampling=None if r.greedy else samp)
            if trace_dir:
                with ann("bench.submit"):
                    lg.handle = cluster.submit(req, prompt=r.prompt,
                                               on_token=on_token)
            else:
                lg.handle = cluster.submit(req, prompt=r.prompt,
                                           on_token=on_token)
            lg.submitted = clock.now()
            nxt += 1
        busy = cluster.step()
        if not busy:
            due = logs[nxt].due if nxt < len(logs) else t1
            wait = max(0.0, min(due, t1) - clock.now())
            if trace_dir:
                with ann("bench.wait"):
                    time.sleep(wait)
            else:
                time.sleep(wait)
    for iid in list(cluster._inflight):
        cluster._finalize_now(iid)
    flips = cluster.flip_counts()["total"] - flips0
    events = None
    if trace_dir:
        jax.profiler.stop_trace()
        import trace_reduce
        events = trace_reduce.load(trace_dir)
    # after the close: every request due in the window is served to its end
    stop = clock.now() + drain_s
    while clock.now() < stop and not all(lg.done for lg in logs):
        if not cluster.step():
            time.sleep(1e-3)
    for iid in list(cluster._inflight):
        cluster._finalize_now(iid)
    return t0, t1, logs, flips, events


# -------------------------------------------------------------- end to end

def end_to_end(run: Run, setup_s: float) -> Dict[str, float]:
    """``tpot_p50_ms``: the median, over the requests due in the window, of
    each one's time per output token, (last token - first token) / (tokens
    - 1); ``setup_s``. Logs the tails and the token-weighted mean beside
    them."""
    done = [lg for lg in run.reqs if lg.done and len(lg.tokens) > 1]
    tpot = [(lg.last - lg.first) / (len(lg.tokens) - 1) * 1e3 for lg in done]
    ttft = [(lg.first if lg.first is not None else run.t1) - lg.due
            for lg in run.reqs]
    span = sum(lg.last - lg.first for lg in done)
    steps = sum(len(lg.tokens) - 1 for lg in done)
    log(f"latencies: ttft p50 {percentile(ttft, 50)} p90 {percentile(ttft, 90)}"
        f" s; per-request tpot p50 {percentile(tpot, 50)} p90 "
        f"{percentile(tpot, 90)} ms; {steps} decode steps over {span:.3f} s, "
        f"{1e3 * span / max(steps, 1)} ms each")
    return {"tpot_p50_ms": percentile(tpot, 50), "setup_s": setup_s}


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no reader for per-layer metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------- check

def sample(logs: List[ReqLog], cell: Cell) -> List[ReqLog]:
    """The finished greedy requests, the longest answers first, as many as
    the reference's batch holds (``max_requests``)."""
    pool = [lg for lg in logs if lg.req.greedy and lg.done]
    pool.sort(key=lambda lg: (-lg.req.out_len, lg.req.idx))
    return pool[:cell.cell["check"]["max_requests"]]


def ref_len(cell: Cell) -> int:
    most = cell.mix["prompt"]["max"] + cell.mix["output"]["max"]
    return -(-most // 256) * 256


def reference_gaps(cell: Cell, seed: int, picked: List[ReqLog],
                   control: bool = False):
    """(widest gap of a served token, widest gap of the int8 control's
    first choice at the same positions or nan, tokens compared) against the
    plain reference."""
    import jax.numpy as jnp
    import weights
    fam = cell.config["reference"]
    spec = importlib.util.spec_from_file_location(
        f"references.{fam}", HERE / "references" / f"{fam}.py")
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    c = Frozen(cell.run_cfg)
    B, T = cell.cell["check"]["max_requests"], ref_len(cell)
    toks = np.zeros((B, T), np.int32)
    spans = []
    for i, lg in enumerate(picked):
        seq = np.concatenate([lg.req.prompt, np.asarray(lg.tokens, np.int32)])
        toks[i, :len(seq)] = seq[:T]
        spans.append((i, len(lg.req.prompt) - 1, len(lg.tokens)))
    targets = np.zeros((B, T), np.int32)
    targets[:, :-1] = toks[:, 1:]
    params = weights.make(cell.run_cfg, seed)
    gp, gc_ = ref.run(c, params, jnp.asarray(toks), jnp.asarray(targets),
                      control)
    gp, gc_ = np.asarray(gp), np.asarray(gc_)
    del params
    worst = ctl = 0.0
    n = 0
    for i, a, m in spans:
        worst = max(worst, float(gp[i, a:a + m].max()))
        ctl = max(ctl, float(gc_[i, a:a + m].max()))
        n += m
    return worst, ctl if control else math.nan, n


# -------------------------------------------------------------------- main

def run_cell(argv=None, *, allow_cpu: bool = False, cfg_override=None,
             cell_override: Optional[dict] = None, fault=None,
             control: bool = False):
    """One run. ``allow_cpu``, ``cfg_override`` and ``cell_override`` serve
    the CPU tests (a small configuration, no chip); ``fault`` plants a
    fault in the timed path; ``control`` puts the int8 control's first
    choices in the place of the served tokens of the sample, so that the
    same checks judge the control (``control.py``). Returns the result
    object, or exits."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    if cell_override:
        for k, v in cell_override.items():
            getattr(cell, k).update(v)
    import jax
    devs = check_device(cell.spec["chips"], allow_cpu)
    if not allow_cpu:
        enable_cache()
    clog = CompileLog()
    peaks = read_json(HERE / "peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks and not allow_cpu:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    peak = peaks.get(kind, {"flops": float("nan"), "hbm_bytes_per_s": float("nan")})
    seed = args.seed
    seed31 = seed % (1 << 31)
    cc = cell.cell
    cfg = program_config(cell, cfg_override)
    # prompt ids come from the published vocabulary (no pad rows)
    vocab_src = min(cell.config["vocab_size"], cfg.vocab_size)
    reqs = traffic.window(cell.mix, cc["rate"], args.seconds, seed, vocab_src)
    log(f"device: {devs[0].platform} {kind} x{len(devs)}")
    log(f"{cell.name} seed {seed}: {traffic.describe(reqs, args.seconds)}")

    import weights
    from repro.core import SLO
    from repro.engine import ArrowEngineCluster
    split = {}
    t = time.perf_counter()
    params = weights.make(cell.run_cfg, seed)
    jax.block_until_ready(params)
    check_layout(cfg, params)
    split["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    slo = cell.mix["slo"]
    cluster = ArrowEngineCluster(
        cfg, n_instances=cc["instances"], n_prefill=cc["n_prefill"],
        n_slots=cc["n_slots"], capacity=cc["capacity"],
        slo=SLO(ttft=slo["ttft_s"], tpot=slo["tpot_s"]), seed=seed31,
        params=params, chunk_tokens=cc["chunk_tokens"], policy=cc["policy"])
    split["cluster_s"] = time.perf_counter() - t
    t = time.perf_counter()
    n_prog, n_cat, n_mig = warm_up(cluster, cell, [len(r.prompt) for r in reqs],
                                   clog)
    split["warm_s"] = time.perf_counter() - t
    if fault is not None:
        fault(cluster)
    probe = Probe(cluster, annotate=bool(args.trace))
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_PROCESS
    split.update({k + "_s": v for k, v in clog.secs.items()})
    log(f"set-up {setup_s:.3f} s {json.dumps(split)}; programs {n_prog}, "
        f"concatenations {n_cat}, transfer shapes {n_mig}")

    trace_dir = str(ROOT / ".bench_trace" / cell.name) if args.trace else None
    if trace_dir:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    clog.in_window = True
    t0, t1, logs, flips, events = serve(
        cluster, reqs, cell, args.seconds, probe, trace_dir, cc["drain_s"])
    clog.in_window = False
    log(f"compiled, lowered or loaded after set-up: {len(clog.window)} "
        f"{sorted(set(clog.window))[:8]}")
    run = Run(cell, t0, t1, logs, probe.steps,
              probe.migrations, flips, peak)
    stats = devs[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    failed = sum(1 for lg in logs if not lg.done)
    log(f"window: {len(logs)} due, {len(logs) - failed} finished, "
        f"{failed} unfinished after the drain, {len(probe.migrations)} "
        f"migrations ({probe.refused} transfers turned back), {flips} flips, "
        f"{len(run.window_steps)} instance steps")

    result = {"correct": None, "attempted": len(logs), "failed": failed}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    if events is not None:
        import trace_reduce
        red = trace_reduce.reduce(events)
        run.trace = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = t1 - t0
        metrics = {}
        for m in cell.per_layer():
            v = load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        vals = end_to_end(run, setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end()}
    log(f"metrics: {json.dumps(metrics)}")

    # the check, on freed memory
    picked = sample(logs, cell)
    vocab = cell.run_cfg["vocab_size"]
    wrong_len = sum(1 for lg in logs if len(lg.tokens) > lg.req.out_len)
    out_vocab = sum(1 for lg in logs for tk in lg.tokens
                    if not 0 <= tk < vocab)
    del cluster, params, probe
    gc.unfreeze()
    gc.collect()
    t = time.perf_counter()
    gap, ctl, n_cmp = (reference_gaps(cell, seed, picked, control)
                       if picked else (math.inf, math.nan, 0))
    moved = sum(1 for lg in picked if lg.handle.req.decode_instance
                not in (None, lg.handle.req.prefill_instance))
    log(f"reference: {len(picked)} requests ({moved} decoded on another "
        f"instance than their prefill; prompts "
        f"{[len(lg.req.prompt) for lg in picked]}), {n_cmp} served tokens, "
        f"{time.perf_counter() - t:.1f} s")
    if control:
        log(f"control: the int8 reference's first choices stand in for the "
            f"served tokens (the program's own widest gap {gap})")
    judged = ctl if control else gap
    ch = cc["check"]
    checks = {"max_logit_gap": (judged, ch["max_logit_gap"]),
              "tokens_compared": (n_cmp, ch["sample_tokens"]),
              "overlong": (wrong_len, 0),
              "out_of_vocab": (out_vocab, 0),
              "unfinished_after_drain": (failed, 0)}
    ok = (judged <= ch["max_logit_gap"] and n_cmp >= ch["sample_tokens"]
          and wrong_len == 0 and out_vocab == 0 and failed == 0)
    result["correct"] = bool(ok)
    result["metrics"] = metrics
    result["device"] = device
    lim = {k: {"value": v, "limit": l} for k, (v, l) in checks.items()}
    result["checks"] = lim
    result["run"] = run
    result["program_gap"] = gap
    for k, (v, l) in checks.items():
        log(f"check {k}: {v} limit {l}")
    return result


def main():
    res = run_cell()
    out = {k: res[k] for k in ("correct", "attempted", "failed", "metrics",
                               "device")}
    if "breakdown" in res:
        out["breakdown"] = res["breakdown"]
    out["checks"] = res["checks"]
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

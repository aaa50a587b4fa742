import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on the
production meshes, record memory/cost analysis and the collective-bytes sum
for the roofline report.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]

Results append to benchmarks/results/dryrun.json (incremental; safe to rerun).
"""
import argparse
import json
import pathlib
import re
import sys
import time
import traceback

import jax

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.distributed.steps import build_dryrun, supports
from repro.launch.mesh import make_production_mesh

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / \
    "results" / "dryrun"

_COLL_RE = re.compile(
    r"=\s*(.*?)\s*(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-operand bytes of every collective op in the post-SPMD,
    post-optimization HLO (``compiled.as_text()``), bucketed by op kind.
    Bytes are per-device (the module is the per-device program); '-done' ops
    are skipped so async pairs count once."""
    out = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        shapes, kind = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(shapes):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + nbytes
    return out


def _measure(cfg, shape, mesh, opts: frozenset = frozenset()) -> dict:
    """lower+compile one config; return per-device cost terms."""
    fn, args = build_dryrun(cfg, shape, mesh, opts)
    t0 = time.time()
    lowered = fn.lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    return {
        "lower_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collective_bytes": collective_bytes(compiled.as_text()),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        },
    }


def _layer_probes(cfg):
    """Reduced-layer unrolled probe configs + extrapolation weights.

    XLA cost analysis counts while-loop (scan) bodies once, and a full unroll
    of a 40-layer model takes minutes on this box — so we compile tiny
    *unrolled* probes at 2-3 layer counts and extrapolate the exactly-linear
    per-layer terms to the full depth. Returns (probe_cfgs, combine) where
    combine(values: list) -> extrapolated full-model value.
    """
    if cfg.family == "encdec":
        e, d = cfg.encoder.n_layers, cfg.n_layers
        probes = [
            cfg.replace(n_layers=2, encoder=cfg.encoder.__class__(
                n_layers=2, n_frames=cfg.encoder.n_frames)),
            cfg.replace(n_layers=2, encoder=cfg.encoder.__class__(
                n_layers=4, n_frames=cfg.encoder.n_frames)),
            cfg.replace(n_layers=4, encoder=cfg.encoder.__class__(
                n_layers=2, n_frames=cfg.encoder.n_frames)),
        ]

        def combine(v):
            per_enc = (v[1] - v[0]) / 2.0
            per_dec = (v[2] - v[0]) / 2.0
            ovh = v[0] - 2 * per_enc - 2 * per_dec
            return ovh + e * per_enc + d * per_dec
        return probes, combine

    if cfg.family == "hybrid":
        plen = len(cfg.hybrid.pattern)
        groups = cfg.n_layers // plen
        tail = cfg.n_layers % plen
        probes = [cfg.replace(n_layers=plen), cfg.replace(n_layers=2 * plen)]
        if tail:
            probes.append(cfg.replace(n_layers=plen + tail))

        def combine(v):
            per_group = v[1] - v[0]
            ovh = v[0] - per_group
            total = ovh + groups * per_group
            if tail:
                total += v[2] - v[0]
            return total
        return probes, combine

    probes = [cfg.replace(n_layers=2), cfg.replace(n_layers=4)]

    def combine(v):
        per = (v[1] - v[0]) / 2.0
        return (v[0] - 2 * per) + cfg.n_layers * per
    return probes, combine


def run_one(arch: str, shape_name: str, multi_pod: bool,
            opts: frozenset = frozenset()) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "opts": sorted(opts), "ts": time.time()}
    if not supports(cfg, shape):
        rec["status"] = "skipped"
        rec["reason"] = "no sub-quadratic variant (DESIGN.md §4)"
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    with mesh:
        # 1) the gate: the FULL config must lower + compile (scan-over-layers)
        full = _measure(cfg, shape, mesh, opts)
        # 2) unrolled reduced-layer probes -> exact per-layer extrapolation
        probes, combine = _layer_probes(cfg.replace(scan_unroll=True))
        pvals = [_measure(p, shape, mesh, opts) for p in probes]

        def extra(key):
            return combine([p[key] for p in pvals])

        coll_kinds = set()
        for p in pvals:
            coll_kinds |= set(p["collective_bytes"])
        coll = {k: max(combine([p["collective_bytes"].get(k, 0)
                                for p in pvals]), 0.0) for k in coll_kinds}
    rec.update({
        "status": "ok",
        "lower_s": full["lower_s"],
        "compile_s": full["compile_s"],
        "flops": max(extra("flops"), 0.0),            # per-device, full depth
        "bytes_accessed": max(extra("bytes_accessed"), 0.0),
        "collective_bytes": coll,
        "flops_scanned_hlo": full["flops"],           # loop-body-once figure
        "memory": full["memory"],
    })
    return rec


def _results_dir(opts: frozenset) -> pathlib.Path:
    return RESULTS_DIR if not opts else RESULTS_DIR.parent / "dryrun_opt"


def load_results(opts: frozenset = frozenset()) -> list:
    d = _results_dir(opts)
    if not d.exists():
        return []
    return [json.loads(p.read_text()) for p in sorted(d.glob("*.json"))]


def save_result(rec: dict, opts: frozenset = frozenset()) -> None:
    d = _results_dir(opts)
    d.mkdir(parents=True, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh'].replace('x','-')}.json"
    (d / name).write_text(json.dumps(rec, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma list: act_shard,kv_seq_shard (results land "
                         "in dryrun_opt/)")
    args = ap.parse_args()
    opts = frozenset(o for o in args.opt.split(",") if o)

    meshes = []
    if args.single_pod or not args.multi_pod:
        meshes.append(False)
    if args.multi_pod or not args.single_pod:
        meshes.append(True)

    combos = []
    if args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    done = {(r["arch"], r["shape"], r["mesh"]) for r in load_results(opts)
            if r.get("status") in ("ok", "skipped")} if args.skip_done else set()

    failures = 0
    for arch, shape in combos:
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            if (arch, shape, mesh_name) in done:
                continue
            try:
                rec = run_one(arch, shape, mp, opts)
            except Exception as e:  # noqa: BLE001
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "status": "error", "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
                failures += 1
            save_result(rec, opts)
            msg = rec["status"]
            if rec["status"] == "ok":
                msg += (f" flops={rec['flops']:.3e} "
                        f"coll={sum(rec['collective_bytes'].values()):.3e}B "
                        f"compile={rec['compile_s']}s")
            print(f"[dryrun] {arch} x {shape} x {mesh_name}: {msg}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()

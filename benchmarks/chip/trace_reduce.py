"""Reduce a profiler trace to the numbers the benchmark reports.

Input is a flat list of events ``(plane, line, name, start_ns, dur_ns)``:
``load`` reads it from the ``.xplane.pb`` that ``jax.profiler`` writes.
Device events are those of the ``XLA Ops`` line of each ``/device:``
plane; host spans are the benchmark's own ``bench.*`` annotations.

* busy: the union of a device's op intervals, averaged over devices;
* ops: device time per op name, leaving out control-flow containers
  (``while``, ``conditional``, ``call``), whose intervals hold other ops;
* kernels: time and call count per Pallas kernel, named by its HLO
  instruction (``%paged_attention.3 = ...`` counts for ``paged_attention``);
* idle gaps: the stretches with no op on the device, each named by the
  host span that covers most of it (``host`` where no span does).
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

KERNELS = ("paged_attention", "flash_prefill", "ssd_scan", "rglru_scan")
_CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]*\s")
_KERNEL = re.compile(r"^%?(" + "|".join(KERNELS) + r")[_a-z]*[.\d]*\s=")

Event = Tuple[str, str, str, float, float]


def load(profile_dir: str) -> List[Event]:
    """Events of the newest ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(profile_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append((plane.name, line.name, e.name,
                            float(e.start_ns), float(e.duration_ns)))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def short_name(name: str, width: int = 160) -> str:
    return " ".join(name.split())[:width]


def kernel_of(name: str):
    m = _KERNEL.match(name)
    return m.group(1) if m else None


def reduce(events: List[Event], top: int = 10) -> Dict:
    dev = defaultdict(list)
    host = []
    for plane, line, name, s, d in events:
        if plane.startswith("/device:") and line == "XLA Ops":
            dev[plane].append((name, s, s + d))
        elif name.startswith("bench."):
            host.append((name, s, s + d))
    if not dev:
        return {"devices": 0, "busy_s": 0.0, "kernels": {}, "ops": [],
                "idle_gaps": []}
    busy, ops, kern = [], defaultdict(float), {}
    calls = defaultdict(int)
    gaps_all = []
    for plane, evs in sorted(dev.items()):
        merged = _union([(s, e) for _, s, e in evs])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for a, b in zip(merged, merged[1:]):
            gaps_all.append((a[1], b[0]))
        for name, s, e in evs:
            if _CONTAINER.match(name):
                continue
            ops[short_name(name)] += (e - s) * 1e-9
            k = kernel_of(name)
            if k:
                kern[k] = kern.get(k, 0.0) + (e - s) * 1e-9
                calls[k] += 1
    n = len(dev)
    gaps = []
    for s, e in sorted(gaps_all, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "host", 0.0
        for name, hs, he in host:
            ov = min(e, he) - max(s, hs)
            if ov > cover:
                best, cover = name, ov
        gaps.append([best, (e - s) * 1e-9])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {"devices": n, "busy_s": sum(busy) / n,
            "kernels": {k: {"seconds": kern[k] / n, "calls": calls[k]}
                        for k in kern},
            "ops": [[k, v / n] for k, v in top_ops],
            "idle_gaps": gaps}

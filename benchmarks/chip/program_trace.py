"""The program's own spans and scopes in a traced run, for the per-layer
readers that look inside the engine.

The engine writes ``arrow.*`` host spans (``jax.profiler.TraceAnnotation``,
with their arguments as event stats) on the profiler's clock, beside the
device ops. ``load`` reads the newest ``.xplane.pb`` under a directory, the file
``trace_reduce.load`` reads, and keeps what that drops: each event's stats.
``of(run)`` reads the traced run's directory (``.bench_trace/<cell>``,
which ``run.py`` writes) once and keeps the result on the run, so that
every reader shares one read.

Device ops are the intervals of the ``XLA Ops`` line of each ``/device:``
plane, as in ``trace_reduce``. On a TPU v5e their events carry no scope
path (their only stats are ``device_offset_ps``, ``device_duration_ps``
and ``Time Scale Multiplier``), so the fused step's ``sample`` scope cannot
be read from them. A reader returns None where what it needs is absent: no
trace, no ``arrow.*`` span (a program without them), no device plane (a
CPU run).
"""
from __future__ import annotations

import warnings
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

import trace_reduce

ROOT = Path(__file__).resolve().parents[2]

# (plane, line, name, start_ns, duration_ns, stats)
Event = Tuple[str, str, str, float, float, dict]


@dataclass
class Span:
    name: str
    start: float              # ns, the profiler's clock
    end: float
    args: dict

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclass
class ProgramTrace:
    spans: List[Span] = field(default_factory=list)
    # device plane -> its ops' (start, end), ns
    devices: Dict[str, List[Tuple[float, float]]] = field(
        default_factory=dict)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]


def from_events(events: Iterable[Event]) -> ProgramTrace:
    spans, devices = [], defaultdict(list)
    for plane, line, name, s, d, stats in events:
        if plane.startswith("/device:") and line == "XLA Ops":
            devices[plane].append((s, s + d))
        elif name.startswith("arrow."):
            spans.append(Span(name, s, s + d, dict(stats)))
    spans.sort(key=lambda sp: sp.start)
    return ProgramTrace(spans, dict(devices))


def load(profile_dir) -> Optional[ProgramTrace]:
    from jax.profiler import ProfileData
    files = sorted(Path(profile_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    events = []
    with warnings.catch_warnings():
        # the stats' type warns, as it is read, that it has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        data = ProfileData.from_file(str(files[-1]))
        for plane in data.planes:
            dev = plane.name.startswith("/device:")
            for line in plane.lines:
                ops = dev and line.name == "XLA Ops"
                for e in line.events:
                    if ops:
                        events.append((plane.name, line.name, e.name,
                                       float(e.start_ns),
                                       float(e.duration_ns), {}))
                    elif e.name.startswith("arrow."):
                        events.append((plane.name, line.name, e.name,
                                       float(e.start_ns),
                                       float(e.duration_ns), dict(e.stats)))
    return from_events(events)


def of(run) -> Optional[ProgramTrace]:
    """The traced run's program trace, read once and kept on the run."""
    if "program_trace" not in vars(run):
        run.program_trace = load(ROOT / ".bench_trace" / run.cell.name)
    return run.program_trace


# ---------------------------------------------------------- interval sums

def union(intervals) -> List[Tuple[float, float]]:
    return trace_reduce._union(list(intervals))


def measure(intervals) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    return sum(e - s for s, e in union(intervals))


def minus(a, b) -> float:
    """Length of the union of ``a`` outside the union of ``b``."""
    left, cut = union(a), union(b)
    out, j = 0.0, 0
    for s, e in left:
        while j < len(cut) and cut[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(cut) and cut[k][0] < e:
            if cut[k][0] > at:
                out += cut[k][0] - at
            at = max(at, cut[k][1])
            k += 1
        if at < e:
            out += e - at
    return out


def inside(span: Span, outer: Span) -> bool:
    return outer.start <= span.start and span.end <= outer.end

"""Seeded open-loop traffic for one benchmark window.

One general generator reads a traffic mix (``traffic/<name>.json``) and the
cell's offered rate, and draws the window's arrival schedule from
``--seed``. The work a window offers is the same for every seed; when and
in what order it arrives is the seed's.

* Count. ``n = round(rate * seconds)`` requests are due in every window.
* Sizes. Prompt and output lengths are correlated lognormals (a Gaussian
  copula with correlation ``in_out_corr``), read at the fixed quantiles
  ``(i + 0.5) / n``, with a fixed pairing of the two quantile ladders.
  Which pairs decode greedily is fixed too (every ``greedy_every``-th).
* Arrivals. A two-state Markov-modulated Poisson process (MMPP), as in
  BurstGPT (arXiv:2401.17644): a base state and a burst state whose rate is
  ``burst_mult`` times the base state's, the burst state holding
  ``burst_frac`` of the time; the two rates average to the cell's rate.
  The window is cut into slots of ``slot_s`` seconds, and
  ``round(burst_frac * slots)`` of them are burst slots. Each slot's share
  of the ``n`` requests follows its state's rate (largest remainder), so
  the number of requests in each state is fixed.
* From the seed: which slots are bursts, which requests fall in which
  slot (a permutation of the size pairs), and each arrival time. A slot of
  ``c`` requests is cut into ``c`` equal parts and one request arrives at a
  uniform time in each (stratified, where a Poisson process would draw
  ``c`` uniform times), so that two seeds offer their bursts at the same
  density.

Prompt token ids are drawn from the seed over the configuration's source
vocabulary.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

HERE = Path(__file__).resolve().parent
# the fixed pairing of the prompt and output quantile ladders
PAIRING_SEED = 20250517


@dataclass(frozen=True)
class Req:
    """One request of the window: due ``due_s`` seconds after the window
    opens, ``prompt`` token ids, ``out_len`` tokens to generate."""

    idx: int
    due_s: float
    prompt: np.ndarray
    out_len: int
    greedy: bool


def load_mix(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def _ladder(n: int) -> np.ndarray:
    nd = NormalDist()
    return np.asarray([nd.inv_cdf((i + 0.5) / n) for i in range(n)])


def sizes(mix: dict, n: int) -> List[tuple]:
    """The window's (prompt_len, out_len, greedy) triples in pair order:
    the same list for every seed."""
    p, o = mix["prompt"], mix["output"]
    rho = float(mix.get("in_out_corr", 0.0))
    z_in = _ladder(n)
    z_perp = _ladder(n)[np.random.default_rng(PAIRING_SEED).permutation(n)]
    z_out = rho * z_in + math.sqrt(max(1.0 - rho * rho, 0.0)) * z_perp
    ins = np.exp(math.log(p["median"]) + p["sigma"] * z_in)
    outs = np.exp(math.log(o["median"]) + o["sigma"] * z_out)
    ins = np.clip(np.rint(ins), p["min"], p["max"]).astype(int)
    outs = np.clip(np.rint(outs), o["min"], o["max"]).astype(int)
    every = int(mix["sampling"]["greedy_every"])
    return [(int(a), int(b), i % every == 0)
            for i, (a, b) in enumerate(zip(ins, outs))]


def slot_counts(n: int, n_slots: int, n_burst: int, mult: float) -> tuple:
    """Requests in each burst slot and in each base slot, as integer lists
    whose multiset is fixed: largest-remainder rounding of the MMPP's
    rates, the burst state's ``mult`` times the base state's."""
    weights = [mult] * n_burst + [1.0] * (n_slots - n_burst)
    total = sum(weights)
    raw = [n * w / total for w in weights]
    counts = [int(math.floor(r)) for r in raw]
    order = sorted(range(n_slots), key=lambda i: (-(raw[i] - counts[i]), i))
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts[:n_burst], counts[n_burst:]


def slot_plan(mix: dict, n: int, seconds: float) -> tuple:
    """(slot length, requests in each burst slot, in each base slot)."""
    a = mix["arrivals"]
    if a["process"] != "mmpp2":
        raise SystemExit(f"unknown arrival process {a['process']!r}")
    n_slots = max(1, int(round(seconds / a["slot_s"])))
    frac = float(a["burst_frac"])
    n_burst = min(n_slots, int(round(frac * n_slots))) if frac > 0 else 0
    burst, base = slot_counts(n, n_slots, n_burst, float(a["burst_mult"]))
    return seconds / n_slots, burst, base


def window(mix: dict, rate: float, seconds: float, seed: int,
           vocab: int) -> List[Req]:
    """The requests due in one window, in due order."""
    n = int(round(rate * seconds))
    triples = sizes(mix, n)
    length, burst, base = slot_plan(mix, n, seconds)
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    counts = burst + base
    counts = [counts[k] for k in rng.permutation(len(counts))]
    pairs = iter(rng.permutation(n))
    due = []
    for k, c in enumerate(counts):
        offs = (np.arange(c) + rng.uniform(0.0, 1.0, c)) * (length / max(c, 1))
        due += [(k * length + float(o), int(next(pairs))) for o in offs]
    reqs = []
    for t, i in sorted(due):
        plen, olen, greedy = triples[i]
        prompt = rng.integers(0, vocab, size=plen, dtype=np.int32)
        reqs.append(Req(i, t, prompt, olen, greedy))
    return reqs


def describe(reqs: List[Req], seconds: float) -> str:
    """One line that tells two schedules apart."""
    due = [r.due_s for r in reqs]
    head = ",".join(f"{t:.3f}" for t in due[:4])
    return (f"{len(reqs)} requests due in {seconds:g} s, "
            f"{sum(len(r.prompt) for r in reqs)} prompt and "
            f"{sum(r.out_len for r in reqs)} output tokens, "
            f"{sum(r.greedy for r in reqs)} greedy; first due at [{head}]; "
            f"pair order {[r.idx for r in reqs[:6]]}")

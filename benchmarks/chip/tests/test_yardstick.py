"""The benchmark's yardstick on the CPU: the trace reduction on a recorded
chip trace and on a hand-made one, the operation and byte counts, the
program-shape enumeration, and the seeded traffic.

    python -m pytest benchmarks/chip/tests
"""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import flops  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

MAMBA = json.loads((HERE / "configs" / "mamba2-370m.json").read_text())["run"]
RECORDED = HERE / "tests" / "data" / "qwen3_two_steps.events.json.gz"


def recorded():
    """Two fused steps and a migration of qwen3-1.7b on a TPU v5 lite: every
    device op of the sample and the benchmark's host spans."""
    return [tuple(e[:5]) for e in json.load(gzip.open(RECORDED))]


# ------------------------------------------------------------ the trace

def test_reduce_hand_made_trace():
    dev, ops = "/device:TPU:0", "XLA Ops"
    ev = [(dev, ops, "%while.1 = (f32[2]) while(f32[2] %a)", 0, 100),
          (dev, ops, "%fusion.1 = f32[2] fusion(f32[2] %a)", 10, 30),
          (dev, ops, "%paged_attention.3 = bf16[8,1,2048] custom-call(x)", 40, 20),
          (dev, ops, "%flash_prefill_dyn.2 = bf16[1,16,256,128] custom-call(y)",
           300, 50),
          (dev, ops, "%ssd_scan.6 = (f32[1]) custom-call(z)", 400, 25),
          ("/host:CPU", "python3", "bench.migrate", 90, 200),
          ("/host:CPU", "python3", "bench.dispatch", 350, 60)]
    r = trace_reduce.reduce(ev)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx((100 + 50 + 25) * 1e-9)
    k = r["kernels"]
    assert k["paged_attention"] == {"seconds": pytest.approx(20e-9), "calls": 1}
    assert k["flash_prefill"]["calls"] == 1 and k["ssd_scan"]["calls"] == 1
    # the while loop holds the fusion and the kernel: it is not an op
    assert not any(n.startswith("%while") for n, _ in r["ops"])
    # gaps: 100..300 under the migration, 350..400 under the dispatch
    assert r["idle_gaps"] == [["bench.migrate", pytest.approx(200e-9)],
                              ["bench.dispatch", pytest.approx(50e-9)]]


def test_reduce_recorded_trace():
    ev = recorded()
    r = trace_reduce.reduce(ev)
    dev = sorted((s, s + d) for p, l, n, s, d in ev
                 if p.startswith("/device:") and l == "XLA Ops")
    # busy: the union of op intervals, counted by brute force over ns ticks
    lo, hi = int(dev[0][0]), int(max(e for _, e in dev))
    covered = np.zeros(hi - lo + 1, bool)
    for s, e in dev:
        covered[int(s) - lo:int(e) - lo] = True
    assert r["busy_s"] == pytest.approx(covered.sum() * 1e-9, rel=1e-6)
    n_flash = sum(1 for p, l, n, s, d in ev if n.startswith("%flash_prefill"))
    assert r["kernels"]["flash_prefill"]["calls"] == n_flash == 56
    assert set(r["kernels"]) == {"flash_prefill"}
    assert len(r["ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert r["ops"] == sorted(r["ops"], key=lambda kv: -kv[1])
    assert {g[0] for g in r["idle_gaps"]} <= {"bench.dispatch",
                                             "bench.finalize", "host"}


def test_reduce_no_device():
    r = trace_reduce.reduce([("/host:CPU", "python3", "bench.migrate", 0, 5)])
    assert r["devices"] == 0 and r["kernels"] == {}


# ---------------------------------------------------- operations, bytes

def test_prefill_flops_add_up_token_by_token():
    c = MAMBA
    whole = flops.prefill_flops(c, 0, 300)
    by_token = sum(flops.token_flops(c, i + 1) for i in range(300))
    assert whole == pytest.approx(by_token, rel=1e-12)
    split = flops.prefill_flops(c, 0, 128) + flops.prefill_flops(c, 128, 172)
    assert split == pytest.approx(whole, rel=1e-12)


def test_matmul_params_match_published_counts():
    # Mamba2-370m: ~370e6 parameters
    assert 3.4e8 < flops.matmul_params(MAMBA) < 4.0e8


def test_ssd_counts():
    o, b = flops.ssd_scan(MAMBA, 256)
    o1, b1 = flops.ssd_scan(MAMBA, 128)
    assert o == pytest.approx(2 * o1) and b > b1


# ------------------------------------------------------- program shapes

def test_chunk_shapes_respect_budget_and_slots():
    import run
    progs, cats = run.chunk_shapes(256, 8)
    assert len(progs) == 47
    for entry, w, k in progs:
        least = 1 if w == 32 else w - 31
        assert k * least <= 256
        assert k <= (7 if entry == "mixed" else 8)
    assert ("chunks", 256, 1) in progs and ("mixed", 32, 7) in progs
    assert ("mixed", 32, 8) not in progs
    assert all(len(c) >= 2 for c in cats)
    assert (1 + 8, 1) in cats          # decode batch + one chunk, then one


# -------------------------------------------------------------- traffic

def test_same_seed_same_traffic():
    m = traffic.load_mix("chat-burst")
    a = traffic.window(m, 1.0, 51, 2**31 + 12345, 50277)
    b = traffic.window(m, 1.0, 51, 2**31 + 12345, 50277)
    assert [(r.idx, r.due_s, r.out_len, r.greedy) for r in a] == \
        [(r.idx, r.due_s, r.out_len, r.greedy) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_change_arrivals_not_totals():
    m = traffic.load_mix("chat-burst")
    runs = [traffic.window(m, 1.2, 51, s, 50277) for s in (1, 2, 3_000_000_001)]
    for w in runs:
        assert len(w) == round(1.2 * 51)
        assert all(0 <= r.due_s < 51 for r in w)
        assert [r.due_s for r in w] == sorted(r.due_s for r in w)
    # the same work: the same size pairs, the same greedy ones
    sets = [sorted((len(r.prompt), r.out_len, r.greedy) for r in w)
            for w in runs]
    assert sets[0] == sets[1] == sets[2]
    # another schedule: other times, another order, bursts elsewhere
    dues = [frozenset(round(r.due_s, 6) for r in w) for w in runs]
    assert not (dues[0] & dues[1]) and not (dues[1] & dues[2])
    assert len({tuple(r.idx for r in w) for w in runs}) == 3
    slot = 51 / 20

    def busiest(w):
        n = np.bincount([int(r.due_s // slot) for r in w], minlength=20)
        return frozenset(np.argsort(-n, kind="stable")[:2].tolist())
    assert len({busiest(w) for w in runs}) > 1


def test_bursts_carry_their_share():
    m = traffic.load_mix("chat-burst")
    burst, base = traffic.slot_counts(51, 20, 2, 8.0)
    assert sum(burst) + sum(base) == 51
    # 8x the base rate for 10% of the time: 16/34 of the requests
    assert sum(burst) == pytest.approx(16 / 34 * 51, abs=1)
    for seed in (7, 2**31 + 3):
        w = traffic.window(m, 1.0, 51, seed, 50277)
        per_slot = np.bincount([int(r.due_s // (51 / 20)) for r in w],
                               minlength=20)
        assert sorted(per_slot) == sorted(burst + base)


def test_lengths_at_fixed_quantiles():
    s = traffic.sizes(traffic.load_mix("chat-burst"), 101)
    assert sorted(a for a, _, _ in s)[50] == 256          # the medians
    assert sorted(b for _, b, _ in s)[50] == pytest.approx(96, rel=0.15)
    assert sum(g for _, _, g in s) == 26                  # every 4th pair

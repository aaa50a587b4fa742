"""Determinism layer for replayable on-device sampling and self-speculative
decoding (DESIGN.md §12).

The contract under test: token selection is a pure function of
``(seed, rid, absolute position, logits)`` — no PRNG counter state exists
anywhere — so a stream replays bit-for-bit across runs, across step modes
(fused vs legacy), across KV migration and crash recovery, and under
self-speculative decoding (which emits exactly the tokens sequential decode
would). ``temperature<=0`` is provably the pre-sampling argmax path, pinned
against golden streams recorded at PR 8 so greedy serving can never drift.

Everything here asserts token *ids* (bit-identity), never timings, so a
loaded CI machine can only time out, not produce a wrong pass.
"""
import dataclasses
import json
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from invariants import check_invariants

from repro.configs import get_smoke_config
from repro.core import Request, SLO, SamplingParams
from repro.core.faults import FaultPlan
from repro.engine import ArrowEngineCluster, EngineInstance
from repro.engine import fused_step as fs
from repro.models import build_model

DRAIN_TIMEOUT = 300.0
GOLDEN = Path(__file__).parent / "data" / "golden_streams_pr8.json"


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen3-1.7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    return cfg, model, params


def golden_prompts(cfg):
    """The prompts the golden pin was recorded with (seed fixed forever)."""
    rng = np.random.default_rng(123)
    return {i: rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(8, 28))).astype(np.int32)
            for i in range(3)}


def instance_stream(inst, rid, prompt, n_new, sp=None):
    """Sequential prefill+decode on one instance; returns n_new tokens."""
    inst.set_sampling(rid, sp)
    inst.run_prefill(rid, prompt)
    inst.local.start_local_decode(rid, len(prompt), n_new - 1)
    for _ in range(n_new - 1):
        inst.run_decode_iteration([rid])
    return [int(t) for t in inst.generated[rid][:n_new]]


def cluster_streams(cfg, params, *, sampling=None, speculate=0, seed=0,
                    fault_plan=None, n=4, out_len=8, arrivals=None,
                    chunk_tokens=None):
    cluster = ArrowEngineCluster(cfg, n_instances=2, n_prefill=1, n_slots=4,
                                 capacity=128, slo=SLO(5.0, 2.0),
                                 params=params, seed=seed,
                                 speculate=speculate, fault_plan=fault_plan,
                                 chunk_tokens=chunk_tokens)
    rng = np.random.default_rng(5)
    prompts = {i: rng.integers(1, cfg.vocab_size, size=20).astype(np.int32)
               for i in range(n)}
    handles = [cluster.submit(
        Request(rid=i, arrival=(arrivals or {}).get(i, 0.0), input_len=20,
                output_len=out_len, sampling=sampling),
        prompt=prompts[i]) for i in range(n)]
    report = cluster.drain(timeout=DRAIN_TIMEOUT)
    check_invariants(cluster)
    assert report.n_finished == n
    return {h.rid: [int(t) for t in h.tokens] for h in handles}, report


# ------------------------------------------------------------ greedy pin

def test_greedy_streams_match_golden_pin(setup):
    """temperature=0 (and sampling=None) must reproduce the argmax streams
    recorded when sampling was introduced — the regression pin that greedy
    serving is byte-identical to the pre-sampling engine."""
    cfg, model, params = setup
    golden = json.loads(GOLDEN.read_text())["greedy"]
    inst = EngineInstance(0, cfg, params, n_slots=4, capacity=128)
    for rid, prompt in golden_prompts(cfg).items():
        got = instance_stream(inst, rid + 100, prompt, 10)
        assert got == golden[str(rid)], f"greedy stream {rid} drifted"
        inst.drop(rid + 100)


def test_sampled_streams_match_golden_pin(setup):
    """Seeded sampled streams are part of the replay contract too: the
    exact ``fold_in(fold_in(key(seed), rid), position)`` derivation and the
    Gumbel-max nucleus rule are pinned, so any change to key order,
    position bookkeeping or the keep-mass rule shows up as a diff here.
    The Gumbel noise comes from JAX's PRNG bits, so the file records the
    JAX version this section was pinned under (``sampled_jax_version``)."""
    cfg, model, params = setup
    golden = json.loads(GOLDEN.read_text())["sampled"]
    sp = SamplingParams(temperature=0.9, top_p=0.9, seed=77)
    inst = EngineInstance(0, cfg, params, n_slots=4, capacity=128)
    for rid, prompt in golden_prompts(cfg).items():
        got = instance_stream(inst, rid + 100, prompt, 10, sp=sp)
        assert got == golden[str(rid)], f"sampled stream {rid} drifted"
        inst.drop(rid + 100)


def test_temp0_param_is_exact_greedy(setup):
    """SamplingParams(temperature=0) ≡ sampling=None ≡ argmax; a nucleus
    collapsed to the top-1 token (tiny top_p) also reduces to argmax."""
    cfg, model, params = setup
    inst = EngineInstance(0, cfg, params, n_slots=4, capacity=128)
    prompt = golden_prompts(cfg)[0]
    base = instance_stream(inst, 1, prompt, 8, sp=None)
    inst.drop(1)
    explicit = instance_stream(inst, 1, prompt, 8,
                               sp=SamplingParams(temperature=0.0))
    inst.drop(1)
    collapsed = instance_stream(
        inst, 1, prompt, 8, sp=SamplingParams(temperature=0.7, top_p=1e-9))
    assert explicit == base
    assert collapsed == base


# -------------------------------------------------------------- replay

def test_sampled_replay_bit_identical(setup):
    """The replay guarantee: same trace + same run seed => bit-identical
    sampled streams across independent cluster runs (different wall-clock
    schedules and all); a different seed diverges."""
    cfg, _, params = setup
    sp = SamplingParams(temperature=0.8, top_p=0.9)
    s1, r1 = cluster_streams(cfg, params, sampling=sp, seed=42)
    s2, r2 = cluster_streams(cfg, params, sampling=sp, seed=42)
    assert s1 == s2
    assert r1.sampling["seed"] == 42 and r1.sampling["sampled_requests"] == 4
    s3, _ = cluster_streams(cfg, params, sampling=sp, seed=43)
    assert s3 != s1, "changing the run seed must change sampled streams"


def test_greedy_report_has_no_sampling_section(setup):
    """All-greedy runs keep the pre-PR report shape: the sampling and
    speculation detail dicts stay empty (byte-identical summaries)."""
    cfg, _, params = setup
    _, report = cluster_streams(cfg, params, sampling=None)
    assert report.sampling == {} and report.speculation == {}


def test_per_request_seed_overrides_run_seed(setup):
    """A request-level seed pins its stream regardless of the run seed;
    distinct rids draw distinct keys from the same seed."""
    cfg, _, params = setup
    inst = EngineInstance(0, cfg, params, n_slots=4, capacity=128)
    prompt = golden_prompts(cfg)[1]
    sp = SamplingParams(temperature=1.2, top_p=0.95, seed=11)
    a = instance_stream(inst, 1, prompt, 12, sp=sp)
    inst.drop(1)
    b = instance_stream(inst, 2, prompt, 12, sp=sp)     # same seed, new rid
    inst.drop(2)
    c = instance_stream(inst, 1, prompt, 12, sp=sp)     # exact replay
    inst.drop(1)
    d = instance_stream(inst, 1, prompt, 12,
                        sp=SamplingParams(temperature=1.2, top_p=0.95,
                                          seed=12))
    assert a == c, "same (seed, rid) must replay bit-for-bit"
    assert a != b, "distinct rids must fold to distinct key streams"
    assert a != d, "distinct seeds must fold to distinct key streams"


# ------------------------------------------------------- step-mode parity

def test_fused_vs_legacy_sampled_streams(setup):
    """Sampled streams are step-mode independent: the legacy (eager) path
    selects through the same jitted sampler as the fused step."""
    cfg, _, params = setup
    sp = SamplingParams(temperature=0.9, top_p=0.8, seed=3)
    prompt = golden_prompts(cfg)[2]
    fused = EngineInstance(0, cfg, params, n_slots=4, capacity=128)
    legacy = EngineInstance(1, cfg, params, n_slots=4, capacity=128,
                            step_mode="legacy")
    assert instance_stream(fused, 9, prompt, 10, sp=sp) \
        == instance_stream(legacy, 9, prompt, 10, sp=sp)


# ----------------------------------------------- migration / recovery

def test_migration_preserves_sampled_stream(setup):
    """KV migration mid-decode: sampling params travel with the KV and the
    keys are instance-independent, so the continued stream equals the
    uninterrupted one token-for-token."""
    cfg, _, params = setup
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=21)
    prompt = golden_prompts(cfg)[0]
    ref_inst = EngineInstance(0, cfg, params, n_slots=4, capacity=128)
    ref = instance_stream(ref_inst, 7, prompt, 9, sp=sp)
    a = EngineInstance(1, cfg, params, n_slots=4, capacity=128)
    b = EngineInstance(2, cfg, params, n_slots=4, capacity=128)
    a.set_sampling(7, sp)
    got = [a.run_prefill(7, prompt)]
    a.local.start_local_decode(7, len(prompt), 8)
    for _ in range(3):
        got.append(a.run_decode_iteration([7])[7])
    samp = a.kv.samp_of.get(7)
    k, v, L, last, gen = a.export_kv(7)
    assert b.import_kv(7, k, v, L, last, gen, sampling=samp)
    a.drop(7)
    b.local.start_local_decode(7, L, 5)
    for _ in range(5):
        got.append(b.run_decode_iteration([7])[7])
    assert got == ref


def test_chunked_prefill_preserves_sampled_stream(setup):
    """Chunked prefill (the §11 deflection micro-batch mechanism) samples
    its first output token at the same absolute position whole-prompt
    prefill does, so chunking never changes a sampled stream."""
    cfg, _, params = setup
    sp = SamplingParams(temperature=0.8, top_p=0.9)
    whole, _ = cluster_streams(cfg, params, sampling=sp, seed=13)
    chunked, _ = cluster_streams(cfg, params, sampling=sp, seed=13,
                                 chunk_tokens=8)
    assert chunked == whole


def test_crash_recovery_preserves_sampled_stream(setup):
    """Crash recovery re-prefills prompt+emitted tokens; the recovery o_1
    recomputes at the same absolute position the lost next-token would have
    sampled at, so recovered sampled streams are bit-identical to the
    unfaulted run (not just greedy ones — ISSUE 8 acceptance)."""
    cfg, _, params = setup
    sp = SamplingParams(temperature=0.8, top_p=0.9)
    arrivals = {3: 0.5}                     # straggler keeps the poll alive
    base, _ = cluster_streams(cfg, params, sampling=sp, seed=9, n=4,
                              out_len=24, arrivals=arrivals)
    chaos, rep = cluster_streams(
        cfg, params, sampling=sp, seed=9, n=4, out_len=24,
        arrivals=arrivals,
        fault_plan=FaultPlan.parse("crash@0.1:target=1"))
    assert rep.faults["crashes"] == 1
    assert chaos == base, "recovered sampled streams diverged"


# -------------------------------------------------------- speculation

def test_speculative_streams_bit_identical(setup):
    """Self-speculative decoding emits exactly the tokens sequential decode
    would (every accepted draft was verified against the same key and
    context) — speculation changes throughput, never content."""
    cfg, _, params = setup
    sp = SamplingParams(temperature=0.8, top_p=0.9)
    base, _ = cluster_streams(cfg, params, sampling=sp, seed=4, out_len=12)
    spec, rep = cluster_streams(cfg, params, sampling=sp, seed=4,
                                out_len=12, speculate=4)
    assert spec == base
    assert rep.speculation["rounds"] > 0
    assert rep.speculation["emitted"] > 0
    assert 0.0 <= rep.speculation["acceptance"] <= 1.0


def test_speculative_greedy_matches_golden_pin(setup):
    """Greedy + speculation still equals the pinned argmax streams."""
    cfg, _, params = setup
    golden = json.loads(GOLDEN.read_text())["greedy"]
    inst = EngineInstance(0, cfg, params, n_slots=4, capacity=128,
                          speculate=3, draft_layers=1)
    for rid, prompt in golden_prompts(cfg).items():
        inst.run_prefill(rid + 200, prompt)
        inst.local.start_local_decode(rid + 200, len(prompt), 9)
        while len(inst.generated[rid + 200]) < 10:
            pend = inst.dispatch_step([rid + 200], [])
            inst.finalize_step(pend)
        assert inst.generated[rid + 200][:10] == golden[str(rid)]
        inst.drop(rid + 200)


# ------------------------------------------- nucleus mask by two sorts

def _gather_scatter_sample_one(cfg, logits, temp, top_p, seed, rid, pos):
    """The sampler as it stood before the nucleus mask moved to two sorts:
    ``argsort``, a gather of the sorted probabilities and a scatter of the
    mask back to vocabulary order. Kept as the reference the two-sort
    sampler must match token for token."""
    V = cfg.vocab_size
    row = logits[:V]
    greedy = jnp.argmax(row).astype(jnp.int32)
    rowf = row.astype(jnp.float32)
    t = jnp.maximum(temp, 1e-6).astype(jnp.float32)
    scaled = rowf / t
    probs = jax.nn.softmax(scaled)
    order = jnp.argsort(-probs)
    sp = probs[order]
    keep_sorted = (jnp.cumsum(sp) - sp) < jnp.maximum(top_p, 1e-6)
    keep = jnp.zeros((row.shape[0],), bool).at[order].set(keep_sorted)
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), rid), pos)
    g = jax.random.gumbel(key, (row.shape[0],), jnp.float32)
    sampled = jnp.argmax(jnp.where(keep, scaled + g,
                                   -jnp.inf)).astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy, sampled)


@partial(jax.jit, static_argnums=(0,))
def _gather_scatter_sample_tokens(cfg, logits, temps, top_ps, seeds, rids,
                                  pos):
    return jax.vmap(
        lambda lg, t, p, sd, rid, ps: _gather_scatter_sample_one(
            cfg, lg, t, p, sd, rid, ps)
    )(logits, temps, top_ps, seeds, rids, pos)


SERVED_VOCAB = 50_280               # mamba2-370m's program vocabulary
ROW_TEMPS = (0.0, 0.3, 0.8, 1.5)
ROW_KINDS = ("ties", "peaked", "flat", "padded")


def _rows(kind, V, rng):
    """Eight logits rows, two per temperature in ``ROW_TEMPS``:
    ``ties`` bf16 logits rounded to halves (a dozen distinct values, so
    the sort's tie order decides the nucleus), ``peaked`` one token far
    above the rest, ``flat`` near-uniform float32, ``padded`` bf16 rows
    wider than the vocabulary whose pad columns would win if read."""
    B = 2 * len(ROW_TEMPS)
    if kind == "ties":
        return jnp.asarray(np.round(rng.normal(size=(B, V)) * 2) / 2,
                           jnp.bfloat16)
    if kind == "peaked":
        x = rng.normal(size=(B, V))
        x[np.arange(B), rng.integers(0, V, size=B)] += 12.0
        return jnp.asarray(x, jnp.bfloat16)
    if kind == "flat":
        return jnp.asarray(rng.normal(size=(B, V)) * 1e-3, jnp.float32)
    assert kind == "padded"
    pad = 128 - V % 128 + 128
    x = np.concatenate([rng.normal(size=(B, V)),
                        np.full((B, pad), 50.0)], axis=1)
    return jnp.asarray(x, jnp.bfloat16)


@pytest.mark.parametrize("top_p", [1e-9, 0.5, 0.95, 1.0])
@pytest.mark.parametrize("kind", ROW_KINDS)
@pytest.mark.parametrize("vocab", ["smoke", SERVED_VOCAB])
def test_two_sort_nucleus_matches_gather_scatter(vocab, kind, top_p):
    """The sampler's nucleus mask, taken through a stable sort of
    ``(-probs, iota)`` and a sort back by ``order``, selects exactly the
    tokens the argsort/gather/scatter sampler selected: on tied bf16 rows,
    peaked and near-uniform rows and padded rows, at every temperature
    (greedy included) and top-p from a collapsed nucleus to the whole
    vocabulary."""
    cfg = get_smoke_config("qwen3-1.7b")
    if vocab != "smoke":
        cfg = dataclasses.replace(cfg, vocab_size=vocab)
    V = cfg.vocab_size
    rng = np.random.default_rng([V, ROW_KINDS.index(kind),
                                 int(top_p * 1e9)])
    logits = _rows(kind, V, rng)
    B = logits.shape[0]
    args = (logits,
            jnp.asarray(np.repeat(ROW_TEMPS, 2), jnp.float32),
            jnp.full((B,), top_p, jnp.float32),
            jnp.asarray(rng.integers(0, 2**31 - 1, size=B), jnp.int32),
            jnp.asarray(rng.integers(0, 10_000, size=B), jnp.int32),
            jnp.asarray(rng.integers(0, 4096, size=B), jnp.int32))
    got = np.asarray(fs.sample_tokens(cfg, *args))
    want = np.asarray(_gather_scatter_sample_tokens(cfg, *args))
    np.testing.assert_array_equal(got, want)
    assert (got < V).all()


def test_sampler_lowers_without_gather_or_scatter():
    """Structural guard: the batched sampler at the served shape, (8,
    50,280), lowers to StableHLO with no gather and no scatter, the
    permutation traffic that took over half the TPU's decode step. The
    gather/scatter reference lowers with both, so the probe can see them."""
    cfg = dataclasses.replace(get_smoke_config("qwen3-1.7b"),
                              vocab_size=SERVED_VOCAB)
    B = 8
    shapes = (jax.ShapeDtypeStruct((B, SERVED_VOCAB), jnp.bfloat16),
              jax.ShapeDtypeStruct((B,), jnp.float32),
              jax.ShapeDtypeStruct((B,), jnp.float32),
              *(jax.ShapeDtypeStruct((B,), jnp.int32),) * 3)
    text = fs.sample_tokens.lower(cfg, *shapes).as_text()
    assert "stablehlo.sort" in text
    assert "gather" not in text and "scatter" not in text
    old = _gather_scatter_sample_tokens.lower(cfg, *shapes).as_text()
    assert "stablehlo.gather" in old and "stablehlo.scatter" in old


# ------------------------------------------------------------- simulator

def test_sim_sampling_and_speculation_modeled():
    """The simulator mirrors the engine's accounting: sampled requests and
    run seed land in the report, speculative rounds emit the modeled
    multi-token streams (exact output lengths, strictly ordered times) and
    a same-seed replay is event-for-event identical."""
    from repro.core.serving import replay_trace
    from repro.sim import Simulator
    from repro.traces import load_trace
    cfg = get_smoke_config("qwen3-1.7b")
    trace = load_trace("azure_code", rate_scale=4.0, seed=0, duration=20.0)
    for r in trace:
        r.sampling = SamplingParams(temperature=0.7)

    def run():
        sim = Simulator(cfg, n_instances=2, n_prefill=1, seed=6,
                        speculate=4, spec_accept=0.8)
        replay_trace(sim, trace)
        rep = sim.drain()
        check_invariants(sim)
        return sim, rep

    sim1, rep1 = run()
    assert rep1.n_finished == len(trace)
    assert rep1.sampling["seed"] == 6
    assert rep1.sampling["sampled_requests"] == len(trace)
    assert rep1.speculation["rounds"] > 0
    # modeled lengths are exact: every stream has its trace output length
    for h in sim1.handles.values():
        assert len(h.tokens) == h.req.output_len
    # modeled acceptance tracks the configured per-draft acceptance
    assert 0.3 <= rep1.speculation["acceptance"] <= 1.0
    _, rep2 = run()
    assert rep1.summary() == rep2.summary(), "sim replay must be exact"

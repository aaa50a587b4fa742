"""Smoke run of the serving path on one TPU chip. It is not a benchmark:
its times include compilation and are printed only to show where a cold
start goes.

Serves qwen3-1.7b at its published widths in bf16, with random weights made
from ``--seed``, through the launcher's own ``serve_engine``: policy
``arrow`` with one prefill and one decode instance, fused donated steps,
prefill-to-decode KV migration, and the Pallas attention kernels compiled by
Mosaic. It then checks that

  * every request finished with exactly its output length, and every token
    id is inside the vocabulary;
  * at least one migration moved its KV, with its bytes recorded;
  * the lowered fused steps hold a Mosaic kernel (``tpu_custom_call``);
  * for one prompt, the Pallas path's last-position prefill logits, and the
    logits of one decode step after it, agree with the reference-attention
    path under the same parameters (see ``LOGIT_TOL``).

    python chip_smoke.py [--seed N]

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
When JAX finds no TPU the script exits non-zero and prints no result; there
is no CPU fallback. JAX's compilation cache goes where
``repro.launch.serve.enable_compile_cache`` puts it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "qwen3-1.7b"
# prompts of 128-1024 tokens, two of each length: arriving together, they
# make four prefill-chunk shapes (two chunks of one width per step) and so
# four programs to compile
PROMPT_LENS = (1024, 1024, 512, 512, 256, 256, 128, 128)
OUT_LEN = 32
CAPACITY = 2048
PARITY_PROMPT = 512
# Pallas vs reference logits: max |Δ| over the vocabulary, relative to the
# reference's max |logit|. Both paths attend in float32 over the same bf16
# q/k/v and round the attention output to bf16 (relative step 2^-8), but
# they sum in different orders (online vs one-shot softmax), so an output
# element can round one bf16 step apart; such steps pass through 28
# residual layers and the bf16 logits themselves round at 2^-8 of their
# size. On the CPU this gap is about 7 bf16 steps (0.026 at 28 layers); the
# chip's one-pass bf16 matmuls in the reference's float32 einsums may add
# as much again. 2^-3 (32 steps) admits that, and still fails a kernel that
# maps query heads to the wrong kv head, which moved the decode logits by
# 1.4 of their scale.
LOGIT_TOL = 2.0 ** -3


class CompileClock:
    """Seconds JAX spent compiling, or loading compiled programs from its
    persistent cache, since the clock was made; and the cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class Phases:
    """Wall and compile seconds per phase, printed as the phase ends."""

    def __init__(self, clock: CompileClock):
        self.clock = clock

    def run(self, name, fn, *args, **kwargs):
        t0, c0, h0 = (time.perf_counter(), self.clock.seconds,
                      self.clock.cache_hits)
        out = fn(*args, **kwargs)
        print(f"[smoke run, not a benchmark] phase {name}: wall "
              f"{time.perf_counter() - t0:.1f}s, compile "
              f"{self.clock.seconds - c0:.1f}s, compile-cache hits "
              f"{self.clock.cache_hits - h0}", flush=True)
        return out


def serve_phase(cfg, params, *, prompt_lens=PROMPT_LENS, out_len=OUT_LEN,
                capacity=CAPACITY, timeout=1000.0):
    """Serve greedy requests of ``prompt_lens`` tokens, all arriving at
    once, on arrow with one prefill and one decode instance of 8 slots
    each. Returns
    ``(cluster, report, errors)``; ``errors`` lists every failed check."""
    from repro.core import SLO, Request
    from repro.launch.serve import serve_engine

    trace = [Request(rid=i, arrival=0.0, input_len=n, output_len=out_len)
             for i, n in enumerate(prompt_lens)]
    # compilation lands inside the first steps; a generous SLO keeps the
    # policy from flipping instances over those stalls, which would only
    # add programs to compile
    cluster, report = serve_engine(
        cfg, trace, instances=2, capacity=capacity,
        slo=SLO(ttft=600.0, tpot=60.0), params=params, timeout=timeout,
        label=f"smoke {cfg.arch_id} arrow")
    errors = []
    if report.unfinished:
        errors.append(f"unfinished rids {report.unfinished}")
    for h in report.handles:
        toks = [t for t in h.tokens if t is not None]
        if h.done and len(toks) != out_len:
            errors.append(f"rid {h.rid}: {len(toks)} tokens, "
                          f"expected {out_len}")
        bad = [t for t in toks if not 0 <= t < cfg.vocab_size]
        if bad:
            errors.append(f"rid {h.rid}: token ids out of vocabulary {bad}")
    moves = cluster.migration_log
    if not moves or any(m["bytes"] <= 0 for m in moves):
        errors.append(f"no KV migration with bytes recorded: {moves}")
    return cluster, report, errors


@partial(jax.jit, static_argnums=(0, 3))
def _prefill_then_decode(cfg, params, tokens, capacity):
    """Last-position logits of a prefill chunk into an empty slot cache,
    then the logits of one decode step (fed the prompt's first token)."""
    from repro.models import dense

    S = tokens.shape[1]
    kv = (cfg.n_layers, 1, capacity, cfg.n_kv_heads, cfg.head_dim_)
    cache = {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
             "pos_map": jnp.full((1, capacity), -1, jnp.int32)}
    logits, cache = dense.prefill_chunk(
        cfg, params, cache, dense.embed_tokens(cfg, params, tokens),
        jnp.int32(0))
    dlogits, _ = dense.decode_step(
        cfg, params, cache, dense.embed_tokens(cfg, params, tokens[:, :1]),
        jnp.full((1,), S, jnp.int32))
    V = cfg.vocab_size
    return logits[0, S - 1, :V], dlogits[0, 0, :V]


def logits_parity(cfg, params, *, prompt_len=PARITY_PROMPT, seed=0):
    """Compare the Pallas path's prefill and decode logits with the
    reference-attention path's on one random prompt. Returns
    ``{name: (max |Δ| / max |ref|, argmax agrees)}``."""
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (1, prompt_len), 1, cfg.vocab_size)
    capacity = -(-(prompt_len + 1) // 128) * 128
    out = {impl: [np.asarray(a, np.float32) for a in _prefill_then_decode(
        cfg.replace(attn_impl=impl), params, tokens, capacity)]
        for impl in ("pallas", "reference")}
    res = {}
    for i, name in enumerate(("prefill", "decode")):
        pal, ref = out["pallas"][i], out["reference"][i]
        rel = float(np.max(np.abs(pal - ref)) / np.max(np.abs(ref)))
        res[name] = (rel, int(pal.argmax()) == int(ref.argmax()))
    return res


def lowered_kernel_calls(cluster):
    """Count ``tpu_custom_call`` in the lowered fused steps of the served
    cluster: its decode step and a 128-token prefill chunk step."""
    from repro.engine import fused_step as fs

    inst = next(iter(cluster.instances.values()))
    B = inst.kv.n_slots
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    slabs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in inst.kv.slabs()]
    dec = fs.decode_only.lower(cluster.cfg, cluster.params, *slabs,
                               i32(B, 1), i32(B), f32(B), f32(B), i32(B),
                               i32(B))
    chk = fs.chunks_only.lower(cluster.cfg, cluster.params, *slabs,
                               i32(1, 128), i32(1), i32(1), i32(1), f32(1),
                               f32(1), i32(1), i32(1))
    return {"decode_only": dec.as_text().count("tpu_custom_call"),
            "chunks_only": chk.as_text().count("tpu_custom_call")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the parity prompt")
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    print(f"jax {jax.__version__}; platform {dev.platform}; device_kind "
          f"{dev.device_kind}; device count {len(devs)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this script does not run on the CPU", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.launch.serve import enable_compile_cache
    from repro.models import build_model

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    phases = Phases(CompileClock())
    cfg = get_config(ARCH).replace(attn_impl="pallas")
    print(f"config {cfg.arch_id}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, head_dim "
          f"{cfg.head_dim_}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{cfg.dtype}, attn_impl {cfg.attn_impl}", flush=True)
    params = phases.run("init", lambda: jax.block_until_ready(
        jax.jit(build_model(cfg).init)(jax.random.PRNGKey(args.seed))))
    n_params = sum(a.size for a in jax.tree.leaves(params))
    print(f"parameters: {n_params:,}", flush=True)

    cluster, report, errors = phases.run("serve", serve_phase, cfg, params)
    print(f"requests: {report.n_finished}/{report.n_total} finished, "
          f"{sum(len(h.tokens) for h in report.handles)} tokens; "
          f"migrations {len(cluster.migration_log)}, bytes "
          f"{[m['bytes'] for m in cluster.migration_log]}", flush=True)
    calls = phases.run("lower", lowered_kernel_calls, cluster)
    print(f"tpu_custom_call in lowered steps: {calls}", flush=True)
    if not all(calls.values()):
        errors.append(f"a lowered fused step holds no Mosaic kernel: {calls}")
    del cluster, report                 # free the two instances' KV slabs

    parity = phases.run("parity", logits_parity, cfg, params,
                        seed=args.seed)
    for name, (rel, same) in parity.items():
        print(f"{name} logits pallas vs reference: max|diff|/max|ref| "
              f"{rel:.6g} (tolerance {LOGIT_TOL:g}), argmax agrees {same}",
              flush=True)
        if not rel <= LOGIT_TOL:
            errors.append(f"{name} logits differ by {rel:.6g} > {LOGIT_TOL}")

    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}", flush=True)
    if errors:
        for e in errors:
            print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

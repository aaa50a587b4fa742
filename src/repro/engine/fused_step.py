"""Fused per-instance engine step: one jitted call with donated KV buffers
(DESIGN.md §9), with on-device replayable sampling and self-speculative
decoding (DESIGN.md §12).

Every function here takes the ``ModelConfig`` as a *static* jit argument
(it is a frozen, hashable dataclass), so traces are shared across all
``EngineInstance``s of a cluster — and across clusters — instead of each
instance re-jitting its own closures. An elastic spawn (§6) therefore
starts with a warm jit cache.

The SlotKVCache slabs (``k``, ``v``, ``pos_map``) are **donated**: XLA
aliases them with the corresponding outputs, so the multi-MB cache updates
in place every step instead of being functionally copied. Callers must
immediately replace their references with the returned slabs
(``SlotKVCache.swap``) — the donated inputs are dead after the call.

Token selection stays on device. Each slot samples with a key derived
*statelessly* as ``fold_in(fold_in(PRNGKey(seed), rid), position)`` —
no PRNG counter state exists anywhere, so a stream replays bit-for-bit
across runs, across step modes, and across KV migration / crash-recovery
re-prefill (the position is absolute in the request's token stream).
``temperature <= 0`` selects the exact argmax the pre-sampling engine
computed, on the un-cast logits, so greedy serving is provably unchanged.
Each entry point returns a single stacked int32 token array per step,
which the instance fetches with one blocking transfer at finalize time so
concurrent instances' steps overlap.
"""
from __future__ import annotations

from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import dense, hybrid
from repro.models import ssm as ssm_models


# ------------------------------------------------------------- sampling

def _sample_one(cfg, logits, temp, top_p, seed, rid, pos):
    """Select one token from a single logits row (padded vocab).

    Gumbel-max over the temperature-scaled, top-p-masked logits: the
    sample is an *argmax* of perturbed scores, so it inherits the same
    ulp-robustness the greedy path relies on for fused-vs-legacy and
    cross-instance (migration) bit-identity — fusion-level float noise
    only matters on exact score ties, which the Gumbel noise breaks.
    ``temp <= 0`` short-circuits to the pre-sampling argmax on the
    original-dtype logits.

    The nucleus mask is built in sorted order and moved back by a second
    sort: one stable sort of ``(-probs, iota)`` gives both the descending
    probabilities and ``order`` (the tie order of ``jnp.argsort``, with no
    gather), and since ``order`` is a permutation, sorting
    ``(order, keep_sorted)`` by ``order`` puts the mask in vocabulary
    order, bit for bit the mask a scatter would build. On a TPU a gather
    and a scatter over the whole vocabulary cost several times the sorts.

    Every entry point reaches the sampler through here, so the ``sample``
    scope names its ops (in the compiled program's op metadata) on every
    path: decode rows through ``vmap``, chunk finals through ``scan``."""
    with jax.named_scope("sample"):
        V = cfg.vocab_size
        row = logits[:V]
        greedy = jnp.argmax(row).astype(jnp.int32)
        rowf = row.astype(jnp.float32)
        t = jnp.maximum(temp, 1e-6).astype(jnp.float32)
        scaled = rowf / t
        probs = jax.nn.softmax(scaled)
        iota = lax.iota(jnp.int32, row.shape[0])
        neg, order = lax.sort((-probs, iota), num_keys=1, is_stable=True)
        sp = -neg
        # nucleus rule: keep tokens whose *exclusive* prefix mass is < top_p
        # — the top-1 token always survives (its exclusive mass is 0)
        keep_sorted = (jnp.cumsum(sp) - sp) < jnp.maximum(top_p, 1e-6)
        _, keep = lax.sort((order, keep_sorted), num_keys=1)
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), rid), pos)
        g = jax.random.gumbel(key, (row.shape[0],), jnp.float32)
        sampled = jnp.argmax(jnp.where(keep, scaled + g,
                                       -jnp.inf)).astype(jnp.int32)
        return jnp.where(temp <= 0.0, greedy, sampled)


def _sample_rows(cfg, logits, temps, top_ps, seeds, rids, pos):
    """Vectorized :func:`_sample_one` over (B, V_padded) logits rows."""
    return jax.vmap(
        lambda lg, t, p, sd, rid, ps: _sample_one(cfg, lg, t, p, sd, rid, ps)
    )(logits, temps, top_ps, seeds, rids, pos)


@partial(jax.jit, static_argnums=(0,))
def sample_tokens(cfg, logits, temps, top_ps, seeds, rids, pos):
    """Standalone batched sampler for the legacy (eager) step path: the
    same selection ops as the fused step, applied to already-materialized
    logits rows — fused-vs-legacy streams stay bit-identical because the
    logits are (PR 5 parity) and the selection is argmax-shaped."""
    return _sample_rows(cfg, logits, temps, top_ps, seeds, rids, pos)


# ------------------------------------------------------------ core steps

def _decode_core(cfg, params, k, v, pos_map, tokens, pos, temps, top_ps,
                 seeds, rids):
    """Batched decode over every slot (active rows carry real tokens,
    parked slots get the dummy write at their own next position — see
    EngineInstance.dispatch_step). Returns per-slot sampled tokens, keyed
    by each row's absolute position ``pos``."""
    x = dense.embed_tokens(cfg, params, tokens)
    logits, cache = dense.decode_step(
        cfg, params, {"k": k, "v": v, "pos_map": pos_map}, x, pos)
    toks = _sample_rows(cfg, logits[:, 0], temps, top_ps, seeds, rids, pos)
    return toks, cache["k"], cache["v"], cache["pos_map"]


def _chunk_scan(cfg, params, k, v, pos_map, toks, slots, offsets, lens,
                temps, top_ps, seeds, rids):
    """Run every prefill chunk of the plan against its own slot, scanned
    sequentially inside the jit (chunks target distinct slots, so the order
    only matters vs the decode dummy-writes, which ran first). ``toks`` is
    (N, Sq) bucket-padded chunk tokens; ``slots``/``offsets``/``lens`` are
    (N,) i32. Pad-position invalidation is folded in here — no host copy of
    the pos_map remains (ISSUE 5 satellite). Returns the per-chunk sampled
    token at each chunk's last real token — keyed by its absolute position
    ``offset + len - 1`` (meaningful only for final chunks; the host
    decides which)."""
    C = pos_map.shape[1]
    Sq = toks.shape[1]
    idx = jnp.arange(C, dtype=jnp.int32)

    def body(carry, xs):
        k, v, pos_map = carry
        t, s, off, ln, tp, pp, sd, rid = xs
        x = dense.embed_tokens(cfg, params, t[None])
        sub = {"k": lax.dynamic_slice_in_dim(k, s, 1, 1),
               "v": lax.dynamic_slice_in_dim(v, s, 1, 1),
               "pos_map": lax.dynamic_slice_in_dim(pos_map, s, 1, 0)}
        logits, sub = dense.prefill_chunk(cfg, params, sub, x, off)
        # bucket padding [off+ln, off+Sq) never becomes valid KV
        row = jnp.where((idx >= off + ln) & (idx < off + Sq), -1,
                        sub["pos_map"][0])
        k = lax.dynamic_update_slice_in_dim(k, sub["k"], s, 1)
        v = lax.dynamic_update_slice_in_dim(v, sub["v"], s, 1)
        pos_map = lax.dynamic_update_slice_in_dim(pos_map, row[None], s, 0)
        last = jnp.maximum(ln - 1, 0)
        tok = _sample_one(cfg, lax.dynamic_index_in_dim(
            logits[0], last, 0, keepdims=False), tp, pp, sd, rid, off + last)
        return (k, v, pos_map), tok

    (k, v, pos_map), ctoks = lax.scan(
        body, (k, v, pos_map),
        (toks, slots, offsets, lens, temps, top_ps, seeds, rids))
    return ctoks, k, v, pos_map


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
def decode_only(cfg, params, k, v, pos_map, tokens, pos, temps, top_ps,
                seeds, rids):
    """Decode batch, no prefill chunks. -> ((B,) tokens, k, v, pos_map)."""
    return _decode_core(cfg, params, k, v, pos_map, tokens, pos, temps,
                        top_ps, seeds, rids)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
def chunks_only(cfg, params, k, v, pos_map, toks, slots, offsets, lens,
                temps, top_ps, seeds, rids):
    """Prefill chunks, no decode. -> ((N,) tokens, k, v, pos_map)."""
    return _chunk_scan(cfg, params, k, v, pos_map, toks, slots, offsets,
                       lens, temps, top_ps, seeds, rids)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
def mixed_step(cfg, params, k, v, pos_map, tokens, pos, dtemps, dtop_ps,
               dseeds, drids, toks, slots, offsets, lens, ctemps, ctop_ps,
               cseeds, crids):
    """The LocalScheduler's full mixed plan — decode batch first (matching
    the pre-fusion execution order, so parked-slot dummy writes land before
    chunks overwrite them), then every prefill chunk — as ONE jitted call.
    -> ((B+N,) stacked tokens, k, v, pos_map)."""
    dtoks, k, v, pos_map = _decode_core(cfg, params, k, v, pos_map, tokens,
                                        pos, dtemps, dtop_ps, dseeds, drids)
    ctoks, k, v, pos_map = _chunk_scan(cfg, params, k, v, pos_map, toks,
                                       slots, offsets, lens, ctemps,
                                       ctop_ps, cseeds, crids)
    return jnp.concatenate([dtoks, ctoks]), k, v, pos_map


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
def prefill_place(cfg, params, k, v, pos_map, tokens, slot, length, temp,
                  top_p, seed, rid):
    """Whole-prompt prefill fused with the slot placement that previously
    ran as host-level ``.at[].set`` copies: forward the padded prompt,
    write its KV into ``slot``, select o_1 (keyed at absolute position
    ``length - 1``) — one call, donated buffers.
    -> (o_1 token scalar, k, v, pos_map)."""
    C = k.shape[2]
    S = tokens.shape[0]
    x = dense.embed_tokens(cfg, params, tokens[None])
    logits, cache = dense.forward_seq(cfg, params, x, jnp.arange(S),
                                      cache_capacity=C)
    k = lax.dynamic_update_slice(k, cache["k"][:, :1], (0, slot, 0, 0, 0))
    v = lax.dynamic_update_slice(v, cache["v"][:, :1], (0, slot, 0, 0, 0))
    idx = jnp.arange(C, dtype=jnp.int32)
    row = jnp.where(idx < length, idx, -1)
    pos_map = lax.dynamic_update_slice_in_dim(pos_map, row[None], slot, 0)
    last = jnp.maximum(length - 1, 0)
    tok = _sample_one(cfg, lax.dynamic_index_in_dim(
        logits[0], last, 0, keepdims=False), temp, top_p, seed, rid, last)
    return tok, k, v, pos_map


# ------------------------------------------------ ssm (Mamba-2) steps
#
# Same calling convention as the dense entries but over the {"conv","ssm"}
# decode-state slabs (DESIGN.md §13). Two differences forced by recurrence:
# the decode batch carries an explicit ``active`` mask (a recurrent update
# is irreversible, so parked slots keep their old state instead of the
# dense dummy-write trick), and chunked prefill passes each chunk's
# ``valid_len`` into the model so pad positions leave the state untouched.


def _ssm_decode_core(cfg, params, conv, ssm, tokens, pos, temps, top_ps,
                     seeds, rids, active):
    x = ssm_models.embed_tokens(cfg, params, tokens)
    logits, cache = ssm_models.decode_step(
        cfg, params, {"conv": conv, "ssm": ssm}, x, pos)
    conv = jnp.where(active[None, :, None, None], cache["conv"], conv)
    ssm = jnp.where(active[None, :, None, None, None], cache["ssm"], ssm)
    toks = _sample_rows(cfg, logits[:, 0], temps, top_ps, seeds, rids, pos)
    return toks, conv, ssm


def _ssm_chunk_scan(cfg, params, conv, ssm, toks, slots, offsets, lens,
                    temps, top_ps, seeds, rids):
    def body(carry, xs):
        conv, ssm = carry
        t, s, off, ln, tp, pp, sd, rid = xs
        x = ssm_models.embed_tokens(cfg, params, t[None])
        sub = {"conv": lax.dynamic_slice_in_dim(conv, s, 1, 1),
               "ssm": lax.dynamic_slice_in_dim(ssm, s, 1, 1)}
        logits, sub = ssm_models.prefill_chunk(cfg, params, sub, x, off,
                                               valid_len=ln)
        conv = lax.dynamic_update_slice_in_dim(conv, sub["conv"], s, 1)
        ssm = lax.dynamic_update_slice_in_dim(ssm, sub["ssm"], s, 1)
        last = jnp.maximum(ln - 1, 0)
        tok = _sample_one(cfg, lax.dynamic_index_in_dim(
            logits[0], last, 0, keepdims=False), tp, pp, sd, rid, off + last)
        return (conv, ssm), tok

    (conv, ssm), ctoks = lax.scan(
        body, (conv, ssm),
        (toks, slots, offsets, lens, temps, top_ps, seeds, rids))
    return ctoks, conv, ssm


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3))
def ssm_decode_only(cfg, params, conv, ssm, tokens, pos, temps, top_ps,
                    seeds, rids, active):
    return _ssm_decode_core(cfg, params, conv, ssm, tokens, pos, temps,
                            top_ps, seeds, rids, active)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3))
def ssm_chunks_only(cfg, params, conv, ssm, toks, slots, offsets, lens,
                    temps, top_ps, seeds, rids):
    return _ssm_chunk_scan(cfg, params, conv, ssm, toks, slots, offsets,
                           lens, temps, top_ps, seeds, rids)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3))
def ssm_mixed_step(cfg, params, conv, ssm, tokens, pos, dtemps, dtop_ps,
                   dseeds, drids, active, toks, slots, offsets, lens, ctemps,
                   ctop_ps, cseeds, crids):
    dtoks, conv, ssm = _ssm_decode_core(cfg, params, conv, ssm, tokens, pos,
                                        dtemps, dtop_ps, dseeds, drids,
                                        active)
    ctoks, conv, ssm = _ssm_chunk_scan(cfg, params, conv, ssm, toks, slots,
                                       offsets, lens, ctemps, ctop_ps,
                                       cseeds, crids)
    return jnp.concatenate([dtoks, ctoks]), conv, ssm


# --------------------------------------- hybrid (RecurrentGemma) steps
#
# The decode state is the whole hybrid cache pytree with batch == n_slots,
# passed (and donated) as ONE argument. Per-slot slice/update walk the
# structure; the ``active`` mask reverts every leaf of inactive rows (ring
# writes included — cheaper than special-casing which leaves are safe).


def _hyb_rows(act, new, old, axis):
    shape = [1] * new.ndim
    shape[axis] = act.shape[0]
    return jnp.where(act.reshape(shape), new, old)


def _hyb_mask(old, new, act):
    out = {"groups": {k: _hyb_rows(act, new["groups"][k], old["groups"][k], 1)
                      for k in old["groups"]},
           "pos_map": _hyb_rows(act, new["pos_map"], old["pos_map"], 0)}
    if "tail" in old:
        out["tail"] = {k: _hyb_rows(act, new["tail"][k], old["tail"][k], 1)
                       for k in old["tail"]}
    return out


def _hyb_slice(cache, s):
    sub = {"groups": {k: lax.dynamic_slice_in_dim(a, s, 1, 1)
                      for k, a in cache["groups"].items()},
           "pos_map": lax.dynamic_slice_in_dim(cache["pos_map"], s, 1, 0)}
    if "tail" in cache:
        sub["tail"] = {k: lax.dynamic_slice_in_dim(a, s, 1, 1)
                       for k, a in cache["tail"].items()}
    return sub


def _hyb_update(cache, sub, s):
    out = {"groups": {k: lax.dynamic_update_slice_in_dim(
               cache["groups"][k], sub["groups"][k], s, 1)
               for k in cache["groups"]},
           "pos_map": lax.dynamic_update_slice_in_dim(
               cache["pos_map"], sub["pos_map"], s, 0)}
    if "tail" in cache:
        out["tail"] = {k: lax.dynamic_update_slice_in_dim(
            cache["tail"][k], sub["tail"][k], s, 1) for k in cache["tail"]}
    return out


def _hyb_decode_core(cfg, params, cache, tokens, pos, temps, top_ps, seeds,
                     rids, active):
    x = hybrid.embed_tokens(cfg, params, tokens)
    logits, new_cache = hybrid.decode_step(cfg, params, cache, x, pos)
    new_cache = _hyb_mask(cache, new_cache, active)
    toks = _sample_rows(cfg, logits[:, 0], temps, top_ps, seeds, rids, pos)
    return toks, new_cache


def _hyb_chunk_scan(cfg, params, cache, toks, slots, offsets, lens, temps,
                    top_ps, seeds, rids):
    def body(cache, xs):
        t, s, off, ln, tp, pp, sd, rid = xs
        x = hybrid.embed_tokens(cfg, params, t[None])
        sub = _hyb_slice(cache, s)
        logits, sub = hybrid.prefill_chunk(cfg, params, sub, x, off,
                                           valid_len=ln)
        cache = _hyb_update(cache, sub, s)
        last = jnp.maximum(ln - 1, 0)
        tok = _sample_one(cfg, lax.dynamic_index_in_dim(
            logits[0], last, 0, keepdims=False), tp, pp, sd, rid, off + last)
        return cache, tok

    cache, ctoks = lax.scan(
        body, cache, (toks, slots, offsets, lens, temps, top_ps, seeds, rids))
    return ctoks, cache


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def hybrid_decode_only(cfg, params, cache, tokens, pos, temps, top_ps,
                       seeds, rids, active):
    return _hyb_decode_core(cfg, params, cache, tokens, pos, temps, top_ps,
                            seeds, rids, active)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def hybrid_chunks_only(cfg, params, cache, toks, slots, offsets, lens,
                       temps, top_ps, seeds, rids):
    return _hyb_chunk_scan(cfg, params, cache, toks, slots, offsets, lens,
                           temps, top_ps, seeds, rids)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def hybrid_mixed_step(cfg, params, cache, tokens, pos, dtemps, dtop_ps,
                      dseeds, drids, active, toks, slots, offsets, lens,
                      ctemps, ctop_ps, cseeds, crids):
    dtoks, cache = _hyb_decode_core(cfg, params, cache, tokens, pos, dtemps,
                                    dtop_ps, dseeds, drids, active)
    ctoks, cache = _hyb_chunk_scan(cfg, params, cache, toks, slots, offsets,
                                   lens, ctemps, ctop_ps, cseeds, crids)
    return jnp.concatenate([dtoks, ctoks]), cache


# ---------------------------------------------------- family dispatch

_OPS = {
    "dense": SimpleNamespace(decode_only=decode_only, chunks_only=chunks_only,
                             mixed_step=mixed_step),
    "ssm": SimpleNamespace(decode_only=ssm_decode_only,
                           chunks_only=ssm_chunks_only,
                           mixed_step=ssm_mixed_step),
    "hybrid": SimpleNamespace(decode_only=hybrid_decode_only,
                              chunks_only=hybrid_chunks_only,
                              mixed_step=hybrid_mixed_step),
}


def ops_for(family: str):
    """The family's fused-step entry points. All share the calling
    convention ``op(cfg, params, *slots.slabs(), *step_args)`` and return
    ``(tokens, *new_slabs)`` — the caller swaps the slabs back via
    ``StateSlots.swap`` (they were donated)."""
    return _OPS[family]


# -------------------------------------------- self-speculative decoding

@partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(4, 5, 6))
def spec_decode(cfg, draft_layers, k_draft, params, k, v, pos_map, tokens,
                pos, temps, top_ps, seeds, rids, active):
    """One self-speculative decode round for the whole slot batch, inside
    a single jitted call (DESIGN.md §12).

    Draft: ``k_draft`` sequential batched decode steps through only the
    first ``draft_layers`` layers — the params pytree stacks every layer on
    the leading ``lax.scan`` axis, so the truncated model is a tree-slice.
    Each draft token at absolute position ``p`` samples with the *same*
    key the full model would use at ``p`` (Gumbel-max coupling), so when
    the truncated logits agree with the full logits the draft is accepted
    with certainty. Draft KV lives only in the scan carry and is
    discarded.

    Verify: one full-layer pass per slot over ``[t0, d1..dk]`` at per-row
    offsets (a chunked prefill with a per-slot offset — the shared-offset
    ``dense.prefill_chunk`` runs on a single-row sub-cache inside the
    scan), sampling the target token at every position with its own
    positional key. The longest prefix of drafts agreeing with the
    targets is accepted; the emitted tokens are exactly the targets
    ``g_0..g_a`` — i.e. **bit-identical to the non-speculative stream**,
    because every target was sampled from the same context with the same
    key as sequential decode would. KV positions past the accepted prefix
    are invalidated; rows with ``active == False`` (parked slots) are
    written back untouched.

    Callers must ensure every active row satisfies
    ``pos + k_draft + 1 <= capacity`` (the instance falls back to plain
    decode otherwise). -> ((B, k_draft+2) packed [a, g_0..g_k], k, v,
    pos_map)."""
    B, C = pos_map.shape
    dparams = dict(params)
    dparams["layers"] = jax.tree.map(lambda a: a[:draft_layers],
                                     params["layers"])

    def draft_body(carry, _):
        dk, dv, dpm, tok, p = carry
        x = dense.embed_tokens(cfg, dparams, tok)
        logits, cache = dense.decode_step(
            cfg, dparams, {"k": dk, "v": dv, "pos_map": dpm}, x, p)
        nxt = _sample_rows(cfg, logits[:, 0], temps, top_ps, seeds, rids, p)
        return (cache["k"], cache["v"], cache["pos_map"], nxt[:, None],
                p + 1), nxt

    _, drafts = lax.scan(
        draft_body,
        (k[:draft_layers], v[:draft_layers], pos_map, tokens, pos),
        None, length=k_draft)
    ver_tokens = jnp.concatenate([tokens, drafts.T], axis=1)     # (B, k+1)
    idx = jnp.arange(C, dtype=jnp.int32)
    rel = jnp.arange(k_draft + 1, dtype=jnp.int32)

    def ver_body(carry, xs):
        k_, v_, pm = carry
        vt, s, off, t_, tp, sd, rid, act = xs
        # inactive (parked) rows still flow through for static shapes, but
        # clamp their offset into bounds and write back their original
        # slice — a strict no-op on their KV
        off_c = jnp.minimum(off, C - (k_draft + 1))
        sub0 = {"k": lax.dynamic_slice_in_dim(k_, s, 1, 1),
                "v": lax.dynamic_slice_in_dim(v_, s, 1, 1),
                "pos_map": lax.dynamic_slice_in_dim(pm, s, 1, 0)}
        x = dense.embed_tokens(cfg, params, vt[None])
        logits, sub1 = dense.prefill_chunk(cfg, params, sub0, x, off_c)
        g = jax.vmap(lambda lg, pp: _sample_one(cfg, lg, t_, tp, sd, rid,
                                                pp))(logits[0], off_c + rel)
        agree = jnp.cumprod((vt[1:] == g[:-1]).astype(jnp.int32))
        a = jnp.sum(agree)                       # accepted drafts, 0..k
        # valid context after the round: [0, off + a]; rejected draft
        # positions (off+a+1 .. off+k) revert to invalid
        row = jnp.where((idx > off_c + a) & (idx <= off_c + k_draft), -1,
                        sub1["pos_map"][0])
        kw = jnp.where(act, sub1["k"], sub0["k"])
        vw = jnp.where(act, sub1["v"], sub0["v"])
        roww = jnp.where(act, row, sub0["pos_map"][0])
        k_ = lax.dynamic_update_slice_in_dim(k_, kw, s, 1)
        v_ = lax.dynamic_update_slice_in_dim(v_, vw, s, 1)
        pm = lax.dynamic_update_slice_in_dim(pm, roww[None], s, 0)
        return (k_, v_, pm), jnp.concatenate([a[None].astype(jnp.int32), g])

    slots = jnp.arange(B, dtype=jnp.int32)
    (k, v, pos_map), packed = lax.scan(
        ver_body, (k, v, pos_map),
        (ver_tokens, slots, pos, temps, top_ps, seeds, rids, active))
    return packed, k, v, pos_map

"""A stateless engine instance: real JAX compute (dense-family models), a
slot-granular KV cache and the Arrow local scheduler. "Stateless" in the
paper's sense — the instance carries no prefill/decode role; it executes
whatever sub-requests the global scheduler hands it.

Execution model (DESIGN.md §9): the LocalScheduler's mixed plan — the full
decode batch plus every prefill chunk — runs as ONE jitted call with
donated KV buffers (``repro.engine.fused_step``). ``dispatch_step`` launches
the call and returns immediately with the device-side token array;
``finalize_step`` performs the step's single blocking transfer and advances
the host bookkeeping, so a cluster can dispatch every instance before
fetching any — instances' steps overlap. ``step_mode="legacy"`` preserves
the pre-fusion per-rid path (per-request ``int(jnp.argmax(...))`` syncs,
functional cache copies, host pos_map round-trips) as the benchmark
baseline (benchmarks/bench_engine_step.py).
"""
from __future__ import annotations

import time
import zlib
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.local_scheduler import LocalScheduler
from repro.engine import fused_step as fs
from repro.engine.state_slots import make_state_slots
from repro.models import build_model


class CorruptPayload(RuntimeError):
    """Typed transfer-integrity failure (DESIGN.md §14): the migration
    payload's checksum does not match what the exporter computed. Raised by
    ``import_state`` *before* any slot is allocated, so the importer's state
    is untouched; the cluster treats it as a failed transfer attempt and
    retries (source KV is retained until acknowledged)."""

    def __init__(self, iid: int, rid: int):
        super().__init__(f"instance {iid}: corrupt migration payload for "
                         f"rid {rid}")
        self.iid = iid
        self.rid = rid


@partial(jax.profiler.annotate_function, name="arrow.migrate.checksum")
def state_checksum(payload) -> int:
    """CRC32 over the migration payload's raw bytes, chained across arrays.
    Computed at ``export_state`` time and verified at ``import_state`` time —
    the end-to-end integrity check the §14 retry ladder keys off."""
    crc = 0
    for p in payload:
        crc = zlib.crc32(np.asarray(p).tobytes(), crc)
    return crc


class NoFreeSlots(RuntimeError):
    """Typed admission failure: the slot cache is full. Raised instead of
    the old ``assert slot is not None`` crash so callers (the cluster, the
    profiler) can keep the request queued and retry once a slot frees or a
    retained prefix is evicted."""

    def __init__(self, iid: int, rid: int):
        super().__init__(f"instance {iid}: no free KV slot for rid {rid}")
        self.iid = iid
        self.rid = rid


@dataclass
class ChunkWork:
    """One prefill chunk of a fused step."""

    rid: int
    offset: int
    length: int               # real tokens in the chunk
    tokens: np.ndarray        # the chunk's token ids, shape (length,)
    total_len: int            # the request's full prompt length


class PendingStep:
    """A dispatched step whose token array still lives on device. Groups
    are (chunks, device_tokens) pairs; when ``decode_in_group0`` the first
    group carries the decode batch's per-slot tokens stacked ahead of its
    chunk tokens. ``spec`` holds a speculative round's packed (B, k+2)
    ``[a, g_0..g_k]`` array. ``fetch`` is the step's blocking transfer;
    ``ready`` polls the device without blocking, which is what lets the
    cluster's async step collect finished instances while the rest keep
    computing. ``step`` is the instance's own step number, which links the
    step's dispatch and fetch spans in a profiler trace."""

    def __init__(self, decode_rids: List[int],
                 groups: List[Tuple[List[ChunkWork], Any]],
                 spec: Any = None, decode_in_group0: bool = True,
                 step: int = 0):
        self.decode_rids = decode_rids
        self.groups = groups
        self.spec = spec
        self.decode_in_group0 = decode_in_group0
        self.step = step

    @property
    def entry(self) -> str:
        """The fused-step entry point that carried the step's first group."""
        if self.spec is not None:
            return "spec_decode"
        if not (self.decode_in_group0 and self.decode_rids):
            return "chunks_only"
        return "mixed_step" if self.groups[0][0] else "decode_only"

    def ready(self) -> bool:
        if self.spec is not None and not self.spec.is_ready():
            return False
        return all(arr.is_ready() for _, arr in self.groups)

    def fetch(self) -> Tuple[Optional[np.ndarray], List[np.ndarray]]:
        spec_np = None if self.spec is None else np.asarray(self.spec)
        parts = [arr for _, arr in self.groups]
        if not parts:
            return spec_np, []
        if len(parts) == 1:
            return spec_np, [np.asarray(parts[0])]
        # several padded-width groups: concatenate on device so the step
        # still pays exactly one blocking transfer
        flat = np.asarray(jnp.concatenate(parts))
        out, i = [], 0
        for p in parts:
            out.append(flat[i:i + p.shape[0]])
            i += p.shape[0]
        return spec_np, out


class _EagerStep:
    """Legacy-mode stand-in: results were computed synchronously."""

    entry = "legacy"

    def __init__(self, decode_out: Dict[int, int],
                 chunk_out: List[Tuple[int, Optional[int]]], step: int):
        self.decode_out = decode_out
        self.chunk_out = chunk_out
        self.step = step

    def ready(self) -> bool:
        return True


def _bucket32(n: int, cap: int) -> int:
    return min(-(-n // 32) * 32, cap)


class EngineInstance:
    def __init__(self, iid: int, cfg: ModelConfig, params, *,
                 n_slots: int = 8, capacity: int = 256,
                 chunk_tokens: Optional[int] = None,
                 step_mode: str = "fused", run_seed: int = 0,
                 speculate: int = 0, draft_layers: Optional[int] = None):
        assert cfg.family in ("dense", "ssm", "hybrid"), \
            f"no engine decode-state for family {cfg.family!r}"
        assert step_mode in ("fused", "legacy"), step_mode
        if cfg.family != "dense":
            assert step_mode == "fused", \
                "non-dense families have no legacy (pre-fusion) step path"
        self.run_seed = int(run_seed)
        self.speculate = int(speculate)
        self.draft_layers = (int(draft_layers) if draft_layers
                             else max(1, cfg.n_layers // 2))
        self.iid = iid
        self.cfg = cfg
        self.params = params
        self.model = build_model(cfg)
        self.capacity = capacity
        self.step_mode = step_mode
        self.kv = make_state_slots(cfg, n_slots, capacity)
        self._ops = fs.ops_for(cfg.family)
        if not self.kv.supports_speculation:
            # a rejected draft cannot roll back a recurrent state update,
            # so speculation is cleanly disabled for constant-state families
            # (DESIGN.md §13) — the stream is the plain sequential one
            self.speculate = 0
        if self.speculate:
            assert step_mode == "fused", \
                "self-speculative decoding requires the fused step path"
            assert 1 <= self.draft_layers < cfg.n_layers, \
                "draft_layers must be a strict truncation of the model"
        self.local = LocalScheduler(
            iid, token_budget=chunk_tokens or capacity,
            mixed_chunk_budget=chunk_tokens or 2048,
            kv_capacity_tokens=n_slots * capacity)
        if step_mode == "legacy":
            # pre-fusion per-instance jits (the benchmark baseline)
            self._prefill_fn = jax.jit(
                lambda p, b: self.model.prefill(p, b, cache_capacity=capacity))
            self._decode_fn = jax.jit(self.model.decode)
            from repro.models import dense as _dense
            self._chunk_fn = jax.jit(
                lambda p, cache, x, off: _dense.prefill_chunk(cfg, p, cache,
                                                              x, off))
        # request bookkeeping
        self.last_token: Dict[int, int] = {}
        self.generated: Dict[int, List[int]] = {}
        self.step_no = 0                  # steps dispatched so far

    # ------------------------------------------------------------- slots
    def alloc_slot(self, rid: int) -> int:
        slot = self.kv.alloc(rid)
        if slot is None:
            raise NoFreeSlots(self.iid, rid)
        return slot

    # ---------------------------------------------------------- sampling
    def set_sampling(self, rid: int, sp) -> None:
        """Record a request's ``SamplingParams`` as slot state so it
        travels with the KV on migration/recovery. None/greedy clears to
        the default (exact argmax)."""
        if sp is None or sp.greedy:
            self.kv.samp_of.pop(rid, None)
        else:
            seed = self.run_seed if sp.seed is None else int(sp.seed)
            self.kv.samp_of[rid] = (float(sp.temperature), float(sp.top_p),
                                    seed)

    def _samp_of(self, rid: int) -> Tuple[float, float, int]:
        return self.kv.samp_of.get(rid, (0.0, 1.0, self.run_seed))

    def _slot_samp_arrays(self, decode_rids: List[int]):
        """Per-slot (temps, top_ps, seeds, rids) for a decode batch; rows
        whose slot is not decoding this step keep greedy defaults (their
        sampled token is never read)."""
        B = self.kv.n_slots
        temps = np.zeros((B,), np.float32)
        tops = np.ones((B,), np.float32)
        seeds = np.zeros((B,), np.int32)
        rids = np.zeros((B,), np.int32)
        for rid in decode_rids:
            s = self.kv.slot_of[rid]
            t, p, sd = self._samp_of(rid)
            temps[s], tops[s] = t, p
            seeds[s] = sd & 0x7FFFFFFF
            rids[s] = rid & 0x7FFFFFFF
        return temps, tops, seeds, rids

    def _sample_row(self, row, t: float, p: float, sd: int, rid: int,
                    pos: int) -> int:
        """Single-row selection for the legacy (eager) paths, via the same
        jitted sampler the fused step uses — legacy and fused streams stay
        bit-identical under sampling, not just under argmax."""
        return int(fs.sample_tokens(
            self.cfg, row[None],
            jnp.asarray([t], jnp.float32), jnp.asarray([p], jnp.float32),
            jnp.asarray([sd], jnp.int32), jnp.asarray([rid], jnp.int32),
            jnp.asarray([pos], jnp.int32))[0])

    # ----------------------------------------------------------- prefill
    def run_prefill(self, rid: int, prompt: np.ndarray) -> int:
        """Whole-prompt prefill; returns the first output token (o_1).
        Prompts are right-padded to 32-token buckets so jit traces are
        reused across lengths (causal masking keeps the live positions
        exact). Raises :class:`NoFreeSlots` when the cache is full."""
        S = len(prompt)
        if self.cfg.family != "dense":
            # constant-state families prefill via the chunk path: the slot
            # starts from zero recurrent state (the release invariant) and
            # the whole prompt scans as one fused chunk
            return self.run_prefill_chunk(rid, np.asarray(prompt, np.int32),
                                          0, S)
        S_pad = _bucket32(S, self.capacity)
        padded = np.zeros((S_pad,), np.int32)
        padded[:S] = prompt
        self.alloc_slot(rid)
        t, p, sd = self._samp_of(rid)
        sd &= 0x7FFFFFFF
        rid_m = rid & 0x7FFFFFFF
        if self.step_mode == "legacy":
            batch = {"tokens": jnp.asarray(padded)[None]}
            logits, cache = self._prefill_fn(self.params, batch)
            self.kv.place(rid, cache["k"][:, 0], cache["v"][:, 0], S)
            tok = self._sample_row(logits[0, S - 1], t, p, sd, rid_m, S - 1)
        else:
            s = self.kv.slot_of[rid]
            tok_arr, k, v, pm = fs.prefill_place(
                self.cfg, self.params, *self.kv.slabs(),
                jnp.asarray(padded), s, S, t, p, sd, rid_m)
            self.kv.swap(k, v, pm)
            self.kv.len_of[rid] = S
            tok = int(tok_arr)
        self.last_token[rid] = tok
        self.generated[rid] = [tok]
        return tok

    def begin_cached_prefill(self, rid: int, src_rid: int,
                             cached_len: int) -> None:
        """Prefix reuse (DESIGN.md §7): seed ``rid``'s slot with the first
        ``cached_len`` positions of ``src_rid``'s retained KV; subsequent
        chunks start at ``offset == cached_len``."""
        self.alloc_slot(rid)
        self.kv.copy_prefix(src_rid, rid, cached_len)

    def run_prefill_chunk(self, rid: int, chunk: np.ndarray, offset: int,
                          total_len: int) -> Optional[int]:
        """Chunked prefill (§5.4): process prompt tokens [offset, offset+len)
        against this request's slot cache. Returns o_1 on the final chunk,
        else None. (Single-chunk convenience over dispatch/finalize.)"""
        if rid not in self.kv.slot_of:
            if offset != 0:
                # a mid-prompt chunk against an unseeded slot would attend
                # garbage — fail loudly (seed via begin_cached_prefill or
                # earlier chunks), matching the pre-fusion KeyError
                raise KeyError(
                    f"rid {rid} has no KV slot at chunk offset {offset}")
            self.alloc_slot(rid)
        cw = ChunkWork(rid, offset, len(chunk),
                       np.asarray(chunk, np.int32), total_len)
        pending = self.dispatch_step([], [cw])
        _, chunk_out = self.finalize_step(pending)
        return chunk_out[0][1]

    # ------------------------------------------------------------ decode
    def run_decode_iteration(self, rids: List[int]) -> Dict[int, int]:
        """One token for each running request. Returns rid -> token.
        (Decode-only convenience over dispatch/finalize.)"""
        if not rids:
            return {}
        pending = self.dispatch_step(list(rids), [])
        decode_out, _ = self.finalize_step(pending)
        return decode_out

    # ------------------------------------------------------- fused step
    @partial(jax.profiler.annotate_function, name="arrow.dispatch.launch")
    def dispatch_step(self, decode_rids: List[int],
                      chunks: Sequence[ChunkWork]):
        """Launch this instance's whole iteration — the decode batch plus
        every prefill chunk — on device and return without blocking. The
        KV slabs are donated into the call and swapped for the aliased
        outputs immediately; token ids stay on device until
        :meth:`finalize_step`."""
        if not decode_rids and not chunks:
            return None
        self.step_no += 1
        if self.step_mode == "legacy":
            return self._legacy_step(decode_rids, chunks)
        dec_args = None
        spec_arr = None
        # speculative round: every active row must fit its k drafts plus
        # the bonus token; otherwise fall back to plain decode this step
        use_spec = bool(self.speculate and decode_rids and
                        all(self.kv.len_of[r] + self.speculate + 1
                            <= self.capacity for r in decode_rids))
        if decode_rids:
            B = self.kv.n_slots
            tokens = np.zeros((B, 1), np.int32)
            pos = np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            # Inactive-but-occupied slots (e.g. parked awaiting migration)
            # still get a batched dummy write; aim it at the slot's own next
            # position, which any real future decode/chunk overwrites before
            # attending to it. (The speculative path instead masks parked
            # rows out via ``active`` and writes them back untouched.)
            for rid, s in self.kv.slot_of.items():
                pos[s] = min(self.kv.len_of.get(rid, 0), self.capacity - 1)
            for rid in decode_rids:
                s = self.kv.slot_of[rid]
                tokens[s, 0] = self.last_token[rid]
                pos[s] = self.kv.len_of[rid]
                active[s] = True
            samp = tuple(jnp.asarray(a)
                         for a in self._slot_samp_arrays(decode_rids))
            if use_spec:
                spec_arr, k, v, pm = fs.spec_decode(
                    self.cfg, self.draft_layers, self.speculate,
                    self.params, *self.kv.slabs(), jnp.asarray(tokens),
                    jnp.asarray(pos), *samp, jnp.asarray(active))
                self.kv.swap(k, v, pm)
            else:
                dec_args = (jnp.asarray(tokens), jnp.asarray(pos)) + samp
                if self.kv.needs_active_mask:
                    # recurrent state has no harmless dummy-write: parked
                    # slots are masked out inside the fused step instead
                    dec_args += (jnp.asarray(active),)
        groups: List[Tuple[List[ChunkWork], Any]] = []
        for gi, (Sq, group) in enumerate(self._group_chunks(chunks)):
            n = len(group)
            ctoks = np.zeros((n, Sq), np.int32)
            slots = np.zeros((n,), np.int32)
            offsets = np.zeros((n,), np.int32)
            lens = np.zeros((n,), np.int32)
            ctemps = np.zeros((n,), np.float32)
            ctops = np.ones((n,), np.float32)
            cseeds = np.zeros((n,), np.int32)
            crids = np.zeros((n,), np.int32)
            for i, cw in enumerate(group):
                ctoks[i, :cw.length] = cw.tokens
                slots[i] = self.kv.slot_of[cw.rid]
                offsets[i] = cw.offset
                lens[i] = cw.length
                t, p, sd = self._samp_of(cw.rid)
                ctemps[i], ctops[i] = t, p
                cseeds[i] = sd & 0x7FFFFFFF
                crids[i] = cw.rid & 0x7FFFFFFF
            c_args = (jnp.asarray(ctoks), jnp.asarray(slots),
                      jnp.asarray(offsets), jnp.asarray(lens),
                      jnp.asarray(ctemps), jnp.asarray(ctops),
                      jnp.asarray(cseeds), jnp.asarray(crids))
            if gi == 0 and dec_args is not None:
                out = self._ops.mixed_step(
                    self.cfg, self.params, *self.kv.slabs(), *dec_args,
                    *c_args)
            else:
                out = self._ops.chunks_only(
                    self.cfg, self.params, *self.kv.slabs(), *c_args)
            self.kv.swap(*out[1:])
            groups.append((group, out[0]))
        if not groups and dec_args is not None:
            out = self._ops.decode_only(
                self.cfg, self.params, *self.kv.slabs(), *dec_args)
            self.kv.swap(*out[1:])
            groups.append(([], out[0]))
        return PendingStep(list(decode_rids), groups, spec=spec_arr,
                           decode_in_group0=dec_args is not None,
                           step=self.step_no)

    @partial(jax.profiler.annotate_function, name="arrow.fetch.wait")
    def finalize_step(self, pending) -> Tuple[Dict[int, Any],
                                              List[Tuple[int, Optional[int]]]]:
        """Fetch the step's stacked token array (the one blocking transfer)
        and advance host bookkeeping. Returns (decode rid->token — or
        rid->[tokens] for a speculative round — and per-chunk (rid,
        o_1|None) in dispatch order)."""
        if pending is None:
            return {}, []
        if isinstance(pending, _EagerStep):
            return pending.decode_out, pending.chunk_out
        decode_out: Dict[int, Any] = {}
        chunk_out: List[Tuple[int, Optional[int]]] = []
        spec_np, arrays = pending.fetch()
        if spec_np is not None:
            for rid in pending.decode_rids:
                s = self.kv.slot_of[rid]
                a = int(spec_np[s, 0])
                toks = [int(x) for x in spec_np[s, 1:a + 2]]
                self.kv.advance(rid, len(toks))
                self.last_token[rid] = toks[-1]
                self.generated[rid].extend(toks)
                decode_out[rid] = toks
        for gi, ((group, _), a) in enumerate(zip(pending.groups, arrays)):
            base = 0
            if gi == 0 and pending.decode_in_group0 and pending.decode_rids:
                for rid in pending.decode_rids:
                    s = self.kv.slot_of[rid]
                    tok = int(a[s])
                    self.kv.advance(rid)
                    self.last_token[rid] = tok
                    self.generated[rid].append(tok)
                    decode_out[rid] = tok
                base = self.kv.n_slots
            for i, cw in enumerate(group):
                end = cw.offset + cw.length
                if end >= cw.total_len:
                    self.kv.len_of[cw.rid] = cw.total_len
                    tok = int(a[base + i])
                    self.last_token[cw.rid] = tok
                    self.generated[cw.rid] = [tok]
                    chunk_out.append((cw.rid, tok))
                else:
                    self.kv.len_of[cw.rid] = end
                    chunk_out.append((cw.rid, None))
        return decode_out, chunk_out

    def _group_chunks(self, chunks: Sequence[ChunkWork]
                      ) -> List[Tuple[int, List[ChunkWork]]]:
        """Group the plan's chunks by padded width so each group scans with
        one static shape. A chunk's width is its 32-bucket clipped to the
        slot tail (offset + width <= capacity), so the in-jit
        dynamic_update_slice can never clamp; in the common case every
        chunk shares one bucket and the step is a single call."""
        by_w: Dict[int, List[ChunkWork]] = {}
        for cw in chunks:
            w = min(_bucket32(cw.length, self.capacity),
                    self.capacity - cw.offset)
            by_w.setdefault(w, []).append(cw)
        return [(w, g) for w, g in by_w.items()]

    # -------------------------------------------------- legacy baseline
    def _legacy_step(self, decode_rids: List[int],
                     chunks: Sequence[ChunkWork]) -> _EagerStep:
        decode_out = self._legacy_decode(decode_rids) if decode_rids else {}
        chunk_out = [(cw.rid, self._legacy_chunk(cw)) for cw in chunks]
        return _EagerStep(decode_out, chunk_out, self.step_no)

    def _legacy_decode(self, rids: List[int]) -> Dict[int, int]:
        """Pre-fusion decode, kept verbatim as the bench baseline: the
        full-cache functional copy (no donation) plus an eager logits
        fetch per iteration."""
        B = self.kv.n_slots
        tokens = np.zeros((B, 1), np.int32)
        pos = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        for rid, s in self.kv.slot_of.items():
            pos[s] = min(self.kv.len_of.get(rid, 0), self.capacity - 1)
        for rid in rids:
            s = self.kv.slot_of[rid]
            tokens[s, 0] = self.last_token[rid]
            pos[s] = self.kv.len_of[rid]
            active[s] = True
        batch = {"token": jnp.asarray(tokens), "pos": jnp.asarray(pos)}
        logits, cache = self._decode_fn(self.params,
                                        self.kv.as_model_cache(), batch)
        self.kv.update_from_model_cache(cache)
        out: Dict[int, int] = {}
        temps, tops, seeds, rids_arr = self._slot_samp_arrays(rids)
        arg = np.asarray(fs.sample_tokens(
            self.cfg, logits[:, 0], jnp.asarray(temps), jnp.asarray(tops),
            jnp.asarray(seeds), jnp.asarray(rids_arr), jnp.asarray(pos)))
        for rid in rids:
            s = self.kv.slot_of[rid]
            tok = int(arg[s])
            self.kv.advance(rid)
            self.last_token[rid] = tok
            self.generated[rid].append(tok)
            out[rid] = tok
        return out

    def _legacy_chunk(self, cw: ChunkWork) -> Optional[int]:
        """Pre-fusion chunked prefill: per-chunk host pos_map round-trip
        (writable np copy + three ``.at[].set`` writes back)."""
        from repro.models import dense as _dense
        rid, offset, ln = cw.rid, cw.offset, cw.length
        s = self.kv.slot_of[rid]
        ln_pad = min(-(-ln // 32) * 32, self.capacity - offset)
        padded = np.zeros((ln_pad,), np.int32)
        padded[:ln] = cw.tokens
        x = _dense.embed_tokens(self.cfg, self.params,
                                jnp.asarray(padded)[None])
        sub = {"k": self.kv.k[:, s:s + 1], "v": self.kv.v[:, s:s + 1],
               "pos_map": self.kv.pos_map[s:s + 1]}
        logits, sub = self._chunk_fn(self.params, sub, x, jnp.int32(offset))
        # write back; invalidate pad positions in the pos_map
        pm = np.array(sub["pos_map"][0])          # writable copy
        pm[offset + ln: offset + ln_pad] = -1
        self.kv.k = self.kv.k.at[:, s].set(sub["k"][:, 0])
        self.kv.v = self.kv.v.at[:, s].set(sub["v"][:, 0])
        self.kv.pos_map = self.kv.pos_map.at[s].set(jnp.asarray(pm))
        self.kv.len_of[rid] = offset + ln
        if offset + ln >= cw.total_len:
            self.kv.len_of[rid] = cw.total_len
            t, p, sd = self._samp_of(rid)
            tok = self._sample_row(logits[0, ln - 1], t, p,
                                   sd & 0x7FFFFFFF, rid & 0x7FFFFFFF,
                                   offset + ln - 1)
            self.last_token[rid] = tok
            self.generated[rid] = [tok]
            return tok
        return None

    # --------------------------------------------------------- transfer
    @partial(jax.profiler.annotate_function, name="arrow.migrate.export")
    def export_state(self, rid: int):
        """Family-agnostic migration export: (payload host arrays, context
        length, last token, generated tokens). ``sum(p.nbytes)`` over the
        payload is the real wire size — O(L) for dense, O(1) for ssm/hybrid
        (DESIGN.md §13)."""
        payload, L = self.kv.extract_state(rid)
        return payload, L, self.last_token[rid], self.generated[rid]

    @partial(jax.profiler.annotate_function, name="arrow.migrate.import")
    def import_state(self, rid: int, payload, L: int, last_token: int,
                     generated: List[int], sampling=None,
                     checksum: Optional[int] = None) -> bool:
        # Verify before alloc so a corrupt payload leaves the importer's
        # state untouched and the sender can simply retry (DESIGN.md §14).
        if checksum is not None and state_checksum(payload) != checksum:
            raise CorruptPayload(self.iid, rid)
        if self.kv.alloc(rid) is None:
            return False
        if sampling is not None:
            # the source slot's sampling state rides along with the state,
            # so a migrated stream keeps its key derivation (DESIGN.md §12)
            self.kv.samp_of[rid] = tuple(sampling)
        self.kv.place_state(rid, payload, L)
        self.last_token[rid] = last_token
        self.generated[rid] = list(generated)
        return True

    def export_kv(self, rid: int):
        """Dense-layout export kept for compatibility (tests, tools)."""
        k, v, L = self.kv.extract(rid)
        return k, v, L, self.last_token[rid], self.generated[rid]

    def import_kv(self, rid: int, k, v, L: int, last_token: int,
                  generated: List[int], sampling=None) -> bool:
        return self.import_state(rid, [k, v], L, last_token, generated,
                                 sampling=sampling)

    def drop(self, rid: int) -> None:
        if rid in self.kv.slot_of:
            self.kv.release(rid)
        self.last_token.pop(rid, None)

    # -------------------------------------------------------- profiling
    def profile_prefill(self, lengths=(16, 32, 64, 128)) -> List[Tuple[int, float]]:
        """Real wall-clock profiling pass for the TTFT predictor (paper §5.3:
        'profiles each instance's prefill processing capability when the
        cluster is first launched'). Raises :class:`NoFreeSlots` when asked
        to profile an instance whose slot cache is already full."""
        if not self.kv.free:
            raise NoFreeSlots(self.iid, -1)
        samples = []
        for L in lengths:
            if L > self.capacity:
                continue
            prompt = np.ones((L,), np.int32)
            self.run_prefill(-1, prompt)          # warm-up/compile
            self.drop(-1)
            t0 = time.perf_counter()
            self.run_prefill(-1, prompt)
            dt = time.perf_counter() - t0
            self.drop(-1)
            samples.append((L, dt))
        return samples

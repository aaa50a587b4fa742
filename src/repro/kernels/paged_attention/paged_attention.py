"""Paged decode attention — Pallas TPU kernel.

TPU-native design notes (vs. the CUDA PagedAttention of vLLM):
  * The page table and context lengths ride in **scalar prefetch** memory
    (SMEM) via ``pltpu.PrefetchScalarGridSpec``: the BlockSpec index map reads
    ``page_table[b, ip]`` to pick which KV page the next HBM→VMEM DMA fetches.
    This is the TPU analogue of vLLM's pointer-chasing warp loads — the Mosaic
    pipeline overlaps the gathered page DMA with compute on the previous page.
  * grid = (B, num_pages); the last axis is sequential, carrying the
    online-softmax state (m, l, acc) for every query head of one sequence in
    VMEM scratch.
  * One DMA fetches a whole page, all kv heads: the K/V block is
    ``(1, page, Hk, D)``, whose last two dimensions are the array's own, as
    Mosaic's tiling rule requires. A block of one kv head would put a 1 on the
    second-minor axis, which the compiler refuses.
  * GQA is folded inside the kernel with no KV duplication: the page is
    viewed as ``(page*Hk, D)`` rows (row ``t*Hk + kh``), all H query heads
    score against all rows in one MXU matmul, and a head mask keeps the rows
    of each query head's own kv head (``kh == h // G``). The other rows cost
    MXU work only; decode is bound by the KV bytes, which are read once.
  * Pages whose start offset exceeds the context length are skipped with
    pl.when — the DMA still runs (static grid) but the FLOPs don't.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(page_table, lengths, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, page: int, num_pages: int, Hk: int,
            G: int, sm_scale: float):
    b = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths[b]
    start = ip * page

    @pl.when(start < length)
    def _compute():
        H, D = q_ref.shape[1], q_ref.shape[2]
        q = q_ref[0].astype(jnp.float32)                         # (H, D)
        k = k_ref[0].astype(jnp.float32).reshape(page * Hk, D)   # (T, D)
        v = v_ref[0].astype(jnp.float32).reshape(page * Hk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                         # (H, T)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        own = (col % Hk) == (row // G)
        s = jnp.where(own & (start + col // Hk < length), s, NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ip == num_pages - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    interpret: bool = False):
    """q (B,H,D); k_pages/v_pages (P, page, Hk, D); page_table (B,MP) int32;
    lengths (B,). Returns (B,H,D)."""
    B, H, D = q.shape
    P, page, Hk, _ = k_pages.shape
    MP = page_table.shape[1]
    kernel = functools.partial(_kernel, page=page, num_pages=MP, Hk=Hk,
                               G=H // Hk, sm_scale=1.0 / math.sqrt(D))
    kv_spec = pl.BlockSpec((1, page, Hk, D),
                           lambda b, ip, pt, ln: (pt[b, ip], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MP),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, ip, pt, ln: (b, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, ip, pt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )(page_table, lengths, q, k_pages, v_pages)

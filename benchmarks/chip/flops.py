"""Operations and bytes that the model and its kernels need, from shapes.

Every count is of the algorithm's own work on the live tokens: padding,
parked slots and recomputation are not counted, so a change that skips
waste cannot read as lost work. Bytes are the least the kernel must move:
its inputs and outputs once.

``c`` is the ``run`` block of a configuration file of the Mamba-2 family,
the one family that a cell serves.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def matmul_params(c: dict) -> int:
    """Parameters that each token multiplies: every projection of every
    layer, plus the tied unembedding (the embedding lookup is a gather)."""
    d, L, V = c["d_model"], c["n_layers"], c["vocab_size"]
    di = c["expand"] * d
    G, N, P = c["n_groups"], c["d_state"], c["ssm_head_dim"]
    per = d * (2 * di + 2 * G * N + di // P) + di * d
    return L * per + V * d


def token_flops(c: dict, ctx: int) -> float:
    """Model operations of one token whose position sees ``ctx`` tokens
    (itself included): 2 per multiplied parameter, plus the state update
    and readout; a Mamba-2 token's cost does not grow with ``ctx``."""
    di = c["expand"] * c["d_model"]
    return 2.0 * matmul_params(c) + 6.0 * c["n_layers"] * di * c["d_state"]


def prefill_flops(c: dict, offset: int, length: int) -> float:
    """A chunk of ``length`` prompt tokens after ``offset`` cached ones."""
    return token_flops(c, offset + 1) * length


def ssd_scan(c: dict, length: int) -> tuple:
    """(ops, bytes) of the chunked SSD scan over every layer for a chunk of
    ``length`` real tokens: within each block of ``chunk`` tokens the causal
    C.B products and their weighting of x, the block's state from B and x,
    and the readout of the carried state; inputs x, B, C in bfloat16, dt and
    the state in float32, y out in bfloat16."""
    d = c["d_model"]
    di = c["expand"] * d
    P, N, G, L = c["ssm_head_dim"], c["d_state"], c["n_groups"], c["n_layers"]
    H = di // P
    Q = c.get("ssd_chunk", 128)
    pairs, left = 0.0, length
    while left > 0:
        q = min(Q, left)
        pairs += q * (q + 1) / 2
        left -= q
    ops = H * (2.0 * pairs * N + 2.0 * pairs * P + 4.0 * length * N * P) * L
    byts = (length * (2 * di + 2 * G * N) * BF16 + length * H * F32
            + 2.0 * H * P * N * F32) * L
    return ops, byts

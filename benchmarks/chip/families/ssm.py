"""The Mamba-2 family (``repro.models.ssm``): its parameter layout and
initial values, its widths in the program config, and the operations and
bytes of the model and of its ``ssd_scan`` kernel.

Mamba-2's A, dt bias and D follow the paper's init ranges; every other
leaf takes ``weights.py``'s generic rules. Counts are of the algorithm's
own work on the live tokens, as ``flops.py`` says.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from weights import round_up

BF16 = 2
F32 = 4


def layout(c: dict) -> dict:
    """{path tuple: (shape, dtype name)} of the program's parameters for
    configuration ``c`` (the ``run`` block of a configuration file)."""
    d, L, dt = c["d_model"], c["n_layers"], c["dtype"]
    vp = round_up(c["vocab_size"], 256)
    out = {("tok", "embed"): ((vp, d), dt),
           ("final_norm", "scale"): ((d,), dt)}
    di = c["expand"] * d
    N, G, P, W = c["d_state"], c["n_groups"], c["ssm_head_dim"], c["d_conv"]
    H = di // P
    ch = di + 2 * G * N
    lay = {("ln", "scale"): (d,), ("w_in",): (d, 2 * di + 2 * G * N + H),
           ("conv_w",): (W, ch), ("conv_b",): (ch,), ("A_log",): (H,),
           ("D",): (H,), ("dt_bias",): (H,), ("out_norm", "scale"): (di,),
           ("w_out",): (di, d)}
    f32 = (("A_log",), ("D",), ("dt_bias",))
    for k, shape in lay.items():
        out[("layers",) + k] = ((L,) + shape, "float32" if k in f32 else dt)
    return out


def leaf(key, path, shape, dtype):
    """A, dt bias and D of every layer, in float32; ``None`` for the rest."""
    name = path[-1]
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        ldt = jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3),
                                 jnp.log(1e-1))
        return jnp.log(jnp.expm1(jnp.exp(ldt)))     # softplus^-1(dt)
    if name == "D":
        return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
    return None


def widths(cfg) -> dict:
    """The program config's widths under the run block's keys."""
    s = cfg.ssm
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "vocab_size": cfg.vocab_size, "dtype": cfg.dtype,
            "d_state": s.d_state, "d_conv": s.d_conv, "expand": s.expand,
            "ssm_head_dim": s.head_dim, "n_groups": s.n_groups}


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

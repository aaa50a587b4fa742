"""Seeded random weights, made on the device in one jitted call.

The layout is the serving program's parameter pytree, written out here from
the configuration file alone, so that the plain references can rebuild the
same weights from the seed without importing the program. ``run.py`` checks
this layout against the program's own before it hands the weights over.

Scales: projections are N(0, 1/fan_in), so each block adds O(1) to the
residual stream; the tied embedding is N(0, (4/sqrt(d))^2), so logits have
a spread of about 4; norm gains are 1 + N(0, 0.01) (the program stores the
offset from 1); Mamba-2's A, dt bias and D follow the paper's init ranges.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def layout(c: dict) -> dict:
    """{path tuple: (shape, dtype name)} of the program's parameters for
    configuration ``c`` (the ``run`` block of a configuration file)."""
    d, L, dt = c["d_model"], c["n_layers"], c["dtype"]
    vp = round_up(c["vocab_size"], 256)
    out = {("tok", "embed"): ((vp, d), dt),
           ("final_norm", "scale"): ((d,), dt)}
    if c["family"] == "ssm":
        di = c["expand"] * d
        N, G, P, W = c["d_state"], c["n_groups"], c["ssm_head_dim"], c["d_conv"]
        H = di // P
        ch = di + 2 * G * N
        lay = {("ln", "scale"): (d,), ("w_in",): (d, 2 * di + 2 * G * N + H),
               ("conv_w",): (W, ch), ("conv_b",): (ch,), ("A_log",): (H,),
               ("D",): (H,), ("dt_bias",): (H,), ("out_norm", "scale"): (di,),
               ("w_out",): (di, d)}
        f32 = (("A_log",), ("D",), ("dt_bias",))
    else:
        raise SystemExit(f"no weight layout for family {c['family']!r}")
    for k, shape in lay.items():
        out[("layers",) + k] = ((L,) + shape, "float32" if k in f32 else dt)
    return out


def _leaf(key, path, shape, dtype):
    name = path[-1]
    dt = jnp.dtype(dtype)
    if name == "scale":
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dt)
    if name == "embed":
        std = 4.0 / shape[1] ** 0.5
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dt)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        ldt = jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3),
                                 jnp.log(1e-1))
        return jnp.log(jnp.expm1(jnp.exp(ldt)))     # softplus^-1(dt)
    if name == "D":
        return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
    if name == "conv_b":
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dt)
    fan_in = shape[-2]
    return (jax.random.normal(key, shape, jnp.float32)
            / fan_in ** 0.5).astype(dt)


def weights_seed(seed: int) -> int:
    return zlib.crc32(f"weights:{int(seed)}".encode()) & 0x7FFFFFFF


@partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    flat = {}
    for i, (path, shape, dtype) in enumerate(spec):
        flat[path] = _leaf(jax.random.fold_in(key, i), path, shape, dtype)
    return flat


def make(c: dict, seed: int) -> dict:
    """The weights of configuration ``c`` for ``seed``, as the program's
    nested dict, made on the default device in one call."""
    spec = tuple((p, s, d) for p, (s, d) in sorted(layout(c).items()))
    flat = _make(jax.random.PRNGKey(weights_seed(seed)), spec)
    out: dict = {}
    for path, arr in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = arr
    return out

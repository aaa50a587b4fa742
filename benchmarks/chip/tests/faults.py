"""Faults planted in the timed path, for the test that the check catches
them. Each takes the cluster after set-up and breaks it in place."""
from __future__ import annotations

from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np


def token_altered(cluster):
    """Every step's first decode token is replaced where it is produced."""
    for inst in cluster.instances.values():
        real = inst.finalize_step

        def finalize(pending, real=real, inst=inst):
            dec, chunks = real(pending)
            for rid in list(dec)[:1]:
                tok = (dec[rid] + inst.cfg.vocab_size // 2) % inst.cfg.vocab_size
                dec[rid] = tok
                inst.last_token[rid] = tok
            return dec, chunks

        inst.finalize_step = finalize


def exchange_left_out(cluster):
    """A migration delivers zeros in place of the decode state."""
    for inst in cluster.instances.values():
        real = inst.import_state

        def import_state(rid, payload, *a, real=real, **k):
            k.pop("checksum", None)
            return real(rid, [np.zeros_like(np.asarray(p)) for p in payload],
                        *a, **k)

        inst.import_state = import_state


def state_unchanged(cluster):
    """Every fused step returns the decode state it was given, unchanged."""
    for inst in cluster.instances.values():
        ops = inst._ops
        n = len(inst.kv.slabs())

        def keep(fn):
            def step(cfg, params, *args):
                kept = [jnp.array(a, copy=True) for a in args[:n]]
                out = fn(cfg, params, *args)
                return (out[0],) + tuple(kept)
            return step

        inst._ops = SimpleNamespace(decode_only=keep(ops.decode_only),
                                    chunks_only=keep(ops.chunks_only),
                                    mixed_step=keep(ops.mixed_step))


FAULTS = {"token_altered": token_altered,
          "exchange_left_out": exchange_left_out,
          "state_unchanged": state_unchanged}

"""Operations and bytes that the model and its kernels need, from shapes.

Every count is of the algorithm's own work on the live tokens: padding,
parked slots and recomputation are not counted, so a change that skips
waste cannot read as lost work. Bytes are the least the kernel must move:
its inputs and outputs once.

``c`` is the ``run`` block of a configuration file. The counts live in its
family's file (``families/<family>.py``); the functions here hand each
call to it, and ``family(c)`` gives a metric reader that module, for the
counts of a kernel that only some families run.
"""
from __future__ import annotations

import families


def family(c: dict):
    """The module of ``c``'s family."""
    return families.load(c["family"])


def matmul_params(c: dict) -> int:
    """Parameters that each token multiplies."""
    return family(c).matmul_params(c)


def token_flops(c: dict, ctx: int) -> float:
    """Model operations of one token whose position sees ``ctx`` tokens
    (itself included)."""
    return family(c).token_flops(c, ctx)


def prefill_flops(c: dict, offset: int, length: int) -> float:
    """A chunk of ``length`` prompt tokens after ``offset`` cached ones."""
    return family(c).prefill_flops(c, offset, length)


def ssd_scan(c: dict, length: int) -> tuple:
    """(ops, bytes) of the chunked SSD scan for a chunk of ``length`` real
    tokens."""
    return family(c).ssd_scan(c, length)

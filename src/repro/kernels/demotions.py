"""Counted demotions from a Pallas kernel to its jnp reference.

The kernel wrappers pick the kernel by platform (and, for tests, by an
explicit ``use_kernel=``) and fall back to the reference for a shape the
kernel cannot tile: a ragged or unaligned dimension, a 3-bit code width,
a mesh-sharded operand whose local tile will not align.  Such a fallback
is correct but slow, so it must not pass unnoticed: every wrapper that
wanted its kernel and took the reference calls :func:`record`, which
counts it per ``(kernel, reason)`` and logs the first occurrence.

Recording happens while the wrapper traces, so the count is one per
traced program, not per call.  A deployment asserts ``counts() == {}``
after warming up its shapes (``chip_smoke.py`` does).
"""
from __future__ import annotations

import collections
import logging

_log = logging.getLogger(__name__)
_COUNTS: collections.Counter = collections.Counter()


def record(kernel: str, reason: str) -> None:
    """Count one demotion of ``kernel`` to its reference for ``reason``."""
    key = f"{kernel}: {reason}"
    if not _COUNTS[key]:
        _log.warning("kernel demoted to its reference — %s", key)
    _COUNTS[key] += 1


def counts() -> dict[str, int]:
    """``{"kernel: reason": n}`` for every demotion recorded so far."""
    return dict(_COUNTS)


def reset() -> None:
    _COUNTS.clear()

"""Strict environment-flag parsing — the one sanctioned env read path.

Every ``REPRO_*`` behavior flag in the repo is read through these
helpers, and the AST linter (:mod:`repro.analysis.lint`) enforces it:
a raw ``os.environ``/``os.getenv`` read of a ``REPRO_*`` name anywhere
else is a lint finding, and :func:`bool_flag` must be called at module
scope so a flag's value is fixed at import time — a flag that silently
changes between two jit traces of "the same" program is exactly the kind
of drift the contract checker exists to catch.

Strictness over permissiveness: the old reads accepted any string
(``REPRO_ORCH_KERNELS=yes`` silently meant *enabled* because only
``"0"`` disabled), so a typo flipped a kernel path without a peep.  Now
boolean flags accept exactly ``"0"`` and ``"1"`` and anything else
raises with the offending value in the message.
"""
from __future__ import annotations

import os

# Registry of the repo's known flags (documentation + lint cross-check).
ORCH_KERNELS = "REPRO_ORCH_KERNELS"       # bool: fused Pallas orchestration
PROFILE_DIR = "REPRO_PROFILE_DIR"         # path: jax.profiler trace output
KNOWN_FLAGS = (ORCH_KERNELS, PROFILE_DIR)


def bool_flag(name: str, default: bool) -> bool:
    """Read a strict boolean flag: unset → ``default``, ``"0"`` → False,
    ``"1"`` → True, anything else → ``ValueError`` naming the flag and
    the rejected value.  Call at module scope only (lint-enforced), so
    the flag is a trace-time constant."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    if raw not in ("0", "1"):
        raise ValueError(
            f"{name}={raw!r} is not a valid boolean flag value; "
            f"use '0' (off) or '1' (on)")
    return raw == "1"


def path_flag(name: str, default: str | None = None) -> str | None:
    """Read a directory-path flag: unset → ``default`` (``None`` = off).
    A set value must be a non-empty path and, if it already exists, a
    directory — a flag pointing at a regular file (or set to ``""`` by a
    broken shell expansion) raises instead of producing a half-written
    trace dump deep inside a run."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    if not raw.strip():
        raise ValueError(f"{name} is set but empty; unset it or point it "
                         f"at a writable directory")
    if os.path.exists(raw) and not os.path.isdir(raw):
        raise ValueError(f"{name}={raw!r} exists but is not a directory")
    return raw

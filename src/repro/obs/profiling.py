"""Profiling hooks (DESIGN.md §15): a recompile counter.

The recompile counter is fed by the episode-dispatch layer in
``core.t2drl`` — every fresh XLA compile registers a :func:`record_compile`
event, so silent retraces (a ragged final ``log_every`` chunk, a config
leaking a traced value into a static field) show up as a count, not a
mystery slowdown.
"""
from __future__ import annotations

import warnings

# Compile-event log: (tag, signature) per fresh XLA compile, appended by
# core.t2drl's episode dispatch.  Module-global on purpose — it must be
# shared across jit caches and readable from tests.
_COMPILE_EVENTS: list = []
_WARNED_TAGS: set = set()


def record_compile(tag: str, signature: str = "") -> None:
    """Register one fresh compile of the program named ``tag``."""
    _COMPILE_EVENTS.append((tag, signature))
    sigs = {s for t, s in _COMPILE_EVENTS if t == tag}
    if len(sigs) > 2 and tag not in _WARNED_TAGS:
        # two programs per tag are expected for chunked training (full
        # chunk + remainder); a third signature means a silent retrace —
        # or a caller legitimately reusing one config at several batch
        # shapes, so warn once per tag, not per extra program
        _WARNED_TAGS.add(tag)
        warnings.warn(
            f"obs.profiling: {len(sigs)} distinct programs compiled for "
            f"{tag!r} — possible silent retrace (ragged chunk sizes or an "
            f"unstable static config)", stacklevel=2)


def compile_count(tag: str | None = None) -> int:
    """Number of fresh compiles recorded (for ``tag``, or in total)."""
    if tag is None:
        return len(_COMPILE_EVENTS)
    return sum(1 for t, _ in _COMPILE_EVENTS if t == tag)


def compile_events(tag: str | None = None) -> list:
    """The recorded ``(tag, signature)`` events, optionally filtered."""
    if tag is None:
        return list(_COMPILE_EVENTS)
    return [(t, s) for t, s in _COMPILE_EVENTS if t == tag]


def reset_compiles() -> None:
    """Clear the compile-event log (test isolation)."""
    _COMPILE_EVENTS.clear()
    _WARNED_TAGS.clear()


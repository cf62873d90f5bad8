"""The program's own trace marks: host spans, device scopes, host counters.

``span(name)`` is a host span on the profiler's clock, the clock of the
device trace; it costs about a microsecond while no trace runs. ``scope(name)``
writes ``name`` into the HLO ``op_name`` metadata of every op traced under
it, so a device op's time can be put down to the program's layer whatever
the op's HLO number; it changes no computation. Counters are plain integers
in memory, bumped at host boundaries only, never inside a traced function.
"""
from __future__ import annotations

import collections

import jax

_counters: collections.Counter = collections.Counter()


def span(name: str):
    """A host span in the profiler's trace."""
    return jax.profiler.TraceAnnotation(name)


def scope(name: str):
    """A device scope: ``name`` in the ``op_name`` of every op traced under it."""
    return jax.named_scope(name)


def count(name: str, n: int) -> None:
    _counters[name] += int(n)


def counters() -> dict[str, int]:
    return dict(_counters)


def reset_counters() -> None:
    _counters.clear()

"""Adapters of the systems under test, one per ``system`` of a configuration.

An adapter's ``System(cfg, traffic, seed, data, control=False)`` builds the
cell's data and structure from the seed and warms up every shape its steps
use (all of it set-up). ``step(s)`` runs closed-loop step ``s`` to the host
side of the device and returns the seconds of its update, or ``None``.
``kept()`` gives the answers of the last step for the check, ``check(kept)``
compares kept answers with the plain reference, and ``work`` holds the
algorithm's bytes per drain and per build call. ``control=True`` puts the
reference, in bfloat16, in the program's place (and counts no work).
"""
import jax


def span(name: str):
    """A host span in the profiler's trace (near free while not tracing)."""
    return jax.profiler.TraceAnnotation(name)


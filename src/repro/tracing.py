"""The program's one tracing seam: host spans on the profiler's clock, and
the stage scopes of its compiled programs.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation`` while a
profiler trace runs (``jax.profiler.start_trace`` / ``jax.profiler.trace``),
so the span lands in the profiler's own trace, on the clock of the device
ops. Otherwise it is one flag read and a shared no-op context; nothing in
``jax.profiler`` is called. Stats known only inside the span are attached
with ``set_metadata(**stats)`` on the object the ``with`` yields; callers
that must compute them test ``enabled()`` first.

Device-side stages are ``jax.named_scope`` scopes (``fp``, ``na.<semantic
graph>``, ``k1``, ``k2``, ``fusion``, ``gather``): compile-time names in
each HLO instruction's ``op_name`` metadata, with no runtime cost. A trace
names a device op by its HLO instruction alone, so ``record_scopes`` keeps,
per compiled program, each instruction's ``op_name``; ``op_scopes()``
returns them for a trace reader.
"""
from __future__ import annotations

import re
from typing import Dict

import jax
from jax._src import profiler as _profiler

# the session state jax.profiler.start_trace sets and stop_trace clears
_STATE = _profiler._profile_state


class _Off:
    """The span while no trace runs: enters, exits and records nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        pass


_OFF = _Off()


def enabled() -> bool:
    """True while a profiler trace runs."""
    return _STATE.profile_session is not None


def span(name: str, **stats):
    """A host span named ``name`` carrying ``stats`` (ints, floats or
    strings), recorded only while a profiler trace runs."""
    if _STATE.profile_session is None:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **stats)


# HLO module name -> {instruction name: op_name}
_SCOPES: Dict[str, Dict[str, str]] = {}
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*\bop_name="([^"]*)"')


def record_scopes(compiled) -> None:
    """Keep the ``op_name`` of every instruction of a compiled program
    (``jax.stages.Compiled``), under its HLO module's name."""
    text = compiled.as_text()
    module = text.split(None, 2)[1].rstrip(",")  # "HloModule <name>, ..."
    table = _SCOPES.setdefault(module, {})
    for line in text.splitlines():
        m = _OP_NAME.match(line)
        if m:
            table[m.group(1)] = m.group(2)


def op_scopes() -> Dict[str, Dict[str, str]]:
    """``{module name: {instruction name: op_name}}`` of every program
    passed to ``record_scopes`` in this process."""
    return _SCOPES

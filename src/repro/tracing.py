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

Program counters are counted while a program is traced: inside
``counting()``, a ``counted(name, n)`` call site adds ``n`` (the kernel
wrappers count the K1 grid steps each NA call site launches,
``k1_steps``); ``record_scopes(compiled, sites)`` keeps the sites that
survived compilation under the program's module and ``program_counts()``
returns them. Outside ``counting()``, ``counted`` is one attribute read.
"""
from __future__ import annotations

import contextlib
import re
import threading
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
# HLO module name -> {counter: value}
_COUNTS: Dict[str, Dict[str, int]] = {}
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*\bop_name="([^"]*)"')
# the counted call sites of the program this thread traces, in counting()
_ACTIVE = threading.local()


@contextlib.contextmanager
def counting():
    """Collect the ``counted`` call sites this thread traces inside, as the
    list it yields: wrap the tracing (``lower``) of one program and hand
    the list to ``record_scopes``."""
    prev = getattr(_ACTIVE, "sites", None)
    _ACTIVE.sites = []
    try:
        yield _ACTIVE.sites
    finally:
        _ACTIVE.sites = prev


@contextlib.contextmanager
def counted(name: str, n: int):
    """One call site that adds ``n`` to the counter ``name`` of the program
    being traced, if any op traced inside survives its compilation (XLA
    drops a call whose result is never used). Inside ``counting()`` the
    ops carry a scope of their own, ``<name>.<site>``; elsewhere this is
    one attribute read."""
    sites = getattr(_ACTIVE, "sites", None)
    if sites is None:
        yield
        return
    tag = f"{name}.{len(sites)}"
    sites.append((tag, name, int(n)))
    with jax.named_scope(tag):
        yield


def record_scopes(compiled, sites=()) -> None:
    """Keep the ``op_name`` of every instruction of a compiled program
    (``jax.stages.Compiled``), and the counters of the ``counted`` call
    sites (from ``counting``) that survived in it, under its HLO module's
    name."""
    text = compiled.as_text()
    module = text.split(None, 2)[1].rstrip(",")  # "HloModule <name>, ..."
    table = _SCOPES.setdefault(module, {})
    parts = set()
    for line in text.splitlines():
        m = _OP_NAME.match(line)
        if m:
            table[m.group(1)] = m.group(2)
            parts.update(m.group(2).split("/"))
    counts: Dict[str, int] = {}
    for tag, name, n in sites:
        if tag in parts:
            counts[name] = counts.get(name, 0) + n
    if counts:
        _COUNTS[module] = counts


def op_scopes() -> Dict[str, Dict[str, str]]:
    """``{module name: {instruction name: op_name}}`` of every program
    passed to ``record_scopes`` in this process."""
    return _SCOPES


def program_counts() -> Dict[str, Dict[str, int]]:
    """``{module name: {counter: value}}`` of every program passed to
    ``record_scopes`` with counts in this process."""
    return _COUNTS

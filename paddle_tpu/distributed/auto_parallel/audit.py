"""Compile-time sharding auditor.

XLA's partitioner warnings come out of C++ absl logging, which writes
straight to file descriptor 2 — sys.stderr redirection never sees them.
The capture here dup2()s fd 2 into a temp file around the compile, then
restores it; parsing is delegated to parser.py so the detector is
testable from stored fixtures without compiling anything.

Every audit entry point compiles FRESH (a new jax.jit wrapper, or
TrainStep.compiled_executable which re-lowers each call): XLA only
emits the warnings while actually partitioning, so auditing a cached
executable would report a false pass. For the same reason the compile
runs with the PERSISTENT compilation cache suspended
(framework/compile_cache.py makes it process-wide) — a cache hit skips
the partitioner entirely and would silently report clean.
"""
import contextlib
import os
import sys
import tempfile

import jax

from ...framework import compile_cache
from .parser import (parse_spmd_warnings, parse_hlo_collectives,
                     ShardingEvent)

__all__ = ['ShardingAuditReport', 'capture_compiler_stderr',
           'audit_callable', 'audit_train_step', 'audit_from_text',
           'assert_no_involuntary_resharding']

_TAIL_CHARS = 4000


@contextlib.contextmanager
def capture_compiler_stderr():
    """Capture EVERYTHING written to fd 2 (Python and C++ alike) for the
    duration of the block. Yields a dict whose 'text' key holds the
    captured output after the block exits."""
    buf = {'text': ''}
    saved = os.dup(2)
    tmp = tempfile.TemporaryFile(mode='w+b')
    try:
        sys.stderr.flush()
        os.dup2(tmp.fileno(), 2)
        yield buf
    finally:
        try:
            sys.stderr.flush()
        except Exception:
            pass
        os.dup2(saved, 2)
        os.close(saved)
        tmp.seek(0)
        buf['text'] = tmp.read().decode('utf-8', 'replace')
        tmp.close()


class ShardingAuditReport:
    """What GSPMD did to one compiled step: involuntary-reshard events
    (the failure signal), collective counts/bytes from the optimized
    HLO (the context), and the raw stderr tail (the evidence)."""

    def __init__(self, label='', events=(), collectives=None,
                 stderr_tail=''):
        self.label = label
        self.events = list(events)
        self.collectives = dict(collectives or {})
        self.stderr_tail = stderr_tail

    @property
    def passed(self):
        return not self.events

    @property
    def involuntary_bytes(self):
        return sum(e.bytes for e in self.events)

    def to_dict(self):
        return {
            'label': self.label,
            'ok': self.passed,
            'n_events': len(self.events),
            'involuntary_bytes': self.involuntary_bytes,
            'events': [e.to_dict() for e in self.events],
            'collectives': self.collectives,
        }

    @classmethod
    def from_dict(cls, d):
        return cls(label=d.get('label', ''),
                   events=[ShardingEvent.from_dict(e)
                           for e in d.get('events', ())],
                   collectives=d.get('collectives'))

    def summary(self):
        head = ('sharding audit [%s]: %s' %
                (self.label or 'step',
                 'clean' if self.passed else
                 '%d involuntary reshard(s), ~%d bytes replicated'
                 % (len(self.events), self.involuntary_bytes)))
        lines = [head]
        for e in self.events:
            lines.append('  %r' % (e,))
        if self.collectives:
            coll = ' '.join('%s=%d' % (k, v['count'])
                            for k, v in sorted(self.collectives.items()))
            lines.append('  collectives: %s' % coll)
        return '\n'.join(lines)


def audit_from_text(stderr_text, hlo_text=None, label=''):
    """Build a report from already-captured text (stored capture tails,
    the dryrun gate, fixture tests)."""
    return ShardingAuditReport(
        label=label,
        events=parse_spmd_warnings(stderr_text),
        collectives=parse_hlo_collectives(hlo_text) if hlo_text else None,
        stderr_tail=(stderr_text or '')[-_TAIL_CHARS:])


def _mesh_scope(mesh):
    """Make `mesh` the ambient mesh for PartitionSpec-based constraints
    inside the audited fn."""
    return contextlib.nullcontext() if mesh is None else mesh


def audit_callable(fn, args=(), kwargs=None, mesh=None, label=''):
    """Freshly jit-compile fn(*args, **kwargs) under stderr capture and
    report what the partitioner did. fn may itself be jitted (jit of jit
    is fine); args should carry NamedShardings (or the callable should
    place constraints) for the audit to be about anything."""
    kwargs = kwargs or {}
    wrapped = jax.jit(lambda *a, **k: fn(*a, **k))
    with _mesh_scope(mesh):
        lowered = wrapped.lower(*args, **kwargs)
        with compile_cache.suspended(), capture_compiler_stderr() as cap:
            compiled = lowered.compile()
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = None
    return audit_from_text(cap['text'], hlo, label=label or
                           getattr(fn, '__name__', 'fn'))


def audit_train_step(step, inputs, labels, label=''):
    """Audit a framework.functional.TrainStep for one batch. Uses
    compiled_executable (which re-lowers+recompiles every call, so the
    partitioner warnings are emitted even for a step that already ran)."""
    with compile_cache.suspended(), capture_compiler_stderr() as cap:
        compiled = step.compiled_executable(inputs, labels)
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = None
    return audit_from_text(cap['text'], hlo, label=label or 'train_step')


def assert_no_involuntary_resharding(fn, mesh=None, args=(), kwargs=None,
                                     label=''):
    """CI gate: compile fn and fail loudly if GSPMD had to fall back to
    replicate-then-repartition anywhere. Returns the report on success
    so tests can additionally pin collective counts."""
    report = audit_callable(fn, args=args, kwargs=kwargs, mesh=mesh,
                            label=label)
    if not report.passed:
        raise AssertionError(report.summary())
    return report

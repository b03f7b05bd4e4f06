"""Performance introspection: the third leg of the monitor subsystem.

Three components, each usable alone (stdlib at import; jax touched only
when live):

- ``watchdog``  — the process's compile log (every program jax builds:
  which, which stage, whose, cache hit or not; ``compile_log()``) and
  CompileWatchdog on it: recompile attribution (callsite +
  abstract-shape signature), warmup barrier with flight-dump + optional
  strict hard-fail;
- ``timeline``  — StepTimeline: data-wait / host-dispatch /
  device-blocked phase split with rolling percentiles and straggler
  detection;
- ``costmodel`` — XLA cost-analysis -> arithmetic intensity, roofline
  bound, ideal step time, and MFU estimates.

All metric families are single-sourced in
``monitor.telemetry.PERF_FAMILIES`` (registered via
``record_perf_schema``) so the dryrun schema gate covers them without a
perf run. See docs/observability.md for the family/label inventory.
"""
from . import costmodel
from .timeline import PHASES, StepTimeline
from .watchdog import (COMPILE_EVENTS, CompileLog, CompileWatchdog,
                       RecompileError, compile_log)

__all__ = ['CompileWatchdog', 'RecompileError', 'COMPILE_EVENTS',
           'CompileLog', 'compile_log',
           'StepTimeline', 'PHASES', 'costmodel']

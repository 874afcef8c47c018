"""Spans inside the sidecar and the engine, on the device trace's clock.

``span(name, acc, cpu=False, **ids)`` is the one helper (``note(**ids)``
adds arguments known only inside it).  It has three sinks:

* a ``jax.profiler.TraceAnnotation(name, **ids)``, which lands on the
  host plane of the same ``.xplane.pb`` as the device's "XLA Ops" line, so
  a program span and a device operation share one clock.  That clock is
  the profiler session's own: it runs at ``CLOCK_MONOTONIC``'s rate and
  counts from about the session's start, so it is neither the host's
  monotonic nor its wall clock (measured on the v5e's host and on the CPU
  backend alike: OPERATIONS.md, "Tracing").  ``mark`` therefore stamps
  every marker with ``mono_us``, and ``benchmark/daemon_spans.py`` joins
  the storage daemon's ``MonoUs()`` intervals to the trace by the median
  of (marker's trace time - its ``mono_us``).  An annotation is made only
  while a profiler session runs (one flag test otherwise).  Nesting on a
  thread is the parent link; the root span of a request carries the
  daemon's ``session`` and ``base_offset``, which the daemon's
  ``storage.fp_rpc`` interval of the same RPC carries too.
* ``acc``, a plain dict that belongs to one request (``new_acc()``): the
  span's wall time and count by name.  The sidecar folds it into its
  ``stats`` reply (``span_us``, ``span_n``) under the lock it already
  holds, so the helper itself writes nothing shared.
* with ``cpu=True`` (spans in which the thread has only Python to run:
  parse, pack, scatter, reply), and only while a trace runs, also the
  thread's CPU time: wall minus CPU over those spans is time the thread
  had no interpreter or no core (``host_stall_us``).  ``thread_time_ns``
  is a system call (6 us on the chip's host), so the untraced path never
  makes it.

Spans are per request and per tile, never per chunk; names are fixed
strings (the table in OPERATIONS.md, "Tracing").
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


def new_acc() -> dict:
    return {"span_ns": {}, "span_n": {}, "host_wall_ns": 0, "host_cpu_ns": 0}


def mark(name: str, **ids) -> None:
    """A zero-length marker that carries ``ids`` as its arguments, and
    ``mono_us``: this host's ``CLOCK_MONOTONIC`` read beside it, so that
    every marker is an anchor between the trace's clock and the one the
    storage daemon stamps its stages with (``MonoUs()``)."""
    if TraceAnnotation.is_enabled():
        with TraceAnnotation(name, mono_us=time.monotonic_ns() // 1000,
                             **ids):
            pass


class span:  # noqa: N801 — reads as a statement: ``with span(...)``
    __slots__ = ("name", "acc", "cpu", "ann", "t0", "c0")

    def __init__(self, name: str, acc: dict, cpu: bool = False, **ids):
        self.name, self.acc = name, acc
        self.ann = (TraceAnnotation(name, **ids)
                    if TraceAnnotation.is_enabled() else None)
        self.cpu = cpu and self.ann is not None

    def __enter__(self) -> "span":
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        if self.cpu:    # taken inside the wall interval: wall >= CPU
            self.c0 = time.thread_time_ns()
        return self

    def note(self, **ids) -> None:
        """More arguments, known only inside the span (while traced)."""
        if self.ann is not None:
            self.ann.set_metadata(**ids)

    def __exit__(self, *exc) -> bool:
        acc = self.acc
        if self.cpu:
            acc["host_cpu_ns"] += time.thread_time_ns() - self.c0
        wall = time.perf_counter_ns() - self.t0
        if self.cpu:
            acc["host_wall_ns"] += wall
        if self.ann is not None:
            self.ann.__exit__(*exc)
        acc["span_ns"][self.name] = acc["span_ns"].get(self.name, 0) + wall
        acc["span_n"][self.name] = acc["span_n"].get(self.name, 0) + 1
        return False

"""One span primitive for the program's own phases, on the device
trace's clock.

``span(name, obs, hist, **meta)`` is a context manager with two sinks:

- it always enters a ``jax.profiler.TraceAnnotation(name, **meta)``.
  Under a profiler session the span lands on the profiler's host line
  in the same nanoseconds as the device's operations, so an idle gap
  of the device can be attributed to the phase the host was in. With no
  session the annotation is a no-op check (well under a microsecond);
- when the component holds an :class:`Observability` (``obs`` is not
  ``None``) the same name, end instant and duration are recorded into
  its ``Timeline`` ring and, if ``hist`` names one, into that
  histogram. ``ring=False`` keeps a span out of the ring where an
  older event of the component already is that phase and is fed from
  this span's ``dur_ms`` (``prefill_chunk``, ``decode_step``). With
  ``obs=None`` no clock is read and nothing is allocated beyond the
  span itself.

The names are constants (``SERVE_SPANS``, ``TRAIN_SPANS``) that the
tests freeze, like ``LATENCY_HISTOGRAMS``: readers of a trace (the
benchmark's per-layer metrics) find the spans by these names.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

__all__ = ["span", "tracing", "SERVE_SPANS", "TRAIN_SPANS"]


def tracing() -> bool:
    """Whether a profiler session is open (between
    ``jax.profiler.start_trace`` and ``stop_trace``): what a ``span``'s
    annotation asks before it records anything, ~150 ns a call. The
    owners of the jitted programs ask it at a dispatch, so that the
    registry of compiled programs (``observability/programs.py``)
    captures a program only where a trace will hold its operations."""
    return TraceAnnotation.is_enabled()

# one ServingEngine.step(): every name but the first is a child of
# serve/step, entered only when its branch runs
SERVE_SPANS = (
    "serve/step",            # the whole call
    "serve/admit",           # expiry, queue pop, prefix match, pages
    "serve/state_reset",     # zeroing an admitted slot's recurrent state
    "serve/prefill_stage",   # bucket choice, padding, chunk uploads
    "serve/prefill_dispatch",    # the jitted chunk call returning
    "serve/first_token_sync",    # blocking read of a prompt's 1st token
    "serve/window_release",  # giving window pages back, taking new ones
    "serve/table_upload",    # the _dirty re-upload of decode inputs
    "serve/decode_dispatch",     # the jitted decode call returning
    "serve/token_sync",      # the per-step host read of the tokens
    "serve/emit",            # per-slot append / stop test / _finish
    "serve/observe",         # gauges, telemetry, invariant check
)

# one Trainer.step(): sync only on the observed path, which waits for
# the device to split the step's wall time
TRAIN_SPANS = ("train/stage", "train/dispatch", "train/sync")


class span:
    """``with span("serve/admit", obs): ...`` — see the module text.
    After the block ``dur_ms`` holds the duration when ``obs`` was
    given (else ``None``); ``drop()`` inside the block keeps the span
    out of the timeline and the histogram (a step that did no work)."""

    __slots__ = ("name", "obs", "hist", "ring", "meta", "dur_ms", "_ann",
                 "_t0")

    def __init__(self, name, obs=None, hist=None, ring=True, **meta):
        self.name, self.obs, self.hist, self.meta = name, obs, hist, meta
        self.ring = ring
        self.dur_ms = None

    def __enter__(self):
        self._ann = TraceAnnotation(self.name, **self.meta)
        self._ann.__enter__()
        if self.obs is not None:
            self._t0 = time.perf_counter_ns()
        return self

    def drop(self):
        self.obs = None

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        obs = self.obs
        if obs is not None:
            t1 = time.perf_counter_ns()
            self.dur_ms = dur_ms = (t1 - self._t0) / 1e6
            if self.ring:
                obs.timeline.record(self.name, dur_ms=dur_ms, t_ns=t1,
                                    **self.meta)
            if self.hist is not None:
                obs.hist(self.hist).observe(dur_ms)
        return False

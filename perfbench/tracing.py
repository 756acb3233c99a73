"""The short profiler trace in the middle of a traced run's window."""

from __future__ import annotations

import os
import shutil
import time

import jax


def annotate(name: str):
    """A host span the trace reduction reads (``pb.<name>``); free when no
    trace is being taken."""
    return jax.profiler.TraceAnnotation("pb." + name)


class MidWindowTrace:
    """``start()`` .. ``stop()`` around a slice of the window; ``enabled``
    False makes both no-ops. The Python tracer is off: it would slow the
    host it is meant to observe and bury the spans the reduction reads.

    The profiler is not the run: what it raises is kept in ``errors`` and the
    trace goes back to idle, so that the runner can try once more later in
    the window (``can_start``); the harness reports a traced run that ends
    without a trace, with these errors, under an exit code of its own."""

    ATTEMPTS = 2
    RETRY_AFTER_S = 6.0

    def __init__(self, enabled: bool, directory: str):
        self.enabled, self.directory = enabled, directory
        self.started_at = self.stopped_at = None
        self.attempts, self.attempted_at, self.errors = 0, None, []
        if enabled:
            shutil.rmtree(directory, ignore_errors=True)
            os.makedirs(directory, exist_ok=True)

    @property
    def active(self) -> bool:
        return self.started_at is not None and self.stopped_at is None

    @property
    def done(self) -> bool:
        return not self.enabled or self.stopped_at is not None

    @property
    def can_start(self) -> bool:
        """No trace taken or running, and an attempt left that is due."""
        return (self.enabled and self.started_at is None
                and self.attempts < self.ATTEMPTS
                and (self.attempted_at is None or time.perf_counter()
                     - self.attempted_at >= self.RETRY_AFTER_S))

    def start(self) -> None:
        if not self.can_start:
            return
        self.attempts += 1
        self.attempted_at = time.perf_counter()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        try:
            jax.profiler.start_trace(self.directory, profiler_options=opts)
        except Exception as e:  # noqa: BLE001 — kept for the harness to report
            self.errors.append(f"start_trace: {e!r}")
            return
        self.started_at = time.perf_counter()

    def stop(self) -> None:
        if not self.active:
            return
        self.stopped_at = time.perf_counter()
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            self.errors.append(f"stop_trace: {e!r}")
            self.started_at = self.stopped_at = None

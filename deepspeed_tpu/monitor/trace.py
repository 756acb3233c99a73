"""Structured per-step tracing + the compile watchdog.

Two instruments living next to the metrics registry:

- :class:`StepTracer` — host-side spans (``with tracer.span("fwd")``)
  that ALSO push/pop the accelerator's profiler ``TraceAnnotation`` (so
  the same names show up in an ``xprof``/TensorBoard device trace) and are
  exportable as chrome-trace JSON (``chrome://tracing`` / Perfetto).

- :class:`CompileWatchdog` — wraps the framework's ``jax.jit`` entry
  points. Every call through a watched function checks the jit cache size
  before/after: growth means XLA compiled a new program, and the watchdog
  records the compile wall-time (the triggering call's wall time — an
  upper bound including the first execution), the abstract input shapes
  that caused it, and bumps ``compile/count``. Crossing the storm
  threshold logs a loud warning: a recompilation storm (shape churn,
  weak_type flapping, python-scalar leakage) is the classic silent TPU
  perf killer — the program "works" while every step pays seconds of
  XLA compile time.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

from deepspeed_tpu.monitor.metrics import MetricsRegistry, get_registry
from deepspeed_tpu.utils.logging import logger

# ------------------------------------------------------------------ #
# step tracer


class StepTracer:
    """Span recorder: chrome-trace "complete" (ph=X) events, bounded."""

    #: chrome-trace pid of the host-span track group (the serving trace
    #: renderer uses 1/2, so merged host+serving documents never collide)
    PID = 0

    def __init__(self, max_events: int = 100_000, use_accelerator: bool = True):
        self.max_events = max_events
        self.use_accelerator = use_accelerator
        self._events: List[Dict[str, Any]] = []
        self._thread_names: Dict[int, str] = {}   # tid -> thread name
        self._lock = threading.Lock()
        self._dropped = 0
        self._warned_drop = False
        self._t0 = time.perf_counter()

    @property
    def dropped(self) -> int:
        """Spans discarded at ``max_events`` — nonzero means every
        export from this tracer is TRUNCATED, not complete."""
        with self._lock:
            return self._dropped

    def _accelerator(self):
        if not self.use_accelerator:
            return None
        try:
            from deepspeed_tpu.accelerator import get_accelerator
            return get_accelerator()
        except Exception:
            return None

    @contextmanager
    def span(self, name: str, **args):
        """Host span around the with-block; mirrored onto the device
        profiler timeline via ``range_push``/``range_pop``."""
        acc = self._accelerator()
        if acc is not None:
            acc.range_push(name)
        start = time.perf_counter()
        try:
            yield
        finally:
            dur = time.perf_counter() - start
            if acc is not None:
                acc.range_pop()
            self.add_event(name, start, dur, args or None)

    def add_event(self, name: str, start_s: float, dur_s: float,
                  args: Optional[Dict] = None) -> None:
        tid = threading.get_ident() % 2**31
        ev = {"name": name, "ph": "X", "pid": self.PID, "tid": tid,
              "ts": (start_s - self._t0) * 1e6, "dur": dur_s * 1e6}
        if args:
            ev["args"] = {k: str(v) for k, v in args.items()}
        with self._lock:
            if tid not in self._thread_names:
                # captured at record time: export may run from another
                # thread, by which point this one may be gone
                self._thread_names[tid] = threading.current_thread().name
            if len(self._events) >= self.max_events:
                # never silent: the registry counter makes truncation
                # scrapeable, the once-per-run warning makes it loud
                self._dropped += 1
                warn_now = not self._warned_drop
                self._warned_drop = True
                try:
                    get_registry().counter(
                        "trace/dropped_events",
                        "StepTracer spans discarded at max_events — a "
                        "nonzero value means exported chrome traces are "
                        "truncated, not complete").inc()
                except Exception:
                    pass     # a broken registry must never kill a span
                if warn_now:
                    logger.warning(
                        f"StepTracer hit max_events={self.max_events}; "
                        "further spans are dropped (trace/dropped_events "
                        "counts them) — exported traces are truncated")
                return
            self._events.append(ev)

    @property
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._thread_names.clear()
            self._dropped = 0
            self._warned_drop = False    # a fresh run warns afresh

    def export_chrome_trace(self, path: str) -> str:
        """Write the recorded spans as chrome-trace JSON; returns path.
        Process/thread metadata events name the tracks (Perfetto shows
        "deepspeed_tpu host / MainThread" instead of bare integers — and
        a merged host+serving document keeps its groups tellable)."""
        import json
        import os
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
            names = dict(self._thread_names)
        meta: List[Dict[str, Any]] = [
            {"ph": "M", "name": "process_name", "pid": self.PID,
             "args": {"name": "deepspeed_tpu host"}}]
        for tid in sorted(names):
            meta.append({"ph": "M", "name": "thread_name", "pid": self.PID,
                         "tid": tid, "args": {"name": names[tid]}})
        doc = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        if dropped:
            doc["otherData"] = {"dropped_events": dropped}
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


# ------------------------------------------------------------------ #
# on-demand device profiling: a jax.profiler capture window


class ProfileWindow:
    """Bounded ``jax.profiler`` capture armed by config
    (``telemetry.profile: {start_step, num_steps, dir}``) or
    programmatically (``engine.profile(steps=N)``): the engine calls
    :meth:`tick` once per ``train_batch`` dispatch (one None/flag check
    when nothing is armed) and the window starts/stops the device trace
    around the requested steps. Steps are counted as tick calls in THIS
    process (no device sync to read a global step). While capturing,
    :meth:`annotate` pushes the accelerator ``TraceAnnotation`` under the
    same names the :class:`StepTracer` spans use, so host spans line up
    with the device timeline in xprof/TensorBoard."""

    def __init__(self, log_dir: str = "ds_profile", start_step: int = 0,
                 num_steps: int = 0):
        self.log_dir = log_dir
        self._lock = threading.Lock()
        self._step = 0             # tick calls seen
        self._stop_at: Optional[int] = None
        self.active = False
        self.captures = 0
        self._armed: Optional[Dict[str, Any]] = None
        if num_steps > 0:
            self._armed = {"start": max(int(start_step), 0),
                           "steps": int(num_steps), "dir": log_dir}

    def arm(self, steps: int, log_dir: Optional[str] = None,
            start_step: Optional[int] = None) -> None:
        """Request a capture of ``steps`` train steps, starting at the
        next tick (or at absolute tick ``start_step``)."""
        if steps < 1:
            raise ValueError("profile steps must be >= 1")
        with self._lock:
            if self.active:
                raise RuntimeError("a profile capture is already running")
            self._armed = {"start": (self._step if start_step is None
                                     else int(start_step)),
                           "steps": int(steps),
                           "dir": log_dir or self.log_dir}

    def tick(self) -> None:
        """One train-step boundary: start the trace when the armed window
        begins, stop it when the window has covered its steps."""
        with self._lock:
            step = self._step
            self._step += 1
            if self.active:
                if step >= self._stop_at:
                    self._stop()
                return
            armed = self._armed
            if armed is None or step < armed["start"]:
                return
            self._armed = None
            self._stop_at = step + armed["steps"]
            try:
                import jax
                jax.profiler.start_trace(armed["dir"])
            except Exception as e:
                logger.warning(f"profile: start_trace failed ({e}); "
                               "capture window dropped")
                self._stop_at = None
                return
            self.active = True
            logger.info(f"profile: capturing {armed['steps']} step(s) to "
                        f"{armed['dir']} (summarize with "
                        f"`dscli profile {armed['dir']}`)")

    def _stop(self) -> None:
        try:
            import jax
            jax.profiler.stop_trace()
            self.captures += 1
            logger.info("profile: capture complete")
        except Exception as e:
            logger.warning(f"profile: stop_trace failed ({e})")
        self.active = False
        self._stop_at = None

    def stop(self) -> None:
        """Force-stop an active capture (engine teardown safety: a trace
        left open keeps the profiler session dangling)."""
        with self._lock:
            if self.active:
                self._stop()

    @contextmanager
    def annotate(self, name: str):
        """Accelerator ``TraceAnnotation`` around the with-block while a
        capture is active (no-op otherwise) — the host-side span marker
        on the device timeline."""
        if not self.active:
            yield
            return
        try:
            from deepspeed_tpu.accelerator import get_accelerator
            acc = get_accelerator()
        except Exception:
            acc = None
        if acc is not None:
            acc.range_push(name)
        try:
            yield
        finally:
            if acc is not None:
                acc.range_pop()


# ------------------------------------------------------------------ #
# compile watchdog

# Detection: jax emits a '/jax/core/compile/backend_compile_duration'
# monitoring event for every REAL XLA compile. A thread-local accumulator
# attributes those events to the watched call in flight — unlike a
# jit-cache-size heuristic this never miscounts C++ fastpath-cache
# signature misses (e.g. donated-output arrays re-entering a step) as
# compiles.

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_tls = threading.local()
_listener_registered = False


def _compile_listener(name: str, dur: float, **kw) -> None:
    if name != _COMPILE_EVENT:
        return
    acc = getattr(_tls, "acc", None)
    if acc is not None:
        acc.append(dur)


def _ensure_compile_listener() -> None:
    global _listener_registered
    if not _listener_registered:
        _listener_registered = True
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _compile_listener)


def _abstract_signature(args, kwargs, max_leaves: int = 24) -> str:
    """Compact dtype[shape] signature of a call's inputs — the shape set
    that *caused* a compilation, for the recompile post-mortem."""
    try:
        import jax
        leaves = jax.tree.leaves((args, kwargs))
    except Exception:
        leaves = list(args) + list(kwargs.values())
    sigs = []
    for leaf in leaves[:max_leaves]:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None:
            sigs.append(f"{getattr(dtype, 'name', dtype)}[{','.join(map(str, shape))}]")
        else:
            sigs.append(type(leaf).__name__)
    if len(leaves) > max_leaves:
        sigs.append(f"...+{len(leaves) - max_leaves}")
    return "(" + ", ".join(sigs) + ")"


class CompileWatchdog:
    """Counts compilations per watched entry point and flags storms."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 storm_threshold: int = 8, storm_window_s: float = 300.0):
        self.registry = registry if registry is not None else get_registry()
        self.storm_threshold = storm_threshold
        self.storm_window_s = storm_window_s
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._recent: Dict[str, List[float]] = {}   # compile timestamps
        self._warned_at: Dict[str, int] = {}
        self.events: List[Dict[str, Any]] = []      # compile records

    def _metrics(self):
        # resolved per compile (rare) rather than cached: a registry
        # reset between bench metrics must not orphan the families
        return (self.registry.counter(
                    "compile/count",
                    "XLA compilations per watched jit entry point",
                    labelnames=("fn",)),
                self.registry.histogram(
                    "compile/time_ms",
                    "compile wall time (incl. triggering run)",
                    labelnames=("fn",)))

    # ---- wrapping ---- #

    def watch(self, jitted, name: str):
        """Wrap an already-``jax.jit``-ed callable. The wrapper forwards
        the call unchanged (donation/sharding semantics are the inner
        function's) and records one compile — with the summed backend
        compile wall time and the triggering abstract input shapes —
        whenever XLA actually compiled during the call."""
        _ensure_compile_listener()

        def wrapped(*args, **kwargs):
            prev = getattr(_tls, "acc", None)
            _tls.acc = acc = []
            try:
                out = jitted(*args, **kwargs)
            finally:
                _tls.acc = prev
            if acc:
                self._record(name, sum(acc),
                             _abstract_signature(args, kwargs))
            return out

        wrapped.__name__ = f"watched[{name}]"
        wrapped.inner = jitted
        return wrapped

    def jit(self, fn, name: Optional[str] = None, **jit_kwargs):
        """``jax.jit`` + watch in one call — the framework-side entry
        point replacement."""
        import jax
        return self.watch(jax.jit(fn, **jit_kwargs),
                          name or getattr(fn, "__name__", "jit"))

    # ---- recording ---- #

    def _record(self, name: str, wall_s: float, signature: str) -> None:
        now = time.perf_counter()
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + 1
            count = self._counts[name]
            recent = self._recent.setdefault(name, [])
            recent.append(now)
            cutoff = now - self.storm_window_s
            while recent and recent[0] < cutoff:
                recent.pop(0)
            in_window = len(recent)
            self.events.append({"name": name, "wall_time_s": wall_s,
                                "shapes": signature, "count": count})
            should_warn = in_window >= self.storm_threshold and \
                self._warned_at.get(name, 0) < count
            if should_warn:
                # re-arm only after another full threshold of compiles, so
                # a sustained storm warns periodically, not every step
                self._warned_at[name] = count + self.storm_threshold - 1
        count_metric, time_metric = self._metrics()
        count_metric.labels(fn=name).inc()
        time_metric.labels(fn=name).observe(wall_s * 1e3)
        if should_warn:
            logger.warning(
                f"recompilation storm: {name!r} compiled {in_window} times in "
                f"the last {self.storm_window_s:.0f}s ({count} total; latest "
                f"inputs {signature}). Recompiles silently serialize every "
                "step behind XLA — check for shape churn (pad/bucket inputs), "
                "python scalars that should be jnp arrays, or weak_type flap.")

    # ---- queries ---- #

    def compile_count(self, name: Optional[str] = None) -> int:
        with self._lock:
            if name is not None:
                return self._counts.get(name, 0)
            return sum(self._counts.values())

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            return {"total": sum(self._counts.values()),
                    "by_fn": dict(self._counts),
                    "events": list(self.events[-50:])}

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._recent.clear()
            self._warned_at.clear()
            self.events.clear()


# ------------------------------------------------------------------ #
# process-global instances

_tracer: Optional[StepTracer] = None
_watchdog: Optional[CompileWatchdog] = None
_lock = threading.Lock()


def get_tracer() -> StepTracer:
    global _tracer
    if _tracer is None:
        with _lock:
            if _tracer is None:
                _tracer = StepTracer()
    return _tracer


def get_compile_watchdog() -> CompileWatchdog:
    global _watchdog
    if _watchdog is None:
        with _lock:
            if _watchdog is None:
                _watchdog = CompileWatchdog()
    return _watchdog


def watched_jit(fn, name: Optional[str] = None, **jit_kwargs):
    """Module-level convenience: ``jax.jit`` through the global watchdog."""
    return get_compile_watchdog().jit(fn, name=name, **jit_kwargs)

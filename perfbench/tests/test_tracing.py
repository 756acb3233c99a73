"""The mid-window trace survives a profiler that raises: the error is kept,
the trace goes back to idle, and one more attempt is due after a pause."""

import jax

import tracing


def _trace(monkeypatch, tmp_path, start=None, stop=None):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: (calls.append("start"), start and start(calls)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: (calls.append("stop"), stop and stop(calls)))
    monkeypatch.setattr(tracing.MidWindowTrace, "RETRY_AFTER_S", 0.0)
    return tracing.MidWindowTrace(True, str(tmp_path / "trace")), calls


def _raise_first(kind):
    def fn(calls):
        if calls.count(kind) == 1:
            raise RuntimeError("refused")
    return fn


def test_disabled_is_inert(tmp_path):
    t = tracing.MidWindowTrace(False, None)
    assert t.done and not t.can_start and not t.active
    t.start(), t.stop()
    assert t.attempts == 0 and not t.errors


def test_a_clean_trace(monkeypatch, tmp_path):
    t, calls = _trace(monkeypatch, tmp_path)
    assert t.can_start and not t.done
    t.start()
    assert t.active and not t.can_start
    t.stop()
    assert t.done and not t.active and not t.can_start and not t.errors
    assert calls == ["start", "stop"] and t.started_at <= t.stopped_at


def test_start_raises_once(monkeypatch, tmp_path):
    t, calls = _trace(monkeypatch, tmp_path, start=_raise_first("start"))
    t.start()
    assert not t.active and not t.done and len(t.errors) == 1 and t.can_start
    t.start()
    assert t.active
    t.stop()
    assert t.done and t.attempts == 2


def test_stop_raises_once_then_attempts_run_out(monkeypatch, tmp_path):
    t, calls = _trace(monkeypatch, tmp_path, stop=_raise_first("stop"))
    t.start(), t.stop()
    assert not t.done and not t.active and t.can_start and len(t.errors) == 1
    t.start(), t.stop()
    assert t.done and calls == ["start", "stop", "start", "stop"]
    t.start()                               # nothing left to do
    assert calls.count("start") == 2


def test_the_second_attempt_waits(monkeypatch, tmp_path):
    t, calls = _trace(monkeypatch, tmp_path, start=_raise_first("start"))
    monkeypatch.setattr(tracing.MidWindowTrace, "RETRY_AFTER_S", 3600.0)
    t.start()
    assert not t.can_start
    t.start()
    assert calls == ["start"]

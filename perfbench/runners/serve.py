"""The serve runner: ``init_inference`` + ``AsyncServingEngine.add_request``
/ ``handle.stream()`` — what ``dscli serve`` builds behind its HTTP front.

Telemetry is on for the registry's counters only; events stay off (with
events on the program blocks on the device to time its spans, ROADMAP D5).
All latencies are the client's: a thread per request in flight sends it,
reads ``handle.stream()`` and stamps each burst as it arrives. An open loop
follows an ABSOLUTE schedule (request i is due at t0 + a_i whatever happened
before) and times each request from when it was due; how late the generator
itself ran is reported.
"""

from __future__ import annotations

import re
import threading
import time

import numpy as np

import correctness
from build_model import build_model
import traffic as traffic_mod
from weights import make_params

#: a tenth of the requests sent later than this and the latencies cannot be
#: trusted: the run is not ``correct``. The 90th percentile, because a window
#: of 82 requests has one beyond its 99th: a single late wake-up of the
#: generator's thread (seen in a third of the runs, 40-110 ms) moves no
#: percentile that is judged
GENERATOR_LATE_LIMIT_MS = 25.0
#: a histogram is read as the difference of two ``count_le`` ladders
LADDER = np.geomspace(0.01, 1e7, 600)



class _Rec:
    __slots__ = ("index", "due", "sent", "stamps", "counts", "status",
                 "max_new", "n_prompt", "error")

    def __init__(self, index, due, max_new, n_prompt):
        self.index, self.due, self.max_new, self.n_prompt = \
            index, due, max_new, n_prompt
        self.sent = None
        self.stamps, self.counts = [], []
        self.status, self.error = "pending", None

    @property
    def tokens(self):
        return sum(self.counts)


class ServeRunner:
    kind = "serve"

    def __init__(self, cell, config, spec, seed, say):
        self.cell, self.config, self.spec = cell, config, spec
        self.seed, self.say = seed, say
        self.chips = cell["chips"]

    # ------------------------------------------------------------------ #

    def weights_seed(self):
        """``--seed``, unless the mix names a ``weights_seed``: where a chip
        holds a share of a router's experts, how many of them a step touches
        (and so its time) follows the seeded router's skew, and the mix gives
        every ``--seed`` the same weights (``traffic.py``). Prompts, and the
        check's, are the seed's in either case."""
        return int(self.spec.get("weights_seed", self.seed))

    def setup(self):
        import jax
        import jax.numpy as jnp

        import deepspeed_tpu
        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.inference.serve import AsyncServingEngine
        from deepspeed_tpu.monitor.metrics import get_registry

        cfgf, spec = self.config, self.spec
        preset = cfgf["preset"]
        self.model = build_model(preset)
        mcfg = self.model.config
        serve = cfgf["assumed"]["serve"]
        self.rows = int(serve["max_running"])
        self.pool_blocks = int(serve["max_num_blocks"])
        self.traffic = traffic_mod.ServeTraffic(
            spec, mcfg.vocab_size, self.seed, cfgf.get("length_scale", 1.0))
        if self.traffic.longest_request() > mcfg.max_seq:
            raise ValueError("the mix's longest request exceeds max_seq")
        t0 = time.perf_counter()
        params = make_params(self.model, self.weights_seed(), jnp.bfloat16,
                             jax.devices()[:1])
        jax.block_until_ready(params)
        self.say(f"weights: {time.perf_counter() - t0:.1f}s, bf16, seeded "
                 f"({self.weights_seed()})")
        self.engine = deepspeed_tpu.init_inference(
            self.model, params=params, dtype="bf16",
            telemetry={"enabled": True},
            serving={"block_size": int(serve["block_size"]),
                     "max_running": self.rows,
                     "max_num_blocks": int(serve["max_num_blocks"])})
        del params
        self.registry = get_registry()
        self.serving = AsyncServingEngine(
            self.engine, max_new_tokens=mcfg.max_seq)
        # warm-up: one request alone per prompt-length bucket of this mix
        # (the program's own bucketing), two tokens each, so the prefill
        # program of that bucket and the decode program have run
        buckets = {}
        for lo, hi in self.traffic.prompt_bounds():
            for n in range(lo, hi + 1):
                buckets.setdefault(InferenceEngine._bucket(n, mcfg.max_seq), n)
        rng = np.random.default_rng([self.seed, 9])
        for b, n in sorted(buckets.items()):
            t0 = time.perf_counter()
            h = self.serving.add_request(
                rng.integers(0, mcfg.vocab_size, size=n), max_new_tokens=2)
            got = sum(len(burst) for burst in h.stream(timeout=1100))
            if got != 2:
                raise RuntimeError(f"warm-up of bucket {b} gave {got} tokens")
            self.say(f"warm-up: prompt bucket {b}: "
                     f"{time.perf_counter() - t0:.2f}s")

    # ------------------------------------------------------------------ #
    # the client side

    def _request(self, req, due):
        """One request on the calling thread, from ``add_request`` to its
        last token. Once the window is closed, a request is cut by us: at
        once if it is sent then, else after its next burst."""
        rec = _Rec(req["index"], due, req["max_new"], len(req["prompt"]))
        with self._lock:
            self.recs.append(rec)
        rec.sent = time.perf_counter()
        try:
            handle = self.serving.add_request(req["prompt"],
                                              max_new_tokens=req["max_new"])
            with self._lock:
                self._handles[rec.index] = handle
                cut = self._closing
                if cut:
                    self.cancelled.add(rec.index)
            if cut:
                handle.cancel()
            for burst in handle.stream(timeout=300):
                rec.stamps.append(time.perf_counter())
                rec.counts.append(len(burst))
                if self._closing and rec.index not in self.cancelled:
                    # the window is over and this request has its first
                    # token: the rest of it would only be waited for
                    with self._lock:
                        self.cancelled.add(rec.index)
                    handle.cancel()
            rec.status = handle.status
        except Exception as e:  # noqa: BLE001 — a failed request is counted, not raised
            rec.status, rec.error = "error", repr(e)
        finally:
            with self._lock:
                self._handles.pop(rec.index, None)

    def _snapshot(self):
        snap = self.registry.snapshot()
        hist = self.registry.histogram("serving/queue_wait_ms")
        return {"counters": dict(snap["counters"]),
                "gauges": dict(snap["gauges"]),
                "ladders": {"serving/queue_wait_ms":
                            [hist.count_le(float(v)) for v in LADDER]}}

    def window(self, seconds, trace):
        """One window. What is in flight at its close is cut by us and held
        to no length: a closed loop's requests at once, an open loop's each
        after its next burst, so that every request that was due inside the
        window still has its time to first token."""
        spec = self.spec
        ramp = float(spec["ramp_s"])
        self._lock = threading.Lock()
        self._handles = {}               # requests in flight, by index
        self._closing = False            # we are cancelling what is in flight
        self.recs = []
        self.cancelled = set()           # in flight at the close, cut by us
        marks = {}
        trace_len = float(spec["trace_seconds"])

        blocks_used = self.registry.gauge("serving/kv_blocks_used")
        marks["blocks_used_peak"] = 0.0

        def on_clock(t0):
            """Window marks, taken by the thread that drives the load."""
            now = time.perf_counter() - t0
            marks["blocks_used_peak"] = max(marks["blocks_used_peak"],
                                            blocks_used.value)
            if "start" not in marks and now >= 0:
                marks["start"] = self._snapshot()
            # the profiler starts and stops on threads of its own (either
            # takes long enough to make the generator late), one at a time
            th = marks.get("trace_thread")
            if th is not None and th.is_alive():
                return
            if trace.can_start and now >= 0.4 * seconds:
                th = threading.Thread(target=trace.start, daemon=True)
            elif trace.active and \
                    time.perf_counter() - trace.started_at >= trace_len:
                th = threading.Thread(target=trace.stop, daemon=True)
            else:
                return
            marks["trace_thread"] = th
            th.start()

        t0 = time.perf_counter() + ramp
        self._stall = (0.0, 0.0)         # (longest hold-up s, from s)
        self._ticked = time.perf_counter()
        if spec["loop"] == "open":
            late = self._open_loop(t0, seconds, ramp, on_clock)
        else:
            late = self._closed_loop(t0, seconds, on_clock)
        marks["end"] = self._snapshot()
        self.say(f"load thread: longest hold-up {self._stall[0] * 1e3:.1f} ms "
                 f"from {self._stall[1]:.2f} s")
        t_close = t0 + seconds
        with self._lock:
            self._closing = True         # each request cuts itself from now
            cut = []
            if spec["loop"] == "closed":
                self.cancelled |= set(self._handles)
                cut = list(self._handles.values())
        for h in cut:
            h.cancel()
        # bounded wait for the cut to reach every request
        deadline = time.perf_counter() + float(spec.get("drain_s", 60))
        for th in self._threads:
            th.join(max(deadline - time.perf_counter(), 0.0))
        th = marks.pop("trace_thread", None)
        if th is not None:
            th.join(120)
        if th is not None and th.is_alive():
            trace.errors.append("the profiler's thread did not return")
        else:
            trace.stop()                 # a window too short to end it
        facts = self._facts(t0, t_close, seconds, late, marks)
        facts["trace_host_window"] = (trace.started_at, trace.stopped_at)
        return facts

    def _tick(self, t0, dt, on_clock):
        """Sleep ``dt`` on the thread that drives the load and keep its
        longest hold-up since the tick before (an oversleep, or a thread
        that would not start): that thread only sleeps and starts threads,
        so what holds it up held the whole process up."""
        time.sleep(dt)
        now = time.perf_counter()
        over = now - self._ticked - dt
        if over > self._stall[0]:
            self._stall = (over, now - over - t0)
        on_clock(t0)
        self._ticked = time.perf_counter()

    def _open_loop(self, t0, seconds, ramp, on_clock):
        """Each request gets a thread of its own when it is due, which sends
        it and reads its stream: a send the program holds up delays no other
        request."""
        self._threads = []
        late = []                        # (due s, ms late)
        plan = self.traffic.open_plan(ramp, seconds)
        for req in plan:
            a = req["due"]
            while True:
                wait = t0 + a - time.perf_counter()
                if wait <= 0:
                    break
                self._tick(t0, min(wait, 0.02), on_clock)
            late.append((a, -wait * 1e3))
            th = threading.Thread(target=self._request, args=(req, t0 + a),
                                  daemon=True)
            th.start()
            self._threads.append(th)
        while time.perf_counter() < t0 + seconds:
            self._tick(t0, 0.005, on_clock)
        on_clock(t0)
        self.say(f"generator: {len(plan)} requests; latest (ms late, due s): "
                 f"{[(round(l, 2), round(a, 2)) for a, l in sorted(late, key=lambda x: -x[1])[:3]]}")
        return [l for a, l in late if a >= 0]

    def _closed_loop(self, t0, seconds, on_clock):
        n_clients = int(round(float(self.spec["clients_per_row"]) * self.rows))
        counter = iter(range(1 << 30))
        stop = threading.Event()

        def client():
            while not stop.is_set():
                with self._lock:
                    i = next(counter)
                self._request(self.traffic.request(i), None)

        clients = [threading.Thread(target=client, daemon=True)
                   for _ in range(n_clients)]
        for c in clients:
            c.start()
        while time.perf_counter() < t0 + seconds:
            self._tick(t0, 0.005, on_clock)
        on_clock(t0)
        stop.set()
        self._threads = clients
        return []

    # ------------------------------------------------------------------ #

    def _facts(self, t0, t_close, seconds, late, marks):
        """What the clients saw. ``attempted``: an open loop's requests due
        inside the window; a closed loop's requests that ended inside it
        (those we cancelled at the close are held to nothing). Gaps between
        tokens count where the later token arrived inside the window,
        whichever request it belongs to."""
        ttft, itl, first_tokens = [], [], []
        tokens_in_window = attempted = failed = 0
        open_loop = self.spec["loop"] == "open"
        # requests waiting for their first token, averaged over the second
        # and over the last quarter of the window: the queue, not the batch
        quarters = {"waiting_mid": (t0 + 0.25 * seconds, t0 + 0.5 * seconds),
                    "waiting_end": (t0 + 0.75 * seconds, t_close)}
        waiting = dict.fromkeys(quarters, 0.0)
        for r in self.recs:
            # a thread the drain did not wait out may still be appending
            # (stamp first, then count): take the pairs that are whole
            counts = np.asarray(list(r.counts))
            stamps = np.asarray(r.stamps[:len(counts)])
            start = r.due if open_loop else r.sent
            first = stamps[0] if len(stamps) else np.inf
            for key, (a, b) in quarters.items():
                waiting[key] += max(min(first, b) - max(start, a), 0.0) / (b - a)
            if len(stamps):
                inside = (stamps >= t0) & (stamps < t_close)
                tokens_in_window += int(counts[inside].sum())
                first_tokens.append((float(stamps[0]), r.n_prompt))
                itl.extend(np.diff(stamps)[inside[1:]] * 1e3)
            if t0 <= start < t_close and len(stamps):
                ttft.append((stamps[0] - start) * 1e3)
            cut = r.index in self.cancelled
            if open_loop:
                counted = t0 <= r.due < t_close
            else:
                counted = (not len(stamps) or stamps[-1] >= t0) and not cut
            if counted:
                attempted += 1
                if not cut and (r.status != "finished"
                                or r.tokens != r.max_new):
                    failed += 1
                    self.say(f"request {r.index}: {r.status}, {r.tokens} of "
                             f"{r.max_new} tokens, {r.error}")
        return {
            "t_open": t0, "t_close": t_close, "seconds": seconds,
            "attempted": attempted, "failed": failed,
            "tokens_in_window": tokens_in_window,
            "client": {"ttft_ms": ttft, "itl_ms": itl, "late_ms": late},
            "first_tokens": first_tokens,
            "marks": marks, "rows": self.rows,
            **waiting,
            "pool_peak_used_share": 100.0 * marks["blocks_used_peak"]
            / max(self.pool_blocks - 1, 1),
        }

    def end_to_end(self, facts, names):
        """``serve_out_tokens_per_s``, and any ``ttft_p<q>_ms`` or
        ``itl_p<q>_ms`` among ``names``: percentile ``q`` of the client's
        times to first token (from when each request was due) and of the
        gaps between its tokens."""
        w = facts["window"]
        out = {"serve_out_tokens_per_s":
               (w["tokens_in_window"] / w["seconds"], "tokens/s")}
        for name in names:
            got = re.fullmatch(r"(ttft|itl)_p(\d+)_ms", name)
            series = got and w["client"][got.group(1) + "_ms"]
            if not series:
                continue
            if got.group(1) == "ttft":
                # a failed or refused request misses every limit: it sits
                # beyond the percentile, as the worst seen
                series = np.concatenate(
                    [series, [np.max(series)] * w["failed"]])
            out[name] = (float(np.percentile(series, int(got.group(2)))), "ms")
        return out

    def shapes(self, dims):
        return {**dims, "rows": self.rows}

    # ------------------------------------------------------------------ #

    def check(self, facts, name_map):
        w = facts["window"]
        counters = w["marks"]["end"]["counters"]
        started = w["marks"]["start"]["counters"]
        faults = {k: v - started.get(k, 0) for k, v in counters.items()
                  if k.startswith(("serving/step_faults", "serving/request_retries",
                                   "serving/engine_restarts", "serving/timeouts",
                                   "serving/shed_requests",
                                   "serving/rejected_requests"))
                  and v - started.get(k, 0)}
        late = w["client"]["late_ms"]
        late_p90, late_p99 = (np.percentile(late, [90, 99]) if late
                              else (0.0, 0.0))
        # what /healthz would say: a loop that stopped, crashed or sits in
        # its crash-loop breaker takes no request, and the run is a result
        # all the same (its requests failed; ``correct`` is false)
        code, health = self.serving.health_state()
        if self.serving.error is not None:
            health["error"] = repr(self.serving.error)
        cfg = correctness.reference_config(self.config, name_map)
        weights = correctness.Weights(self.engine.params, name_map)
        rng = np.random.default_rng([self.seed, 11])
        vocab = self.model.config.vocab_size
        want = int(self.spec["check"]["tokens"])
        served = []
        for n in self._check_lengths() if code == 200 else ():
            prompt = rng.integers(0, vocab, size=n).astype(np.int32)
            h = self.serving.add_request(prompt, max_new_tokens=want)
            toks, raised = [], None
            try:
                for burst in h.stream(timeout=600):
                    toks.extend(burst)
            except Exception as e:  # noqa: BLE001 — a failed request is a verdict
                raised = repr(e)
            if len(toks) != want:
                served.append({"ok": False, "prompt_tokens": n,
                               "served": len(toks), "status": h.status,
                               "error": h.error or raised})
                continue
            served.append(correctness.check_served(cfg, weights, prompt, toks))
        ok = bool(not faults and w["failed"] == 0 and w["attempted"] > 0
                  and late_p90 <= GENERATOR_LATE_LIMIT_MS
                  and code == 200 and all(s["ok"] for s in served))
        return {"ok": ok, "faults": faults, "failed": w["failed"],
                "generator_late_p90_ms": float(late_p90),
                "generator_late_p99_ms": float(late_p99),
                "engine": health, "reference": served}

    def _check_lengths(self):
        """Two prompts, inside buckets the window warmed: the shortest class
        at its low end, the longest class at its low end."""
        bounds = self.traffic.prompt_bounds()
        lo = min(bounds)[0]
        hi = max(bounds)[0]
        return [lo + 1, hi + 1] if hi != lo else [lo + 1, lo + 2]

    def close(self):
        self.serving.shutdown(drain=False, timeout=60)

"""``dscli`` — the framework's command-line front door (reference ``bin/``).

Subcommands mirror the reference's script family:

- ``dscli run <script> [args...]``  — the ``deepspeed`` launcher CLI
- ``dscli serve [--model m] [--port p]`` — OpenAI-style completions endpoint
  (``/v1/completions``, SSE streaming) over the async paged serving loop
- ``dscli report [--telemetry f]``  — ``ds_report`` environment/op/memory report
- ``dscli health <jsonl> [--once|--json]`` — live health screen over a telemetry sink
- ``dscli top <url|jsonl>``         — refreshing serving/training dashboard (scrapes
  ``/metrics`` or tails a sampler JSONL; SLO burn rates, KV tiers, percentiles)
- ``dscli bench``                   — ``ds_bench`` collective micro-benchmarks
- ``dscli ckpt verify <dir>``       — checkpoint integrity audit (per-tag manifest check)
- ``dscli lint``                    — dslint trace-safety static analysis (rc=1 on new findings)
- ``dscli trace --validate <path>`` — chrome-trace / events.jsonl schema check
- ``dscli ctl replay|explain <events.jsonl>`` — adaptive-controller decision-
  ledger audit: re-run the pure decision core over the recorded observations
  (rc=1 on divergence) or print the human-readable decision story
- ``dscli profile <logdir|trace>``  — summarize a jax.profiler capture / chrome trace
- ``dscli elastic <config>``        — ``ds_elastic`` elastic-config inspector
- ``dscli autotune <config>``       — ``deepspeed --autotuning`` config search
- ``dscli ssh [-f hostfile] cmd``   — ``ds_ssh`` run a command on every host
"""

from __future__ import annotations

import sys


def _run(argv):
    from deepspeed_tpu.launcher import runner
    runner.main(argv)


def _serve(argv):
    """``dscli serve`` — stand up the always-on async serving loop behind
    an OpenAI-style HTTP endpoint (``POST /v1/completions``, with
    ``"stream": true`` server-sent events). Prompts are token-id lists;
    see ``docs/api.md`` "Async serving" for a curl example."""
    from deepspeed_tpu.inference.serve import serve_main
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    return serve_main(argv)


def _report(argv):
    import argparse

    parser = argparse.ArgumentParser(
        description="environment / op / device-memory report")
    parser.add_argument("--telemetry", type=str, default=None,
                        help="JSONL telemetry sink path; also prints the "
                             "latest snapshot summary")
    args = parser.parse_args(argv)
    from deepspeed_tpu import env_report
    env_report.main(telemetry_path=args.telemetry)


def _health(argv):
    """Live one-screen training/serving health table tailing a JSONL
    telemetry sink (``telemetry.jsonl_path``); ``--once`` renders once."""
    from deepspeed_tpu.monitor.health import health_cli
    return health_cli(argv)


def _top(argv):
    """``dscli top`` — refreshing serving/training dashboard over a
    ``/metrics`` scrape URL (``dscli serve`` exposes one) or a sampler/
    telemetry JSONL: queue depth, TTFT/TPOT percentiles, KV pool + host
    tier, SLO burn rates, loss EWMA, tokens/s."""
    from deepspeed_tpu.monitor.top import top_cli
    return top_cli(argv)


def _bench(argv):
    from deepspeed_tpu.benchmarks.comm_bench import main as bench_main
    bench_main(argv)


def _ckpt(argv):
    """Checkpoint maintenance. ``verify <dir>`` full-checks every tag's
    blake2b manifest and prints INTACT/CORRUPT per tag; exit code 1 when
    any tag is corrupt (CI-friendly)."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="dscli ckpt", description="checkpoint maintenance tools")
    sub = parser.add_subparsers(dest="action", required=True)
    vp = sub.add_parser("verify", help="verify every tag's manifest")
    vp.add_argument("dir", type=str, help="checkpoint save_dir (tag parent)")
    vp.add_argument("--tag", type=str, default=None,
                    help="verify only this tag")
    args = parser.parse_args(argv)

    import os

    from deepspeed_tpu.runtime.checkpoint_engine import safe_engine

    save_dir = os.path.abspath(args.dir)
    reports = ([safe_engine.verify_tag(os.path.join(save_dir, args.tag))]
               if args.tag else
               [safe_engine.verify_tag(r.path)
                for r in safe_engine.list_tags(save_dir)])
    if not reports:
        print(f"no checkpoint tags under {save_dir}")
        return 1
    latest = safe_engine._latest_target(save_dir)
    corrupt = 0
    for rep in reports:
        if rep.legacy:
            status = "LEGACY  (orbax tag: loadable, no manifest to verify)"
        elif rep.intact:
            status = "INTACT"
        else:
            corrupt += 1
            status = "CORRUPT (" + "; ".join(rep.errors) + ")"
        steps = "-" if rep.global_steps is None else str(rep.global_steps)
        mark = " <- latest" if rep.tag == latest else ""
        print(f"{rep.tag:<28} step {steps:<10} {status}{mark}")
    if latest and all(r.tag != latest for r in reports) and not args.tag:
        corrupt += 1
        print(f"latest -> {latest!r}: tag missing (CORRUPT pointer)")
    print(f"{len(reports)} tag(s), {corrupt} corrupt")
    return 1 if corrupt else 0


def _load_dslint():
    """Import ``tools/dslint`` (repo-level tool package, not a package
    module — the same analyzer CI runs standalone) off the checkout's
    tools/ directory."""
    import importlib
    import os

    import deepspeed_tpu
    tools_dir = os.path.abspath(os.path.join(
        os.path.dirname(deepspeed_tpu.__file__), "..", "tools"))
    if not os.path.isdir(os.path.join(tools_dir, "dslint")):
        raise RuntimeError(
            f"tools/dslint not found under {tools_dir} (run from a source "
            "checkout, or `python tools/dslint` directly)")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    return importlib.import_module("dslint")


def _lint(argv):
    """``dscli lint`` — trace-safety static analysis over the package.
    rc=0 clean / rc=1 on findings not in tools/dslint_baseline.json,
    matching ``dscli trace --validate`` semantics."""
    return _load_dslint().main(argv)


def _load_validator():
    """Load ``tools/validate_trace.py`` (repo-level tool, not a package
    module — the same file CI runs standalone) by path."""
    import importlib.util
    import os

    import deepspeed_tpu
    path = os.path.abspath(os.path.join(
        os.path.dirname(deepspeed_tpu.__file__), "..", "tools",
        "validate_trace.py"))
    if not os.path.isfile(path):
        raise RuntimeError(
            f"tools/validate_trace.py not found at {path} (run from a "
            "source checkout, or invoke the script directly)")
    spec = importlib.util.spec_from_file_location("validate_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trace(argv):
    """Trace tooling. ``dscli trace <request-id> --events <jsonl>``
    prints one request's latency anatomy (the phase ledger, recomputed
    from the flight-recorder export — ``<request-id>`` is an integer rid
    or a router trace id like ``t0``, which prints every leg of the
    causal chain plus the handoff hops). ``--validate <path>...``
    schema-checks chrome-trace JSON / events.jsonl exports (rc=1 on
    violations)."""
    import argparse
    import json as _json

    parser = argparse.ArgumentParser(
        prog="dscli trace",
        description="request latency anatomy + chrome-trace/events.jsonl "
                    "schema validation")
    parser.add_argument("request_id", nargs="?", default=None,
                        help="rid (integer) or trace id (t<seq>) to "
                             "decompose; needs --events")
    parser.add_argument("--events", metavar="PATH", default=None,
                        help="flight-recorder events.jsonl export "
                             "(FlightRecorder.write_jsonl) to read the "
                             "anatomy from")
    parser.add_argument("--json", action="store_true",
                        help="print the anatomy as JSON instead of the "
                             "phase table")
    parser.add_argument("--validate", nargs="+", metavar="PATH",
                        default=None, help="file(s) to schema-validate")
    parser.add_argument("--kind", choices=("auto", "chrome", "events"),
                        default="auto")
    args = parser.parse_args(argv)
    if args.validate is not None:
        return _load_validator().main(["--kind", args.kind] + args.validate)
    if args.request_id is None:
        parser.error("need a <request-id> (with --events) or --validate")
    if args.events is None:
        parser.error("anatomy needs --events <events.jsonl> (export one "
                     "with engine.export_events / the serve front-end)")
    from deepspeed_tpu.monitor.anatomy import (
        format_anatomy, format_trace_anatomy, request_anatomy,
        resolve_request_id, trace_anatomy)
    events = []
    with open(args.events) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(_json.loads(line))
    trace, rid = resolve_request_id(args.request_id)
    if rid is not None:
        a = request_anatomy(events, rid)
        if a is None:
            print(f"rid {rid}: no events in {args.events}")
            return 1
        print(_json.dumps(a) if args.json else format_anatomy(a))
        return 0
    t = trace_anatomy(events, trace)
    if t is None:
        print(f"trace {trace}: no req.enqueue carries it in {args.events}")
        return 1
    print(_json.dumps(t) if args.json else format_trace_anatomy(t))
    return 0


def _ctl(argv):
    """``dscli ctl`` — audit an adaptive-controller decision ledger
    (a flight-recorder ``events.jsonl`` export holding ``ctl.*``
    events). ``replay`` re-runs the pure decision core over the recorded
    ``ctl.observe`` trace and diffs against the recorded ``ctl.decide``
    sequence — rc=0 on an exact reproduction, rc=1 on divergence (a
    divergence means the controller was NOT a pure function of its
    observations: nondeterminism worth paging on). ``explain`` prints
    the decision story: every knob movement with the burn/pressure
    observation that triggered it."""
    import argparse
    import json as _json

    parser = argparse.ArgumentParser(
        prog="dscli ctl",
        description="adaptive-controller decision-ledger audit "
                    "(monitor/controller.py)")
    sub = parser.add_subparsers(dest="action", required=True)
    rp = sub.add_parser("replay", help="re-run the decision core over the "
                                       "recorded observations and diff")
    rp.add_argument("events", help="events.jsonl ledger export")
    rp.add_argument("--json", action="store_true",
                    help="print the replayed action sequence as JSON")
    xp = sub.add_parser("explain", help="print the human-readable "
                                        "decision story")
    xp.add_argument("events", help="events.jsonl ledger export")
    args = parser.parse_args(argv)

    from deepspeed_tpu.monitor.controller import (
        explain_decisions, recorded_decisions, replay_decisions)
    if args.action == "explain":
        lines = explain_decisions(args.events)
        if not lines:
            print(f"{args.events}: no ctl.* events (run with --adaptive "
                  "/ telemetry.ctl enabled and export the recorder)")
            return 1
        for line in lines:
            print(line)
        return 0
    try:
        replayed = replay_decisions(args.events)
    except ValueError as e:
        print(f"replay failed: {e}")
        return 1
    recorded = recorded_decisions(args.events)
    if args.json:
        print(_json.dumps(replayed))
    if replayed == recorded:
        print(f"replay OK: {len(recorded)} action(s) reproduced exactly")
        return 0
    print(f"REPLAY DIVERGED: {len(recorded)} recorded vs "
          f"{len(replayed)} replayed action(s)")
    for i, (a, b) in enumerate(zip(recorded, replayed)):
        if a != b:
            print(f"  first divergence at action #{i}:")
            print(f"    recorded: {_json.dumps(a, sort_keys=True)}")
            print(f"    replayed: {_json.dumps(b, sort_keys=True)}")
            break
    return 1


def _profile(argv):
    """Summarize a profiling artifact: a ``jax.profiler`` capture dir
    (``telemetry.profile`` / ``engine.profile(steps=N)``) — run inventory
    plus how to open it — or a chrome-trace JSON (``export_trace`` /
    ``export_serving_trace``) — per-span statistics."""
    import argparse
    import json as _json
    import os

    parser = argparse.ArgumentParser(
        prog="dscli profile",
        description="summarize a jax.profiler logdir or chrome-trace JSON")
    parser.add_argument("path", help="profiler logdir or trace .json")
    parser.add_argument("--top", type=int, default=20,
                        help="spans to show for a chrome trace (default 20)")
    args = parser.parse_args(argv)
    path = os.path.abspath(args.path)

    if os.path.isfile(path):
        # chrome-trace JSON: per-name span statistics
        try:
            with open(path) as f:
                doc = _json.load(f)
            events = doc.get("traceEvents", [])
        except ValueError:
            print(f"{path}: not JSON (for xplane.pb captures pass the "
                  "logdir, then open it in TensorBoard/xprof)")
            return 1
        spans = {}
        for ev in events:
            if ev.get("ph") == "X" and isinstance(ev.get("dur"), (int, float)):
                s = spans.setdefault(ev.get("name", "?"),
                                     {"n": 0, "total_us": 0.0, "max_us": 0.0})
                s["n"] += 1
                s["total_us"] += ev["dur"]
                s["max_us"] = max(s["max_us"], ev["dur"])
        if not spans:
            print(f"{path}: no complete (ph=X) spans")
            return 1
        print(f"{path}: {sum(s['n'] for s in spans.values())} spans, "
              f"{len(spans)} names")
        print(f"{'name':<32} {'count':>7} {'total ms':>10} {'mean ms':>9} "
              f"{'max ms':>9}")
        ranked = sorted(spans.items(), key=lambda kv: -kv[1]["total_us"])
        for name, s in ranked[:args.top]:
            print(f"{name[:32]:<32} {s['n']:>7} {s['total_us'] / 1e3:>10.2f} "
                  f"{s['total_us'] / s['n'] / 1e3:>9.3f} "
                  f"{s['max_us'] / 1e3:>9.3f}")
        if len(ranked) > args.top:
            print(f"... {len(ranked) - args.top} more (raise --top)")
        return 0

    if not os.path.isdir(path):
        print(f"{path}: no such file or directory")
        return 1
    # jax.profiler logdir: TensorBoard layout <dir>/plugins/profile/<run>/
    runs_root = os.path.join(path, "plugins", "profile")
    runs = sorted(os.listdir(runs_root)) if os.path.isdir(runs_root) else []
    if not runs:
        print(f"{path}: no profiler runs under plugins/profile/ — capture "
              "one with engine.profile(steps=N) or telemetry.profile")
        return 1
    print(f"{path}: {len(runs)} profiler run(s)")
    for run in runs:
        rdir = os.path.join(runs_root, run)
        files = sorted(os.listdir(rdir))
        total = sum(os.path.getsize(os.path.join(rdir, f)) for f in files)
        hosts = sorted({f.split(".")[0] for f in files if ".xplane.pb" in f})
        print(f"  {run}: {len(files)} file(s), {total / 1e6:.1f} MB"
              + (f", hosts: {', '.join(hosts)}" if hosts else ""))
        for f in files:
            print(f"    {f}")
    print("open with: tensorboard --logdir", path,
          " (Profile tab), or xprof")
    return 0


def _elastic(argv):
    import argparse
    import json

    from deepspeed_tpu.elasticity import compute_elastic_config

    parser = argparse.ArgumentParser(description="elastic batch-size planner")
    parser.add_argument("config", type=str, help="ds_config json path")
    parser.add_argument("-w", "--world-size", type=int, default=0)
    args = parser.parse_args(argv)
    with open(args.config) as fd:
        ds_config = json.load(fd)
    if args.world_size:
        batch, micro, gas = compute_elastic_config(ds_config, world_size=args.world_size)
        print(f"world_size={args.world_size}: train_batch={batch}, "
              f"micro_batch={micro}, gradient_accumulation_steps={gas}")
    else:
        batch, valid_worlds = compute_elastic_config(ds_config)
        print(f"valid world sizes: {valid_worlds}")
        print(f"max train_batch:   {batch}")


def _autotune(argv):
    import argparse
    import json

    parser = argparse.ArgumentParser(
        description="search zero stage x micro-batch x remat x loss-chunk "
                    "(reference: deepspeed --autotuning)")
    parser.add_argument("config", type=str, help="ds_config json path")
    parser.add_argument("--model", type=str, default="gpt2:125m",
                        help="model zoo preset, e.g. gpt2:125m, llama:tiny")
    parser.add_argument("--seq-len", type=int, default=None)
    args = parser.parse_args(argv)

    from deepspeed_tpu.autotuning import Autotuner
    from deepspeed_tpu.models.presets import get_model

    with open(args.config) as fd:
        ds_config = json.load(fd)
    name, _, size = args.model.partition(":")
    model = get_model(name, *( [size] if size else [] ))
    best = Autotuner(model, base_config=ds_config, seq_len=args.seq_len).tune()
    print(json.dumps(best, indent=2))


def _ssh(argv):
    """Broadcast a shell command to every hostfile host over pdsh
    (reference ``bin/ds_ssh``)."""
    import argparse
    import os
    import shutil
    import subprocess

    parser = argparse.ArgumentParser(description="run a command on all hosts")
    parser.add_argument("-f", "--hostfile", type=str, default=None,
                        help=f"hostfile path (default {_dlts_hostfile()})")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="command to run on every host")
    args = parser.parse_args(argv)
    if not args.command:
        parser.error("no command given")
    if shutil.which("pdsh") is None:
        raise RuntimeError("cannot find pdsh; install it (apt-get install pdsh)")

    from deepspeed_tpu.launcher.runner import fetch_hostfile
    resources = fetch_hostfile(args.hostfile or _dlts_hostfile())
    if not resources:
        raise RuntimeError(f"missing or empty hostfile "
                           f"{args.hostfile or _dlts_hostfile()}")
    hosts = ",".join(resources)
    env = dict(os.environ, PDSH_RCMD_TYPE="ssh")
    return subprocess.call(["pdsh", "-w", hosts] + args.command, env=env)


def _dlts_hostfile():
    from deepspeed_tpu.launcher.runner import DLTS_HOSTFILE
    return DLTS_HOSTFILE


_COMMANDS = {"run": _run, "serve": _serve, "report": _report,
             "health": _health, "top": _top, "bench": _bench,
             "ckpt": _ckpt, "lint": _lint, "trace": _trace, "ctl": _ctl,
             "profile": _profile, "elastic": _elastic, "autotune": _autotune,
             "ssh": _ssh}


def main():
    if len(sys.argv) < 2 or sys.argv[1] in ("-h", "--help"):
        print(__doc__)
        print("usage: dscli {run|serve|report|health|top|bench|ckpt|lint|"
              "trace|ctl|profile|elastic|autotune|ssh} [args...]")
        return 0
    cmd = sys.argv[1]
    if cmd not in _COMMANDS:
        print(f"unknown command {cmd!r}; expected one of {sorted(_COMMANDS)}")
        return 2
    rc = _COMMANDS[cmd](sys.argv[2:])
    return 0 if rc is None else rc


if __name__ == "__main__":
    sys.exit(main())

"""One cell, once:

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout, finds the cell's
configuration (``perfbench/configs/``), traffic (``perfbench/traffic/``),
per-layer metrics (``perfbench/layer_metrics/``) and their readers
(``perfbench/readers/``) by name, runs the cell through the train or the
serve runner, and prints detail lines followed by the contract's one JSON
object as the LAST line of stdout. No TPU (or fewer chips than the cell asks
for): non-zero exit and no result line. ``--rehearse`` swaps in a toy
configuration (the one the cell's configuration names, else the dense toy)
and the CPU backend for debugging; it prints ``platform=cpu`` and never a
device metric.

A run that cannot give a result prints none and says where it died through
its exit code, because the code may be all of a refusal that reaches the next
builder: 1 bad arguments or manifest (or the program is not importable), 10
no TPU or too few chips, 11 set-up raised, 12 the window raised, 13 a traced
run ended without a readable trace, 14 the correctness check raised. The
traceback is on stderr. A run whose requests failed, whose engine died or
whose check disagreed is a result: ``correct`` false, exit 0.
"""

import sys
import time

T0 = time.perf_counter()          # process start, as near as Python allows

import argparse   # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


EXIT_NO_DEVICE, EXIT_SETUP, EXIT_WINDOW, EXIT_TRACE, EXIT_CHECK = 10, 11, 12, 13, 14


def say(msg):
    print(f"[perfbench +{time.perf_counter() - T0:7.2f}s] {msg}", flush=True)


def die(code, msg):
    """No result line; ``code`` says where the run died. The process ends
    here, without the interpreter's tear-down: a device thread that is still
    running could turn the code into a signal's."""
    print(f"perfbench: {msg} (exit {code})", file=sys.stderr, flush=True)
    sys.stdout.flush()
    os._exit(code)


@contextlib.contextmanager
def stage(name, code):
    try:
        yield
    except Exception:  # noqa: BLE001 — reported, then the run ends
        traceback.print_exc()
        die(code, f"{name} raised")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(manifest, name):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        sys.exit(f"perfbench: no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    return cell, conf


def layer_metrics_for(manifest, cell_name):
    """The manifest's per-layer metrics this cell reports, each joined with
    its own file (reader and parameters)."""
    out = []
    for m in manifest["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        # "<group>.<file>": the same reading under another end-to-end metric
        # (the manifest takes one ``moves`` a name) shares the file
        spec = load_json(HERE, "layer_metrics",
                         m["name"].rpartition(".")[2] + ".json")
        out.append({**spec, **m})
    return out


def model_dims(config, name_map):
    """The sizes the cost functions and the readers see: the reference's
    configuration under its own names. The three an architecture may leave
    to convention default from the others; every other size a map declares
    (``n_experts``, ``experts_per_token``, ``d_expert``, ``window``... any
    whole number under ``from_config`` or ``fixed``) passes through."""
    import correctness
    ref = correctness.reference_config(config, name_map)
    dims = {"n_kv_head": ref["n_head"],
            "head_dim": ref["d_model"] // ref["n_head"],
            "vocab": config["vocab_size"], "positions": ref.get("positions"),
            "max_seq": config[name_map["max_seq_key"]],
            "embed_layernorm": bool(ref.get("embed_layernorm"))}
    dims.update({k: v for k, v in ref.items()
                 if isinstance(v, int) and not isinstance(v, bool)})
    return dims


class CompileCounter:
    """Compiles as jax reports them (``jax.monitoring``): a request to the
    backend compiler, whether the persistent cache answered it or not."""

    def __init__(self):
        import jax.monitoring
        self.events = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda name, **kw: self.events.update([name]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.events.update([name]))

    def snapshot(self):
        e = self.events
        return {"requests": e["/jax/core/compile/backend_compile_duration"],
                "cache_misses": e["/jax/compilation_cache/cache_misses"],
                "cache_hits": e["/jax/compilation_cache/cache_hits"]}


def device_memory(devices):
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        out[str(d.id)] = {k: int(v) for k, v in stats.items()
                          if isinstance(v, (int, float))}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse", nargs="?", const="", default=None,
                    metavar="CONFIG",
                    help="CPU backend, toy configuration (given, else the "
                         "one the cell's configuration names as its "
                         "``rehearsal``, else rehearsal-tiny): proves "
                         "nothing about the chip")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the .xplane.pb and its reduction under "
                         ".perfbench_out/ for inspection")
    args = ap.parse_args(argv)

    # any integer is a seed: numpy takes no negative one, jax none over 63 bits
    seed = args.seed % (1 << 63)
    manifest = load_json(args.manifest)
    cell, conf = find_cell(manifest, args.workload)
    chips = int(cell["chips"])
    config_name = conf["name"]
    config = load_json(ROOT, conf["file"])
    rehearsing = args.rehearse is not None
    if rehearsing:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
        # a toy of the cell's own architecture, where its configuration
        # names one
        config_name = args.rehearse or config.get("rehearsal",
                                                  "rehearsal-tiny")
        config = load_json(HERE, "configs", config_name + ".json")

    import jax

    from deepspeed_tpu.accelerator import require_tpu
    from deepspeed_tpu.ops import dispatch
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    if rehearsing:
        d0 = jax.devices()[0]
        dev = {"platform": d0.platform, "kind": d0.device_kind,
               "count": len(jax.devices())}
        if dev["platform"] != "cpu":
            sys.exit("perfbench: --rehearse is for the CPU backend")
    else:
        try:
            dev = require_tpu()
        except RuntimeError as e:
            die(EXIT_NO_DEVICE, str(e))
    if dev["count"] < chips:
        die(EXIT_NO_DEVICE, f"{args.workload} needs {chips} chips, jax sees "
                            f"{dev['count']}")
    say(f"{args.workload}: config {config_name}, traffic {cell['traffic']}, "
        f"{chips} of {dev['count']} x {dev['kind']} (platform="
        f"{dev['platform']})" + ("  *** REHEARSAL ***" if rehearsing else ""))

    peaks = load_json(HERE, "peaks.json")["device_kinds"]
    if not rehearsing and dev["kind"] not in peaks:
        die(EXIT_NO_DEVICE, f"no peaks for device kind {dev['kind']!r}")
    peak = peaks.get(dev["kind"])

    compiles = CompileCounter()
    if not rehearsing:
        cache_dir = enable_compile_cache()
        # every program, however quick to compile, comes from the cache in
        # the second run of a cell
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        say(f"compile cache at {cache_dir}")

    import correctness
    import trace_reduce
    import traffic as traffic_mod
    from tracing import MidWindowTrace

    spec = traffic_mod.load(cell["traffic"])
    name_map = correctness.load_map(config_name)
    runner_mod = importlib.import_module(f"runners.{spec['kind']}")
    runner = getattr(runner_mod, spec["kind"].capitalize() + "Runner")(
        cell, config, spec, seed, say)
    out_dir = os.path.join(ROOT, ".perfbench_out", args.workload)
    trace = MidWindowTrace(bool(args.trace), os.path.join(out_dir, "trace"))

    with stage("set-up", EXIT_SETUP):
        runner.setup()
    at_setup = compiles.snapshot()
    say(f"set-up done; compile requests {at_setup['requests']}, cache hits "
        f"{at_setup['cache_hits']}, misses {at_setup['cache_misses']}")

    with stage("the window", EXIT_WINDOW):
        window = runner.window(args.seconds, trace)
    at_close = compiles.snapshot()
    # process start to the opening of the window (a serve mix ramps first)
    setup_s = window["t_open"] - T0
    say(f"window closed: attempted {window['attempted']}, failed "
        f"{window['failed']}")

    devices = jax.devices()[:chips]
    dims = model_dims(config, name_map)
    facts = {
        "cell": cell, "chips": chips, "peak": peak, "dims": dims,
        "map": name_map,
        "shapes": runner.shapes(dims), "window": window,
        "bench": {"setup_s": setup_s,
                  "compile_cache_misses": at_setup["cache_misses"],
                  "compiles_in_window":
                      at_close["requests"] - at_setup["requests"]},
        "memory": device_memory(devices), "trace": None,
    }
    say("forms selected: " + json.dumps(sorted(dispatch.selected())))

    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": max(
                  (m.get("peak_bytes_in_use", 0)
                   for m in facts["memory"].values()), default=0)}
    breakdown = None
    if trace.errors:
        say(f"profiler, {trace.attempts} attempts: {trace.errors}")
    if trace.enabled:
        path = trace_reduce.find_xplane(trace.directory)
        if path is None:
            die(EXIT_TRACE, f"the profiler wrote no trace: {trace.errors}")
        t0 = time.perf_counter()
        with stage("reading the trace", EXIT_TRACE):
            loaded = trace_reduce.load_xplane(path)
            say(f"trace: {path} read in {time.perf_counter() - t0:.1f}s; "
                f"structure {json.dumps(loaded['structure'])[:1500]}")
            facts["trace"] = loaded
            if loaded["devices"]:
                lo, hi = trace_reduce.window_of(loaded)
                busy = [trace_reduce.busy_seconds(d["ops"] or d["programs"])
                        for d in loaded["devices"].values()]
                device["busy_s"] = sum(busy) / len(busy)
                device["window_s"] = hi - lo
                breakdown = trace_reduce.breakdown(loaded)
                busiest = loaded["devices"][trace_reduce.busiest_device(loaded)]
                say("largest device ops [name, self s, calls, scope]: "
                    + json.dumps(trace_reduce.largest_ops(
                        busiest["ops"] or busiest["programs"])))
        if args.keep_trace:
            with open(os.path.join(out_dir, "trace_reduced.json"), "w") as f:
                json.dump({k: loaded[k] for k in ("devices", "host")}, f)
        else:
            import shutil
            shutil.rmtree(trace.directory, ignore_errors=True)

    # correctness comes after the window and outside set-up
    t0 = time.perf_counter()
    with stage("the check", EXIT_CHECK):
        verdict = runner.check(facts, name_map)
    no_compiles = facts["bench"]["compiles_in_window"] == 0
    say(f"check ({time.perf_counter() - t0:.1f}s): " + json.dumps(verdict))
    correct = bool(verdict["ok"] and no_compiles)
    if not no_compiles:
        say(f"NOT correct: {facts['bench']['compiles_in_window']} compile "
            "requests inside the window")

    wanted_e2e = [m for m in manifest["end_to_end"]
                  if "workloads" not in m or cell["name"] in m["workloads"]]
    e2e = {"setup_s": (setup_s, "s"),
           **runner.end_to_end(facts, [m["name"] for m in wanted_e2e])}
    per_layer = {}
    for m in layer_metrics_for(manifest, cell["name"]):
        reader = importlib.import_module(f"readers.{m['reader']}")
        try:
            value = reader.read(m.get("params", {}), facts)
        except Exception:  # noqa: BLE001 — one reader is not the run
            traceback.print_exc()
            say(f"reader of {m['name']} raised: the metric is left out")
            value = None
        if value is not None:
            per_layer[m["name"]] = {"value": value, "unit": m["unit"]}
    say("per-layer (what this run could read): " + json.dumps(per_layer))
    say("end-to-end: " + json.dumps({k: v[0] for k, v in e2e.items()}))
    say("window: " + json.dumps({k: v for k, v in window.items()
                                 if isinstance(v, (int, float, str))}))
    try:
        runner.close()
    except Exception:  # noqa: BLE001 — the measurement is whole by now
        traceback.print_exc()
        say("closing the runner raised; the result stands")

    if rehearsing:
        print(json.dumps({"rehearsal": True, "platform": dev["platform"],
                          "correct": correct,
                          "attempted": window["attempted"],
                          "failed": window["failed"],
                          "end_to_end_names": sorted(e2e),
                          "per_layer_names": sorted(per_layer)}), flush=True)
        return 0 if correct else 1

    if args.trace:
        metrics = per_layer
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]][0], "unit": m["unit"]}
                   for m in wanted_e2e if m["name"] in e2e}
    result = {"correct": correct, "attempted": int(window["attempted"]),
              "failed": int(window["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

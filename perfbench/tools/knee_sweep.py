"""Find the knee of an open-loop cell once, on the chip, and see how its
latencies spread between seeds: many windows in ONE process (one set-up, the
cell's own runner and traffic file; what is in flight at a window's close is
cancelled, so a window costs its ramp and its length).

    python perfbench/tools/knee_sweep.py --workload opt1b3_serve_mixed \\
        --seconds 40 --rates 1.4 1.6 1.8 2.0 2.2 --seeds 7 8 \\
        --repeat 40:11,12,13,14,15,16 --repeat 51:21,22,23 --out chiprun_out/knee

The sweep runs each rate under each seed. A window SUSTAINS its rate when
both hold (the rule of the choosing-metrics guide: the limit is met and no
backlog grows):

* the requests waiting for their first token, averaged over the last quarter
  of the window, are at most one more than over its second quarter
  (``waiting_end <= waiting_mid + 1``: the queue, not the batch, which still
  fills long after the ramp);
* the window's ``ttft_p95_ms`` is under ``TTFT_LIMIT_MS`` (1,000: the promise
  to the chat users the mix stands for).

The knee is the highest rate that every seed sustained and below which every
rate was sustained too; the sweep stops at the first rate no seed sustained.
``--repeat SECONDS:SEED,SEED,...`` then runs the cell at ``FRACTION`` (four
fifths) of the knee, rounded to 0.05, once per seed, for the spread (with no
sweep: at the traffic file's rate). Every window prints one JSON line; with
``--out`` its raw client series (times to first token, gaps, lateness) are
written there too, so that any statistic can be taken from them afterwards.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, ROOT)

T0 = time.perf_counter()
TTFT_LIMIT_MS = 1000.0
FRACTION = 0.8


def say(msg):
    print(f"[knee_sweep +{time.perf_counter() - T0:7.2f}s] {msg}", flush=True)


def sustained(row):
    return bool(row["failed"] == 0
                and row["waiting_end"] <= row["waiting_mid"] + 1.0
                and row["ttft_p95_ms"] <= TTFT_LIMIT_MS)


def knee_of(rows):
    """Highest rate every seed sustained, below which every rate was too."""
    knee = None
    for rate in sorted({r["rate_per_s"] for r in rows}):
        if not all(sustained(r) for r in rows if r["rate_per_s"] == rate):
            break
        knee = rate
    return knee


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--seeds", type=int, nargs="*", default=[7])
    ap.add_argument("--repeat", action="append", default=[],
                    metavar="SECONDS:SEED,SEED,...")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", nargs="?", const="rehearsal-tiny",
                    default=None, metavar="CONFIG")
    args = ap.parse_args()

    import run as harness
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    cell, conf = harness.find_cell(manifest, args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = harness.load_json(BENCH, "configs", args.rehearse + ".json")
    else:
        config = harness.load_json(ROOT, conf["file"])

    import numpy as np

    from deepspeed_tpu.accelerator import require_tpu
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    if not args.rehearse:
        require_tpu()
        enable_compile_cache()
    import traffic as traffic_mod
    from readers import counter_delta, counter_ratio, histogram_quantile
    from runners.serve import ServeRunner
    from tracing import MidWindowTrace

    spec = traffic_mod.load(cell["traffic"])
    if spec.get("loop") != "open":
        sys.exit("knee_sweep: the cell's traffic is not an open loop")
    runner = ServeRunner(cell, config, spec, args.seeds[0], say)
    runner.setup()
    vocab = runner.model.config.vocab_size
    scale = config.get("length_scale", 1.0)
    if args.out:
        os.makedirs(args.out, exist_ok=True)

    def one(rate, seed, seconds, tag):
        # a sweep is over the seeds' own sample paths: a cell's fixed plan
        # (``plan_seed``) is set aside here
        trial = {**spec, "arrivals": {"dist": spec["arrivals"]["dist"],
                                      "rate_per_s": rate}}
        runner.spec, runner.seed = trial, seed
        runner.traffic = traffic_mod.ServeTraffic(trial, vocab, seed, scale)
        w = runner.window(seconds, MidWindowTrace(False, None))
        facts = {"window": w}
        c = w["client"]
        pct = lambda s, q: float(np.percentile(c[s], q)) if c[s] else None
        row = {"tag": tag, "rate_per_s": rate, "seed": seed,
               "seconds": seconds, "attempted": w["attempted"],
               "failed": w["failed"],
               "waiting_mid": w["waiting_mid"], "waiting_end": w["waiting_end"],
               "out_tokens_per_s": w["tokens_in_window"] / seconds,
               **{f"ttft_p{q}_ms": pct("ttft_ms", q) for q in (50, 80, 90, 95)},
               **{f"itl_p{q}_ms": pct("itl_ms", q) for q in (50, 90, 95, 99)},
               "gaps": len(c["itl_ms"]),
               "queue_wait_p95_ms": histogram_quantile.read(
                   {"histogram": "serving/queue_wait_ms", "q": 0.95}, facts),
               "preemptions": counter_delta.read(
                   {"counter": "serving/preemptions"}, facts),
               "batch_occupancy": counter_ratio.read(
                   {"num": {"serving/generated_tokens": 1,
                            "serving/prefill_steps": -1},
                    "den": {"serving/decode_steps": 1}, "den_times": "rows",
                    "percent": True}, facts),
               "pool_peak_used_share": w["pool_peak_used_share"],
               "generator_late_p99_ms": pct("late_ms", 99)}
        print(json.dumps(row), flush=True)
        if args.out:
            name = f"{tag}_r{rate:g}_s{seed}_w{seconds:g}.json"
            with open(os.path.join(args.out, name), "w") as f:
                json.dump({**row, "client": {k: [round(float(x), 3) for x in v]
                                             for k, v in c.items()}}, f)
        return row

    rows = []
    for rate in sorted(args.rates):
        at_rate = [one(rate, seed, args.seconds, "sweep")
                   for seed in args.seeds]
        rows += at_rate
        if not any(sustained(r) for r in at_rate):
            break                        # far over the knee: no need to go on
    rate = float(spec["arrivals"]["rate_per_s"])
    if rows:
        knee = knee_of(rows)
        print(json.dumps({"verdicts": [
            [r["rate_per_s"], r["seed"], sustained(r)] for r in rows],
            "knee_per_s": knee}), flush=True)
        if knee is None:
            say("no rate of the sweep was sustained; the repeats run at "
                f"{rate}/s")
        else:
            rate = round(round(FRACTION * knee / 0.05) * 0.05, 2)
        print(json.dumps({"rate_per_s": rate, "fraction": FRACTION}),
              flush=True)
    for rep in args.repeat:
        seconds, seeds = rep.split(":")
        for seed in seeds.split(","):
            one(rate, int(seed), float(seconds), "repeat")
    runner.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

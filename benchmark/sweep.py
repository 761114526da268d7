"""Find a serving cell's knee, once, when the cell is defined.

    python benchmark/sweep.py --workload chat-steady --seconds 20 \\
        --values 4,6,8,10,12

One process brings the cell up as `run.py` does (same replica, same
warm-up), then offers the cell's traffic at each value in turn — requests a
second for an open loop, clients for a closed one — and prints one line per
value. The knee is the highest value at which the backlog does not grow and
the tails stay flat; the cell's file then gets four fifths of it, as a
number. Not part of a check: the driver never runs this."""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import metrics, run, serve_driver
    from benchmark.manifest import Manifest

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--values", required=True)
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args()
    manifest = Manifest()
    ctx = run.context(manifest, manifest.cell(args.workload), args.seed,
                      args.seconds, trace=False)
    traffic = ctx["traffic"]
    run.use_checkout_cache(manifest)
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init()
    try:
        up = serve_driver.bring_up(ctx)
        for i, value in enumerate(float(v) for v in args.values.split(",")):
            t = copy.deepcopy(traffic)
            if "clients" in t:
                t["clients"] = int(value)
            else:
                t["arrivals"]["rate_per_s"] = value
            m = serve_driver.measure(dict(ctx, seed=args.seed + i), up, t,
                                     args.seconds)
            recs = m["recs"]
            ttft = metrics.ttft_ms(recs, args.seconds)
            tpot = metrics.tpot_ms(recs, until_s=args.seconds, min_tokens=8)
            done_late = max((r.done_s or 0.0) for r in recs) - args.seconds
            run.say(json.dumps({
                "value": value, "requests": len(recs),
                "failed": sum(1 for r in recs if not r.ok),
                "out_tok_per_s": metrics.out_tok_per_s(recs, args.seconds),
                "ttft_p50_ms": metrics.percentile(ttft, 50),
                "ttft_p95_ms": metrics.percentile(ttft, 95),
                "tpot_p50_ms": metrics.percentile(tpot, 50),
                "tpot_p95_ms": metrics.percentile(tpot, 95),
                "ttft_last_quarter_p50_ms": metrics.percentile(
                    ttft[-max(1, len(ttft) // 4):], 50),
                "drained_s_after_window": done_late,
                "compiles": sum(e["compiles_in_window"]
                                for e in m["ends"])}))
        serve.shutdown()
        from benchmark import client

        client.wait_gone(up["pids"])
    finally:
        ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())

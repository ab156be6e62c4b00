"""Find the knee of an open-loop cell once, on the chip.

    python bench/sweep.py --workload qwen4b-chat --seed 1 --seconds 51 \\
        --rates 1.0,1.5,2.0,2.5,3.0

One process builds the cell's system once and offers its mix at each
rate in turn for ``--seconds``, clearing the engine between rates.  For
each rate it prints one JSON line: requests due and finished in the
window, the queue at the window's middle and end, and the TTFT
quantiles of the requests that got a token.  The knee is the highest
rate whose queue does not grow over the window (end no longer than the
middle plus half the decode slots); the cell runs at about four fifths
of it, written into the mix file by hand.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import harness
    import run as bench_run
    import stats
    import traffic
    import jax

    cell = harness.resolve(args.workload, ROOT)
    if cell.mix["kind"] != "open_loop":
        sys.exit(f"{args.workload} is not an open-loop cell")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"no TPU: JAX sees {devices[0].platform} devices")
    bench_run.enable_cache(jax)
    c = cell.config
    sys_ = harness.build(cell.family, c, args.seed, devices)
    harness.warm_up(sys_, cell.mix, c["vocab_size"])
    eng = sys_.engine
    points = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = copy.deepcopy(cell.mix)
        mix["rate_per_s"], mix["drain_cap_s"] = rate, 0.0
        reqs = traffic.generate(mix, seconds=args.seconds, seed=args.seed,
                                vocab_size=c["vocab_size"],
                                slots=c["max_batch"])
        out = harness.drive(sys_, mix, reqs, args.seconds, None)
        t0, t1, ticks = out["t0"], out["t1"], out["ticks"]
        mid = [k[4] for k in ticks if k[0] <= (t0 + t1) / 2]
        recs = [r for r in out["records"] if r.measured]
        first = [r.token_times[0] - r.due for r in recs if r.token_times]
        point = {
            "rate_per_s": rate, "due": len(recs),
            "finished": sum(r.status == "finished" for r in recs),
            "queue_mid": mid[-1] if mid else 0,
            "queue_end": ticks[-1][4] if ticks else 0,
            "ttft_p50_ms": (stats.pct(first, 50) or 0) * 1e3,
            "ttft_p90_ms": (stats.pct(first, 90) or 0) * 1e3,
            "gen_lag_p99_ms": (stats.pct([r.submit_start - r.due
                                          for r in recs if r.rid], 99)
                               or 0) * 1e3}
        point["steady"] = (point["queue_end"]
                           <= point["queue_mid"] + c["max_batch"] / 2)
        points.append(point)
        print(json.dumps(point), flush=True)
        eng.scheduler.evacuate()
        eng.scheduler.reset_cache()
        time.sleep(1.0)
    steady = [p["rate_per_s"] for p in points if p["steady"]]
    print(json.dumps({"knee_rate_per_s": max(steady) if steady else None,
                      "points": len(points)}))


if __name__ == "__main__":
    main()

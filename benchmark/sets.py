"""Runs one cell several times, each run a process of its own, and prints
the spread of every metric as the contract defines it: the distance between
the first and third quartile (statistics.quantiles, n=4) over the median.
The parent never touches JAX, so each child gets the chip.

    python -m benchmark.sets --workload <cell> --seconds 30 --seeds 11,12,13 \\
        [--sets 2] [--trace-seeds 21,22] [--out chiprun_out/<file>.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time


def spread(values: list) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def run_once(workload: str, seed: int, seconds: float, trace: int,
             extra: list) -> dict:
    cmd = [sys.executable, "-m", "benchmark.run", "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace), *extra]
    t0 = time.time()
    p = subprocess.run(cmd, capture_output=True, text=True)
    took = time.time() - t0
    lines = p.stdout.strip().splitlines()
    try:
        res = json.loads(lines[-1])
    except (IndexError, ValueError):
        res = {"correct": False, "metrics": {}, "error": p.stderr[-3000:]}
    res.update(rc=p.returncode, seed=seed, trace=trace, took_s=took,
               stderr=[ln for ln in p.stderr.splitlines()
                       if ln.startswith("# ")])
    return res


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--verbose", action="store_true")
    args, extra = ap.parse_known_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    out = open(args.out, "a") if args.out else None
    ok = True
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            r = run_once(args.workload, seed, args.seconds, 0, extra)
            rows.append(r)
            ok = ok and r["rc"] == 0 and r["correct"]
            keep = r["stderr"] if args.verbose or not r["correct"] else [
                ln for ln in r["stderr"]
                if ln.startswith(("# window", "# slowest", "# setup build",
                                  "# check reference"))]
            print(f"set {k} seed {seed} rc={r['rc']} correct={r['correct']} "
                  f"failed={r.get('failed')} took={r['took_s']:.0f}s " + " ".join(
                      f"{n}={m['value']:.6g}" for n, m in r["metrics"].items()))
            for ln in keep:
                print("   ", ln)
            if r.get("error"):
                print(r["error"])
            if out:
                out.write(json.dumps({"set": k, **r}) + "\n")
                out.flush()
        names = rows[0]["metrics"].keys() if rows else []
        for n in names:
            vals = [r["metrics"][n]["value"] for r in rows if n in r["metrics"]]
            # setup_s: the first run of a cold checkout compiles; leave it out
            body = vals[1:] if n == "setup_s" and k == 0 and len(vals) > 2 else vals
            print(f"set {k} {n}: median={statistics.median(body):.6g} "
                  f"spread={spread(body):.4f} n={len(body)}")
    for seed in [int(s) for s in args.trace_seeds.split(",") if s]:
        r = run_once(args.workload, seed, args.seconds, 1, extra)
        ok = ok and r["rc"] == 0 and r["correct"]
        print(f"trace seed {seed} rc={r['rc']} correct={r['correct']} "
              f"took={r['took_s']:.0f}s")
        print("   ", json.dumps({k: r.get(k) for k in
                                 ("metrics", "device", "breakdown")}))
        for ln in r["stderr"] if args.verbose or not r["correct"] else []:
            print("   ", ln)
        if r.get("error"):
            print(r["error"])
        if out:
            out.write(json.dumps({"set": "trace", **r}) + "\n")
            out.flush()
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())

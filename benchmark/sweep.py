"""Find the knee of an open-loop cell: one process, the cell's own set-up,
then a window of ``--seconds`` at each rate in turn, each on documents
no earlier window asked for.

    python3 benchmark/sweep.py --workload string-10k.open-cold \
        --seed 11 --seconds 30 --rates 10,15,20,25,30,35,40

For each rate it prints one JSON line: the latency percentiles, the
sheds, the lanes, how late the generator ran, whether the backlog grew
(requests outstanding, averaged over the last third of the arrivals,
against the first third), and the documents the reference found wrong.  The knee is the highest rate whose backlog does
not grow and whose p95 stays flat; the cell runs at four fifths of it.
Refuses a non-TPU device, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def backlog_growth(reqs) -> tuple:
    """Mean requests outstanding at each due time, over the first and
    the last third of the arrivals."""
    dues = [r.due for r in reqs]
    outstanding = [sum(1 for r in reqs if r.due <= t < r.done)
                   for t in dues]
    third = max(1, len(outstanding) // 3)
    first = sum(outstanding[:third]) / third
    last = sum(outstanding[-third:]) / third
    return first, last


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--rates", required=True)
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax

    from benchmark import harness
    from benchmark.stats import nearest_rank

    harness.setup_jax_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"sweep: JAX found platform {devices[0].platform!r}, not a "
              f"TPU; refusing to measure", file=sys.stderr)
        return 1
    found = harness.find_cell(harness.load_spec(), args.workload)
    cfg, traffic = found["config"], found["traffic"]
    harness._device_line(devices)
    check = __import__(f"benchmark.reference.{cfg['family']}_ref",
                       fromlist=["check"]).check
    with harness.CompileMeter() as meter, \
            harness.served(cfg, traffic, args.seed, "tpu", meter) as s:
        index = {d: i for i, d in enumerate(s.doc_ids)}
        for rate in [float(r) for r in args.rates.split(",")]:
            win = harness.measure(s, dict(traffic, rate_per_s=rate),
                                  args.seconds, False)
            reqs = win["reqs"]
            lat = [r.latency for r in reqs]
            first, last = backlog_growth(reqs)
            verdict = harness.check_window(
                reqs, lambda d: s.gen(args.seed, index[d], cfg["tail_ops"]),
                s.service, cfg, "tpu", check)
            print(json.dumps({
                "rate_per_s": rate, "requests": len(reqs),
                "window_s": win["window_s"],
                "p50_ms": 1000 * nearest_rank(lat, 50),
                "p95_ms": 1000 * nearest_rank(lat, 95),
                "p99_ms": 1000 * nearest_rank(lat, 99),
                "max_ms": 1000 * max(lat),
                "outstanding_first_third": first,
                "outstanding_last_third": last,
                "lateness_max_s": max(r.sent - r.due for r in reqs),
                "sheds": win["server"].get("catchup.shed", 0),
                "lanes": harness._lanes(reqs),
                "failed_docs": verdict["failed_docs"],
                "compiles": win["compiles"][0],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one benchmark cell once on the chips of this machine.

    python3 benchmark/run.py --workload string-10k.bulk --seed 7 \
        --seconds 30 --trace 0

Refuses to run (exit 1, no result) where JAX finds no TPU or fewer chips
than the cell asks for.  The last line of standard output is the JSON
result; see ``harness.py`` for what a run does.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import harness

    cell = harness.find_cell(harness.load_spec(), args.workload)["cell"]
    import jax

    harness.setup_jax_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: JAX found platform {devices[0].platform!r} "
              f"({len(devices)} device(s)), not a TPU; refusing to measure",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"benchmark: {args.workload} needs {cell['chips']} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), platform="tpu",
                              t_start=T_START)
    return 0 if result is not None else 1


if __name__ == "__main__":
    sys.exit(main())

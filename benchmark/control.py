"""Run one cell on the chip with a fault planted under the timed path.

    python3 benchmark/control.py --workload string-10k.bulk --seed 7 \
        --seconds 10 --fault stale_tail

``stale_tail`` is the control: the reference guarantee broken (each
answer misses the last op of its tail).  The other faults are in
``faults.py``.  A sound benchmark prints ``"correct": false`` for every
one of them; the compared numbers close standard error as in a real run.
The benchmark's own runs never plant a fault.
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
    parser.add_argument("--fault", required=True)
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax

    from benchmark import faults, harness

    if args.fault not in faults.FAULTS:
        parser.error(f"--fault: one of {sorted(faults.FAULTS)}")
    harness.setup_jax_cache()
    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU; refusing to run",
              file=sys.stderr)
        return 1
    harness.run_cell(args.workload, args.seed, args.seconds, False,
                     platform="tpu", t_start=T_START, fault=args.fault)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of the LASANA on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload snn-mnist.batch --seed 7 \\
        --seconds 10 --trace 0

Run from the root of a checkout, on a machine with the TPU chips the cell
asks for. Prints progress and every compared number beside its limit on
standard error, and one JSON line last on standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics and a ``breakdown``),
``device`` and ``checks``. Exits non-zero, printing no result, when
JAX's first device is not a TPU or there are fewer chips than the cell
needs.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    sys.path.insert(0, HERE)
    # the TPU runtime's logs stay inside the checkout, as everything else
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(HERE, ".cache", "tpu"))
    from lasbench import harness
    root = os.path.dirname(os.path.dirname(HERE))
    return harness.execute(root, args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())

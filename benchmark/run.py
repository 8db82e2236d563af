"""The benchmark's entry point:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the client and never imports JAX. It starts the server as a child
(the one process on the chip), builds the cell's index from the seed over REST, warms
up, measures one window, compares a sample of the window's own responses with the
numpy reference, and prints one JSON object per phase; the last line is the result.
See benchmark/README.md.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--docs", type=int, default=None,
                    help="CPU rehearsal only: a smaller index")
    args = ap.parse_args(argv)
    # a caller's SIGTERM (a time limit) still stops the child: exit through `finally`
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    return cell.run(args, T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())

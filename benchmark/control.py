"""The control of `correct`: the reference, computed in the nearest precision below the
one the configuration states (bfloat16 for float32), put in the program's place.

    python benchmark/control.py --workload <cell> --seeds 11 12 13 [--docs N]

For each seed it builds the cell's corpus and pool at the cell's own size, lets the
lower-precision reference answer the run's sample of searches (its own top-k, ids and
scores; where the query family states an `answer` of its own, that), and compares
those answers with the float32 reference exactly as a run compares the program's. The
control has to fail a limit; PERF.md sets the smallest `rel_dev` it reads beside the
largest that sound runs of the program give. numpy only: it needs no chip, and runs
there to read the cell's own size.
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import registry  # noqa: E402
from benchmark.harness.cell import Compared, Pool, say, search_path  # noqa: E402
from benchmark.harness.reference import Reference  # noqa: E402


def read(workload: str, seed: int, docs: int | None, precision: str) -> dict:
    bench = registry.benchmark()
    cell = registry.cell(bench, workload)
    config = registry.config(bench, cell["config"])
    mix = registry.mix(cell["traffic"])
    settings = registry.settings()
    gen = registry.module("corpora", config["corpus"]["generator"])
    corpus = gen.generate(config["corpus"]["params"], seed, docs or config["documents"])
    sim = config["similarity"]
    ref = Reference(corpus, sim["k1"], sim["b"])
    low = Reference(corpus, sim["k1"], sim["b"], precision=precision)
    limits = dict(settings["limits"], rel_dev=config["guarantees"]["score_rel_tol"])
    pool = Pool(mix, ref, search_path(settings["index"], config), limits)
    picks = np.random.default_rng(seed).choice(
        len(pool.queries), settings["sample"], replace=False)
    got = Compared(pool.limits)
    devs = []
    for i in picks:
        # what the lower precision would serve: its own ranking and scores
        numbers = pool.compare(ref, int(i), pool.answer(low, int(i)), limits["rel_dev"])
        devs.append(numbers["rel_dev"])
        got.add(numbers)
    return {**got.line(f"control: reference in {precision}"), "workload": workload,
            "seed": seed, "documents": corpus.n_docs, "passed": got.passed,
            "rel_dev_median_over_searches": float(np.median(devs)),
            "searches_past_the_limit": int((np.array(devs) > limits["rel_dev"]).sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--docs", type=int, default=None)
    ap.add_argument("--precision", default="bfloat16")
    args = ap.parse_args(argv)
    held = False
    for seed in args.seeds:
        line = read(args.workload, seed, args.docs, args.precision)
        say(line)
        held = held or line["passed"]
    return 1 if held else 0  # a control that passes means the limits catch nothing


if __name__ == "__main__":
    sys.exit(main())

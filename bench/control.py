#!/usr/bin/env python3
"""The control of a cell's correctness check, on the cell's own input.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed, builds the cell's input on the device as a run does, puts
the reference computed one precision step down (bfloat16 for the float32
the configuration states) in the program's place, and judges it with the
function that decides a run's ``correct`` (``harness.judge``).  Prints one
JSON line per seed: ``correct`` and every number compared, beside its
limit.  The control has to come out not correct on every seed; its
readings are the upper end each limit is set below.  Not part of a
benchmark run.
"""

import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_result(cell, seed: int, precision: str = "bfloat16") -> dict:
    import numpy as np

    from bench import harness, reference
    from bench.session import Session

    s = Session(cell.config, seed, cell.chips, tempfile.gettempdir(),
                cell.layout)
    s.make_inputs()
    answer = {k: reference.control_answer(
        np.asarray(v), k in s.compressed, s.eb, precision)
        for k, v in s.inputs.items()}
    rec = {"prints": [harness._fingerprint_fn()(answer)],
           "sample": (0, answer), "missing": 0, "stale": 0, "failed": 0,
           "n_answers": 1}
    correct, checks = harness.judge(s, rec, cell.config["checks"])
    return {"seed": seed, "precision": precision, "correct": correct,
            "checks": {k: {"value": float(v), "limit": float(lim)}
                       for k, (v, lim) in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [REPO, os.path.join(REPO, "src")]
    from bench import harness

    cell = harness.load_cell(harness.Layout(), args.workload)
    for seed in args.seeds:
        print(json.dumps(control_result(cell, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

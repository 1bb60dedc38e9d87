"""Benchmark harness entry point -- one table per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Timings are host wall-clock on
JAX's default backend; most suites time the jit'd jnp ``ref`` pipelines.
The ``tpu_*`` columns of some suites come from the analytic model in
``benchmarks/tpu_model.py``, not from a chip.  A suite that raises prints
an ``<suite>/ERROR`` row, the remaining suites still run, and the harness
exits 1.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--only tableV,...]
                                               [--record BENCH_tag.json]
                                               [--compare BENCH_old.json]

``--record`` writes the rows to a JSON file so runs can be kept as a
trajectory (convention: ``BENCH_<tag>.json``, e.g. one per PR);
``--compare`` reloads such a file and appends a ``vs_baseline`` speedup
column for every row name present in both runs.
"""

from __future__ import annotations

import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="subset of datasets / sizes (CI mode)")
    ap.add_argument("--only", default=None,
                    help="comma list: tableI,tableII,tableIV,tableV,"
                         "fig2,fig4,batch,store,fused,serving,sharded,"
                         "arch")
    ap.add_argument("--record", default=None, metavar="BENCH_tag.json",
                    help="write rows to a JSON trajectory file")
    ap.add_argument("--compare", default=None, metavar="BENCH_old.json",
                    help="append vs_baseline speedups from a recorded run")
    ap.add_argument("--gate", type=float, default=None, metavar="FACTOR",
                    help="with --compare: exit 1 if any row is slower than "
                         "FACTOR x its baseline (CI perf gate; pick FACTOR "
                         "well above timer noise, e.g. 2.5)")
    args = ap.parse_args()
    if args.gate is not None and not args.compare:
        ap.error("--gate requires --compare")
    only = set(args.only.split(",")) if args.only else None

    baseline = {}
    if args.compare:
        with open(args.compare) as f:
            baseline = {r[0]: float(r[1]) for r in json.load(f)["rows"]}

    from benchmarks import (arch_step, batch_decode, compression_ratio,
                            cr_sensitivity, decode_throughput,
                            decoder_phases, e2e_decompression,
                            encode_throughput, fused_decode, serving_load,
                            sharded_restore, shmem_tuning, store_throughput)

    suites = [
        ("tableV", decode_throughput.run),
        ("tableII", decoder_phases.run),
        ("tableIV", compression_ratio.run),
        ("tableI", shmem_tuning.run),
        ("fig2", cr_sensitivity.run),
        ("fig4", e2e_decompression.run),
        ("batch", batch_decode.run),
        ("store", store_throughput.run),
        ("fused", fused_decode.run),
        ("encode", encode_throughput.run),
        ("serving", serving_load.run),
        ("sharded", sharded_restore.run),
        ("arch", arch_step.run),
    ]
    all_rows = []
    regressions = []
    errors = []
    print("name,us_per_call,derived")
    for key, fn in suites:
        if only and key not in only:
            continue
        try:
            rows = fn(quick=args.quick)
        except Exception as e:  # report, run the other suites, exit non-zero
            print(f"{key}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            errors.append(key)
            continue
        for name, us, derived in rows:
            # Record the un-annotated row: a trajectory file must not bake
            # in speedups relative to whatever --compare happened to load.
            all_rows.append([name, us, derived])
            if name in baseline and us > 0:
                derived = f"{derived};vs_baseline={baseline[name] / us:.2f}"
                if args.gate is not None and us > args.gate * baseline[name]:
                    regressions.append((name, us, baseline[name]))
            print(f"{name},{us:.1f},{derived}", flush=True)

    if args.record:
        with open(args.record, "w") as f:
            json.dump({"argv": sys.argv[1:], "rows": all_rows}, f, indent=1)

    if regressions:
        print(f"PERF GATE FAILED ({len(regressions)} rows > "
              f"{args.gate:g}x baseline):", file=sys.stderr)
        for name, us, base_us in regressions:
            print(f"  {name}: {us:.1f}us vs baseline {base_us:.1f}us",
                  file=sys.stderr)
        sys.exit(1)
    if errors:
        print(f"SUITES FAILED: {', '.join(errors)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

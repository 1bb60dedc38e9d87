#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic and
metrics are files under ``bench/`` found by name (``bench/harness.py``).
Set-up (input built on the device from the seed, the traffic's set-up, one
warm-up pass over the window's operations) is timed as ``setup_s``; then
the window runs for ``--seconds``.  With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
taken from a profiler trace of the window.

The last line of standard output is one JSON object; the numbers compared
to decide ``correct`` are printed beside their limits as the last lines of
standard error and, last, under ``checks`` in that object.  Without a TPU,
or with fewer chips than the cell asks for, the run exits 2 and prints no
result.  JAX's persistent compilation cache is kept in ``.jax_cache`` at
the root of the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cache = os.path.join(REPO, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    # The TPU runtime logs under /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path[:0] = [REPO, os.path.join(REPO, "src")]

    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    # Every program, however quick to compile, and no eviction: what one
    # run compiles, the next run in this checkout finds.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)

    from bench import harness

    n = len(jax.devices())
    harness.log(f"imports and device start {time.perf_counter() - T_START:.2f}"
                f" s, {n} devices")
    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())

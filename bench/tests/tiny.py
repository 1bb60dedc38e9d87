"""Tiny cells for CPU rehearsals, written into a temporary directory.

``write_cells(tmp)`` adds two configurations, two traffic mixes and a
``BENCHMARK.json`` naming two cells, as files only; the harness finds
them under ``tmp`` before ``bench/``.  Pallas kernels run in interpret
mode on the CPU, so the shapes are small.
"""

from __future__ import annotations

import json
import os

from bench import harness

CODEC = {"eb": 1e-3, "mode": "rel", "backend": "pallas",
         "encode_backend": "pallas", "method": "gap", "strategy": "tile",
         "fused": True}
CHECKS = {"err_over_eb": 1.0, "lattice_mismatch": 0.01}

FIELD = {"name": "tiny-field", "source": "test", "reduced": [],
         "input": {"kind": "field", "shape": [16, 16, 16], "dtype": "float32",
                   "generator": "integrated_noise", "base_seed": 3},
         "codec": CODEC, "checks": CHECKS}

TREE = {"name": "tiny-tree", "source": "test", "reduced": [],
        "input": {"kind": "tree", "dtype": "float32", "base_seed": 11,
                  "fsdp": 4, "compress_min_size": 1024,
                  "leaves": [
                      {"name": "embed", "shape": [256, 32], "shard_axis": 0,
                       "init": "normal", "scale": 0.02},
                      {"name": "norm", "shape": [32], "init": "ones"},
                      {"name": "layers.w", "shape": [4, 64, 4, 16],
                       "shard_axis": 1, "init": "normal", "scale": 0.18}]},
        "codec": CODEC, "checks": CHECKS}

TRAFFIC = {
    "tiny-read": {"setup": [{"op": "archive_write"}],
                  "window": [{"op": "archive_read"}]},
    "tiny-save-restore": {"window": [{"op": "ckpt_save"},
                                     {"op": "ckpt_restore"}]},
}

#: cell -> (configuration, traffic, chips)
CELLS = {"tiny-field.read": ("tiny-field", "tiny-read", 1),
         "tiny-tree.save-restore": ("tiny-tree", "tiny-save-restore", 1)}


def write_cells(tmp: str) -> harness.Layout:
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(tmp, sub), exist_ok=True)
    for cfg in (FIELD, TREE):
        with open(os.path.join(tmp, "configs", cfg["name"] + ".json"),
                  "w") as f:
            json.dump(cfg, f)
    for name, mix in TRAFFIC.items():
        with open(os.path.join(tmp, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(harness.REPO_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [{"name": n, "config": c, "traffic": t,
                           "chips": chips, "why": "test"}
                          for n, (c, t, chips) in CELLS.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return harness.Layout(path, roots=[tmp, harness.BENCH_DIR])


def run(layout: harness.Layout, cell: str, seed: int = 7,
        seconds: float = 0.5) -> dict:
    import time

    c = harness.load_cell(layout, cell)
    return harness.run_cell(c, seed, seconds, False, time.perf_counter(),
                            require_tpu=False)

"""Sharded restore throughput vs device count (docs/distributed.md).

A fixed mesh-sharded checkpoint (one shard per device) is written once;
restore is then timed at 1/2/4/8 "hosts".  On a TPU the suite runs in the
harness's own process on ``jax.devices()`` (a chip belongs to one process,
so a child could not reach it).  On the CPU it runs in a forced-8-device
subprocess (``launch.mesh.forced_host_devices_env``, single-threaded
devices so scaling reflects device count, not the intra-op thread pool).

What is timed is the per-host critical path
(``ShardedRestorer.decode_shards``): with H hosts each decodes its own
shard archives concurrently and places the tiles on its devices, so the
restore wall-clock is one host's share and the *aggregate* decode
throughput scales with H.  Shares are equal-sized (equal tile grids), so
host 0's share is the critical path.  A full ``restore()`` into target
``NamedSharding``s on the whole mesh is also timed (``full_mesh`` row)
and its sharding landing asserted.

Run via ``benchmarks.run --only sharded`` (suite key ``"sharded"``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

N_DEVICES = 8
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(n: int = 1 << 19, quick: bool = False):
    import jax
    if jax.default_backend() == "tpu":
        return _worker(n, quick, jax.devices())
    from repro.launch.mesh import forced_host_devices_env
    env = forced_host_devices_env(N_DEVICES, single_threaded=True,
                                  base_env=os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_ROOT, "src"),
                    env.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, "-m", "benchmarks.sharded_restore", "--worker",
           "--n", str(n)]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(cmd, cwd=_ROOT, env=env, capture_output=True,
                          text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded-restore worker failed:\n{proc.stderr}")
    # The worker prints one JSON document on its last stdout line.
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    return [(name, us, derived) for name, us, derived in rows]


# ---------------------------------------------------------------------------
# worker (in-process on a TPU, under forced host devices on the CPU)
# ---------------------------------------------------------------------------


def _timeit(fn, repeats: int = 3) -> float:
    import time
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def _worker(n: int, quick: bool, devs: list) -> list:
    import tempfile

    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks import datasets as DS
    from repro.core import Codec, CodecConfig
    from repro.distributed import ShardedRestorer, ShardedWriter
    from repro.launch.mesh import make_host_mesh

    n_dev = len(devs)
    names = ["HACC", "CESM"] if quick else ["HACC", "CESM", "Nyx", "EXAALT"]
    codec = Codec(CodecConfig(eb=1e-3, mode="rel"))
    rows = []
    with tempfile.TemporaryDirectory() as d:
        ckpt = os.path.join(d, "sharded")
        total_bytes = 0
        with ShardedWriter(ckpt, {"data": n_dev}, codec=codec,
                           n_shards=n_dev) as sw:
            for name in names:
                x, _ = DS.make_dataset(name, n)
                x = x.reshape(n_dev * 64, -1)        # tile rows evenly
                total_bytes += x.nbytes
                sw.add(f"params.{name}", x, P("data"))
        restorer = ShardedRestorer(ckpt, codec=codec)

        # Full restore into target shardings on the whole mesh; warms the
        # plan cache for every timed run below.
        mesh = make_host_mesh(n_dev, 1, devices=devs)
        targets = {e: NamedSharding(mesh, P("data"))
                   for e in restorer.names}
        out = restorer.restore(targets)
        for e, arr in out.items():
            assert len(arr.addressable_shards) == n_dev, e
        t_full = _timeit(lambda: restorer.restore(targets))
        rows.append(["sharded/restore/full_mesh", t_full * 1e6,
                     f"GBps={total_bytes / t_full / 1e9:.3f};"
                     f"shards={n_dev};entries={len(names)}"])

        # Per-host critical path at 1/2/4/8 hosts: host 0 decodes its
        # n_dev/H-shard share onto its devices; aggregate = total bytes over
        # that wall-clock (all hosts run concurrently, shares are equal).
        for hosts in (h for h in (1, 2, 4, 8) if n_dev % h == 0):
            share = n_dev // hosts
            local = devs[:share]
            t = _timeit(lambda: restorer.decode_shards(range(share),
                                                       devices=local))
            rows.append([f"sharded/restore/d{hosts}", t * 1e6,
                         f"GBps={total_bytes / t / 1e9:.3f};hosts={hosts};"
                         f"shards_per_host={share}"])
        stats = dict(restorer.stats)
    rows.append(["sharded/restore/stats", 0.0,
                 f"tiles_decoded={stats['tiles_decoded']};"
                 f"shards_opened={stats['shards_opened']}"])
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--n", type=int, default=1 << 19)
    ap.add_argument("--quick", action="store_true")
    a = ap.parse_args()
    if a.worker:
        import jax
        print(json.dumps(_worker(a.n, a.quick, jax.devices())))
    else:
        for r in run(a.n, a.quick):
            print(r)

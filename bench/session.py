"""One run's state, and the plug-ins that make its input and operations.

A configuration's ``input.kind`` names an input maker,
``<root>/inputs/<kind>.py``, with

    shapes(spec, chips) -> {name: shape}    the leaves this process holds
    make(spec, seed, chips) -> {name: array}  them, on the device

A traffic file (``<root>/traffic/<mix>.json``) lists operations by name,
with their parameters, under ``setup`` and ``window``:

    {"setup": [{"op": "archive_write"}], "window": [{"op": "archive_read"}]}

and each names an operation, ``<root>/ops/<op>.py``, with

    KIND = "compress" | "decompress"   the span it is timed under
    READS_INPUT = True | False         whether it reads the input arrays
    run(session, op) -> Outcome

Set-up runs its list once.  Then the window's list runs once more as the
warm-up, so that every program the window uses is compiled, and then in a
loop for the run's seconds.  A later cell adds an input kind or an
operation as a file of its own; the harness finds it by name.
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from bench.reference import compressed_leaf
from bench.work import OpWork


def seed_key(seed: int):
    """A PRNG key from a whole number of up to 63 bits: its low and high
    32 bits folded together."""
    import jax

    if not 0 <= seed < 2**63:
        raise ValueError(f"seed {seed} is outside [0, 2**63)")
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def nest(flat: dict) -> dict:
    """``{"a.b": x}`` -> ``{"a": {"b": x}}``, the shape of a model's tree."""
    out: dict = {}
    for name, v in flat.items():
        *path, last = name.split(".")
        node = out
        for p in path:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def flatten(tree, prefix: str = "") -> dict:
    """Inverse of :func:`nest`."""
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(flatten(v, f"{prefix}.{k}" if prefix else str(k)))
        return out
    return {prefix: tree}


def dir_bytes(path: str, suffix: str = "") -> int:
    total = 0
    for root, _, files in os.walk(path):
        total += sum(os.path.getsize(os.path.join(root, f))
                     for f in files if f.endswith(suffix))
    return total


@dataclasses.dataclass
class Outcome:
    """What one operation did.  ``answer``: the arrays it returned, flat
    names (None for a write); ``step``: the checkpoint step it restored;
    ``after``: clean-up to run once the timed span has closed."""

    work: OpWork
    answer: "dict | None" = None
    step: "int | None" = None
    after: "object | None" = None
    t0: float = 0.0
    t1: float = 0.0


class Session:
    """One run's state: the configuration, its input, a working directory.

    ``inputs`` holds the input as device arrays, flat names; ``compressed``
    names the leaves the configuration compresses.  ``layout`` finds the
    input maker and the operations by name.
    """

    def __init__(self, config: dict, seed: int, chips: int, workdir: str,
                 layout):
        from repro.core import CodecConfig

        self.spec = config["input"]
        self.seed = seed
        self.chips = chips
        self.workdir = workdir
        self.layout = layout
        self.codec_config = CodecConfig(**config["codec"])
        self.eb = float(config["codec"]["eb"])
        self.min_size = int(self.spec.get("compress_min_size", 1))
        self.inputs = None
        self._maker = layout.plugin("inputs", self.spec["kind"])
        self.shapes = self._maker.shapes(self.spec, chips)
        self.compressed = {n for n, s in self.shapes.items()
                           if compressed_leaf(s, self.spec)}
        self.step = 0
        self.saved_step = None
        self.disk_bytes = None   # bytes the newest write put on disk

    def make_inputs(self):
        import jax

        if self.spec.get("dtype", "float32") != "float32":
            raise ValueError(f"input dtype {self.spec['dtype']!r} is not "
                             f"float32")
        self.inputs = self._maker.make(self.spec, self.seed, self.chips)
        jax.block_until_ready(self.inputs)

    def codec(self):
        """A fresh ``Codec``: its plan cache is cold, as in a new process."""
        from repro.core import Codec

        return Codec(self.codec_config)

    def total_bytes(self) -> int:
        return sum(int(np.prod(s)) * 4 for s in self.shapes.values())

    def values_bytes(self) -> int:
        return sum(int(np.prod(self.shapes[n])) * 4 for n in self.compressed)

    def op(self, name: str):
        return self.layout.plugin("ops", name)

    def run_op(self, op: dict) -> Outcome:
        """Run one operation and time it on the host clock."""
        mod = self.op(op["op"])
        t0 = time.perf_counter()
        out = mod.run(self, op)
        out.t0, out.t1 = t0, time.perf_counter()
        out.work.kind = mod.KIND
        return out

"""Program spans and the transfer and compile counters (runtime/trace.py).

A tiny checkpoint save and restore and an archive read run under
``jax.profiler.trace`` on the CPU; the test reads the ``.xplane.pb`` back
and checks the spans docs/api.md lists, their nesting, and the operation
ids the prefetch thread carries.  The counters are checked against byte
sums worked out from the archive's own index, and the number of host
transfers per operation against the count taken before the helpers
replaced the direct calls.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import ARCHIVE_NAME, CheckpointManager
from repro.core import Codec, CodecConfig
from repro.core.huffman.pipeline import T_HIGH_DEFAULT
from repro.core.sz.lorenzo import DEFAULT_RADIUS
from repro.runtime import trace
from repro.store import Archive, ArchiveWriter

DOCS = os.path.join(os.path.dirname(__file__), "..", "docs", "api.md")
SRC = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
#: The codec path of the benchmark's cells (device encode, fused decode),
#: with the jnp backends in place of the interpreted Pallas kernels.
CFG = CodecConfig(eb=1e-3, encode_backend="jnp", backend="ref", fused=True)
MIN_SIZE = 1024
SPS = 32                       # subsequences per sequence (encoder default)


def _tree():
    key = jax.random.PRNGKey(0)
    return {"w": jax.random.normal(key, (64, 96), jnp.float32).cumsum(0),
            "v": jax.random.normal(key, (48, 64), jnp.float32),
            "norm": jnp.ones((32,), jnp.float32)}


def _read_spans(directory):
    """``(name, start, end, thread, op)`` of every ``repro.`` event."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out, thread = [], 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            thread += 1
            out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                        thread, dict(ev.stats).get("op", 0))
                       for ev in line.events
                       if ev.name.startswith(trace.PREFIX))
    return out


def _documented():
    """Span names of docs/api.md's "Tracing" table."""
    text = open(DOCS).read()
    section = text.split("## Tracing", 1)[1].split("\n## ", 1)[0]
    return set(re.findall(r"^\| `(repro\.[\w.]+)`", section, re.M))


def _in_source():
    names = set()
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        names |= set(re.findall(r"trace\.(?:span|operation)\(\s*\"([\w.]+)\"",
                                open(path).read()))
    return {trace.PREFIX + n for n in names}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("traced"))
    tree = _tree()
    ckpt = os.path.join(tmp, "ck")
    arch = os.path.join(tmp, "a.szt")
    codec = Codec(CFG)
    with ArchiveWriter(arch, codec=codec) as w:
        for name in ("w", "v", "norm"):
            w.add(name, codec.compress(tree[name]))
    # Warm every program first: the trace then holds what a warm run does.
    CheckpointManager(ckpt, codec=Codec(CFG), compress_min_size=MIN_SIZE
                      ).save(1, tree)
    CheckpointManager(ckpt, codec=Codec(CFG)).restore()
    with Archive(arch, codec=Codec(CFG)) as ar:
        ar.read_all(group_chunks=1)
    with jax.profiler.trace(os.path.join(tmp, "trace")):
        CheckpointManager(ckpt, codec=Codec(CFG), compress_min_size=MIN_SIZE
                          ).save(2, tree)
        jax.block_until_ready(
            CheckpointManager(ckpt, codec=Codec(CFG)).restore()["params"])
        with Archive(arch, codec=Codec(CFG)) as ar:
            # One chunk per group: more than one group, so every group is
            # staged on the prefetch thread.
            jax.block_until_ready(ar.read_all(group_chunks=1))
    return _read_spans(os.path.join(tmp, "trace"))


def test_every_span_is_documented():
    assert _in_source() == _documented()


def test_every_documented_span_appears(traced):
    seen = {name for name, *_ in traced}
    assert _documented() - seen == set()


def _parent(spans, child):
    """The innermost span enclosing ``child`` on its thread, or None."""
    name, s, e, th, _ = child
    around = [sp for sp in spans if sp is not child and sp[3] == th
              and sp[1] <= s and e <= sp[2]]
    return max(around, key=lambda sp: (sp[1], -sp[2]), default=None)


#: Where each span opens in the traced operations (None: outermost on its
#: thread).
PARENTS = {
    "repro.ckpt.save": {None}, "repro.ckpt.restore": {None},
    "repro.archive.read_all": {None, "repro.ckpt.restore"},
    "repro.ckpt.snapshot": {"repro.ckpt.save"},
    "repro.codec.compress": {"repro.ckpt.save"},
    "repro.compress.stats": {"repro.codec.compress"},
    "repro.compress.outliers": {"repro.codec.compress"},
    "repro.compress.codebook": {"repro.codec.compress"},
    "repro.compress.pack": {"repro.codec.compress"},
    "repro.archive.add": {"repro.ckpt.save"},
    "repro.archive.fetch": {"repro.archive.add"},
    "repro.ckpt.save_raw": {"repro.ckpt.save"},
    "repro.ckpt.publish": {"repro.ckpt.save"},
    "repro.ckpt.manifest": {"repro.ckpt.restore"},
    "repro.archive.open": {"repro.ckpt.restore", None},
    "repro.archive.stage": {"repro.archive.read_all", None},
    "repro.archive.wait": {"repro.archive.read_all"},
    "repro.archive.cast": {"repro.archive.read_all"},
    "repro.plan.build": {"repro.archive.read_all"},
    "repro.plan.count": {"repro.plan.build"},
    "repro.plan.offsets": {"repro.plan.build"},
    "repro.plan.classify": {"repro.plan.build"},
    "repro.decode.dispatch": {"repro.archive.read_all"},
    "repro.ckpt.load_raw": {"repro.ckpt.restore"},
}


def test_spans_nest_on_their_thread(traced):
    assert set(PARENTS) == _documented()
    for sp in traced:
        name, s, e, th, _ = sp
        for other in traced:
            if other[3] == th and other is not sp:
                # Properly nested or disjoint, never crossing.
                assert not (s < other[1] < e < other[2]), (sp, other)
        parent = _parent(traced, sp)
        assert (parent and parent[0]) in PARENTS[name], (sp, parent)
        if parent is not None:
            assert parent[4] == sp[4]           # the operation's id


def test_operations_draw_ids_and_prefetch_carries_them(traced):
    tops = [sp for sp in traced if _parent(traced, sp) is None
            and sp[0] in ("repro.ckpt.save", "repro.ckpt.restore",
                          "repro.archive.read_all")]
    assert [sp[0] for sp in tops] == ["repro.ckpt.save", "repro.ckpt.restore",
                                      "repro.archive.read_all"]
    ops = [sp[4] for sp in tops]
    assert len(set(ops)) == 3 and all(ops)
    read = tops[2]
    prefetched = [sp for sp in traced if sp[0] == "repro.archive.stage"
                  and sp[3] != read[3]]
    assert len(prefetched) == 3          # every group, the first included
    assert {sp[4] for sp in prefetched} == {read[4]}
    # Every span serves an operation but an archive's open, which the
    # caller does before it reads.
    assert all(sp[4] for sp in traced if _parent(traced, sp) is not None)
    assert {sp[0] for sp in traced if not sp[4]} == {"repro.archive.open"}


def test_helpers_count_exact_bytes():
    host = np.arange(10, dtype=np.int32)
    dev = jnp.arange(6, dtype=jnp.float32)
    before = trace.counters()
    y = trace.to_device(host)
    assert isinstance(y, jax.Array)
    assert trace.to_device(dev) is dev               # already there
    assert trace.to_host(dev).dtype == np.float32
    assert trace.to_host(host) is not None           # already there
    assert trace.to_host(dev, np.int64).dtype == np.int64
    after = trace.counters()
    assert after["h2d_bytes"] - before["h2d_bytes"] == 40
    assert after["d2h_bytes"] - before["d2h_bytes"] == 2 * 24


def _delta(fn):
    before = Codec(CFG).stats
    fn()
    after = Codec(CFG).stats
    return {k: after[k] - before[k] for k in ("h2d_bytes", "d2h_bytes")}


def test_raw_checkpoint_moves_its_leaves_once(tmp_path):
    tree = _tree()
    leaf_bytes = sum(int(x.nbytes) for x in tree.values())
    mgr = CheckpointManager(str(tmp_path))           # raw leaves only
    assert _delta(lambda: mgr.save(1, tree)) == {"h2d_bytes": 0,
                                                 "d2h_bytes": leaf_bytes}
    assert _delta(lambda: mgr.restore()) == {"h2d_bytes": leaf_bytes,
                                             "d2h_bytes": 0}


def test_compressed_checkpoint_byte_sums(tmp_path):
    """Every transfer of a save and a restore, from the archive's index.

    Save, per compressed leaf of n float32 values: the leaf to the host
    and back (4n each way), the value range and max |x| (2 x 4 B), the
    outlier count (4 B), the 2*radius-bin histogram (int32), the payload
    (units, gaps, outlier positions and values) with its bit and symbol
    counts (2 x 4 B) to the host; the codebook's code and length tables
    (uint32 and uint8 per bin) to the device.  The outlier lists are
    gathered on the device and stay there.  A raw leaf goes to the host
    once.

    Restore, per chunk: the payload to the device, the decode tables
    (uint16 symbol and uint8 length per LUT entry) once for the plan and
    once for the decode, the per-sequence counts (int32) to the device for
    the classification; the gaps back to the host for the corrupt-gap
    check, the per-subsequence counts (int32), the class histogram and the
    class and order lists (int32) to the host.  A raw leaf goes to the
    device once.
    """
    tree = _tree()

    def mgr():                   # a fresh Codec: its plan cache is cold
        return CheckpointManager(str(tmp_path), codec=Codec(CFG),
                                 compress_min_size=MIN_SIZE)

    mgr().save(1, tree)
    mgr().restore()                                  # warm every program
    save = _delta(lambda: mgr().save(2, tree))
    restore = _delta(lambda: mgr().restore())

    bins = 2 * DEFAULT_RADIUS
    path = os.path.join(str(tmp_path), "step_00000002", ARCHIVE_NAME)
    want_save = {"h2d_bytes": 0, "d2h_bytes": 0}
    want_restore = {"h2d_bytes": 0, "d2h_bytes": 0}
    with Archive(path) as ar:
        for name, leaf in tree.items():
            n4 = int(leaf.nbytes)
            want_save["d2h_bytes"] += n4
            if "params." + name not in ar:
                want_restore["h2d_bytes"] += n4
                continue
            rec = ar.chunk("params." + name)
            payload = (rec.units.length + rec.gaps.length
                       + rec.outlier_pos.length + rec.outlier_val.length)
            lut = (1 << ar.codebook(rec.codebook).max_len) * (2 + 1)
            n_subseq = rec.gaps.length
            n_seq = n_subseq // SPS
            want_save["d2h_bytes"] += 8 + 4 + 4 * bins + payload + 8
            want_save["h2d_bytes"] += n4 + bins * (4 + 1)
            want_restore["h2d_bytes"] += payload + 2 * lut + 4 * n_seq
            want_restore["d2h_bytes"] += (n_subseq + 4 * n_subseq
                                          + 4 * (T_HIGH_DEFAULT + 2)
                                          + 2 * 4 * n_seq)
    assert "params.norm" not in Archive(path).names   # one raw leaf
    assert save == want_save
    assert restore == want_restore


def test_compiles_count_new_programs_only():
    f = jax.jit(lambda x: x * 3 + 1)
    x5, x7 = jnp.ones((5,), jnp.float32), jnp.ones((7,), jnp.float32)
    c0 = Codec(CFG).stats
    f(x5).block_until_ready()
    c1 = Codec(CFG).stats
    f(x5).block_until_ready()
    c2 = Codec(CFG).stats
    f(x7).block_until_ready()
    c3 = Codec(CFG).stats
    assert c1["compiles"] == c0["compiles"] + 1
    assert c2["compiles"] == c1["compiles"]
    assert c3["compiles"] == c2["compiles"] + 1
    assert c3["compile_ms"] >= c0["compile_ms"]
    assert all(isinstance(v, int) for v in c3.values())


# -- the helpers replaced transfers and added none ----------------------------

#: Host transfers per warm operation on ``_tree()``: (host-to-device, as the
#: transfer guard logs them; device-to-host, as ``np.asarray`` /
#: ``np.array`` of a device array and ``jax.Array._value`` fetches count
#: them), taken on the code before the helpers replaced the direct calls.
TRANSFERS = {"save": (8, 23), "restore": (29, 10), "write": (6, 20),
             "read": (28, 10)}


@pytest.fixture
def count_transfers(monkeypatch, capfd):
    from jax._src import array as jax_array

    d2h = [0]
    value = jax_array.ArrayImpl._value

    def fetch(self):
        d2h[0] += 1
        return value.fget(self)

    monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(fetch))
    for fn in ("asarray", "array"):
        real = getattr(np, fn)

        def counted(x, *args, _real=real, **kwargs):
            if isinstance(x, jax.Array):
                d2h[0] += 1
            return _real(x, *args, **kwargs)

        monkeypatch.setattr(np, fn, counted)

    def count(op):
        capfd.readouterr()
        d2h[0] = 0
        with jax.transfer_guard_host_to_device("log_explicit"):
            op()
        err = capfd.readouterr().err
        return err.count("host-to-device transfer"), d2h[0]

    return count


def test_transfers_per_operation_unchanged(tmp_path, count_transfers):
    tree = _tree()
    ckpt, arch = str(tmp_path / "ck"), str(tmp_path / "a.szt")

    def save(step):
        CheckpointManager(ckpt, codec=Codec(CFG),
                          compress_min_size=MIN_SIZE).save(step, tree)

    def restore():
        jax.block_until_ready(
            CheckpointManager(ckpt, codec=Codec(CFG)).restore()["params"])

    def write():
        codec = Codec(CFG)
        with ArchiveWriter(arch, codec=codec) as w:
            for name in ("w", "v"):
                w.add(name, codec.compress(tree[name]))

    def read():
        with Archive(arch, codec=Codec(CFG)) as ar:
            jax.block_until_ready(ar.read_all())

    ops = {"save": (lambda: save(1), lambda: save(2)),
           "restore": (restore, restore), "write": (write, write),
           "read": (read, read)}
    for name, (warm, measured) in ops.items():
        warm()
        assert count_transfers(measured) == TRANSFERS[name], name

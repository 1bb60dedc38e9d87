"""The trace reduction: on hand-made intervals, and on a trace recorded on
one TPU v5e (``data/v5e_read.xplane.pb.gz``: one ``Archive.read_all`` of a
512**3 float32 archive at rel eb 1e-3, inside a ``bench.decompress`` span,
all programs compiled beforehand)."""

import gzip
import os
import shutil

import pytest

from bench import trace as T

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_read.xplane.pb.gz")


def _trace():
    # Device 0 busy 0-10 and 20-25, device 1 busy 5-30; spans cover 0-30.
    return T.Trace(
        ops={0: [(0, 4), (3, 10), (20, 25)], 1: [(5, 30)]},
        programs={0: [("decode", 0, 10), ("pack", 20, 25)],
                  1: [("decode", 5, 30)]},
        spans=[("window", 0, 40), ("decompress", 0, 15),
               ("compress", 15, 30)])


def test_union_and_clip():
    assert T.union([(3, 5), (0, 4), (6, 6), (7, 9)]) == [(0, 5), (7, 9)]
    assert T.clip([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
    assert T.clip([(0, 10)], [(2, 3), (4, 6)]) == [(2, 3), (4, 6)]
    assert T.length([(0, 4), (2, 6)]) == 6


def test_busy_and_idle_share():
    t = _trace()
    dec = t.spans_of("decompress")
    assert T.busy_ns(t, 0, dec) == 10
    assert T.busy_ns(t, 1, dec) == 10
    # Mean over the two devices of 1 - busy/span: (1 - 10/15) both.
    assert T.idle_share(t, dec) == pytest.approx(1 / 3)
    comp = t.spans_of("compress")
    assert T.idle_share(t, comp) == pytest.approx(
        ((1 - 5 / 15) + (1 - 15 / 15)) / 2)
    assert T.idle_share(t, []) is None


def test_top_programs_and_gaps():
    t = _trace()
    top = T.top_programs(t, t.spans_of("window"))
    assert top == [["decode", 35 / 1e9], ["pack", 5 / 1e9]]
    # Device 0 is idle over 10-20 (inside compress) and 25-40 (no span).
    assert T.idle_gaps(t, (0, 40)) == [["between spans", 15 / 1e9],
                                       ["compress", 10 / 1e9]]


def test_program_name():
    assert T.program_name("jit_decode_write_tiles_fused(1234)") == \
        "decode_write_tiles_fused"
    assert T.program_name("fusion.3") == "fusion.3"


def test_recorded_v5e_trace(tmp_path):
    path = tmp_path / "read.xplane.pb"
    with gzip.open(RECORDED) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    t = T.load(str(tmp_path))             # finds the file under a directory
    assert t.devices == [0]
    dec = t.spans_of("decompress")
    assert len(dec) == 1
    span = T.length(dec)
    assert span == pytest.approx(11.021171948e9)
    busy = T.busy_ns(t, 0, dec)
    assert busy == pytest.approx(10.983962393e9)
    assert T.idle_share(t, dec) == pytest.approx(1 - busy / span)
    top = T.top_programs(t, dec)
    assert top[0][0] == "decode_write_tiles_fused"
    assert top[0][1] == pytest.approx(10.383368275)
    assert top[1][0] == "subseq_counts"
    gaps = T.idle_gaps(t, dec[0])
    assert len(gaps) == 10 and all(label == "decompress"
                                   for label, _ in gaps)
    assert sum(g for _, g in gaps) <= (span - busy) / 1e9

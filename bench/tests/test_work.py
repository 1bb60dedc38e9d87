"""The work counts behind both rooflines, at a tiny size on the CPU."""

import os

import numpy as np
import pytest

from bench import harness, work
from bench.session import Session
from bench.tests import tiny


def test_byte_arithmetic():
    w = work.OpWork(values_bytes=4000, payload_bytes=500, total_bytes=4100)
    assert work.bytes_moved(w) == 4500
    # 819e9 bytes in one busy second is the whole roofline.
    assert work.roofline_percent(819e9, 1.0, 819e9) == pytest.approx(100.0)
    assert work.roofline_percent(819e6, 2.0, 819e9) == pytest.approx(0.05)
    assert work.roofline_percent(10, 0.0, 819e9) is None


@pytest.fixture
def session(tmp_path):
    s = Session(tiny.TREE, seed=3, chips=1, workdir=str(tmp_path),
                layout=harness.Layout())
    s.make_inputs()
    return s


def test_checkpoint_work_counts(session):
    # embed (64, 32) and layers.w (4, 16, 4, 16) compress; norm (32,) is raw.
    values = (64 * 32 + 4 * 16 * 4 * 16) * 4
    assert session.compressed == {"embed", "layers.w"}
    assert session.values_bytes() == values
    assert session.total_bytes() == values + 32 * 4

    save = session.run_op({"op": "ckpt_save"})
    step_dir = os.path.join(str(session.workdir), "ckpt", "step_00000001")
    szt = sum(os.path.getsize(os.path.join(step_dir, f))
              for f in os.listdir(step_dir) if f.endswith(".szt"))
    assert save.work.kind == "compress"
    assert save.work.values_bytes == values
    assert save.work.payload_bytes == szt > 0
    assert session.disk_bytes > szt          # manifest and raw leaf too

    restore = session.run_op({"op": "ckpt_restore"})
    assert restore.work.kind == "decompress"
    assert restore.work.payload_bytes == szt
    assert restore.step == 1
    assert work.bytes_moved(restore.work) == szt + values
    assert set(restore.answer) == set(session.shapes)
    assert restore.t1 >= restore.t0


def test_archive_work_counts(tmp_path):
    s = Session(tiny.FIELD, seed=3, chips=1, workdir=str(tmp_path),
                layout=harness.Layout())
    s.make_inputs()
    write = s.run_op({"op": "archive_write"})
    size = os.path.getsize(os.path.join(str(tmp_path), "data.szt"))
    values = int(np.prod(tiny.FIELD["input"]["shape"])) * 4
    assert (write.work.values_bytes, write.work.payload_bytes) == (values,
                                                                   size)
    assert work.bytes_moved(write.work) == values + size
    read = s.run_op({"op": "archive_read"})
    assert read.work.payload_bytes == size
    assert read.answer["field"].shape == tuple(tiny.FIELD["input"]["shape"])

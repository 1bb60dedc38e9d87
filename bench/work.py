"""The bytes a compress or a decompress has to move, from its payload.

A roofline share is the least time the chip could take over the time it
took.  The least time here is bytes over the HBM bandwidth: the codec does
a few integer operations per value, far below the chip's compute peak, so
memory bounds it.  The bytes come from what goes in and what comes out,
not from the kernels that move them, so the count stays the same whatever
implements the work:

* decompress: the compressed payload read (the ``.szt`` files) plus the
  decoded values written;
* compress: the values read plus the compressed payload written.

Leaves kept raw are neither read nor written by the codec's device
programs and count in neither.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class OpWork:
    """What one timed operation moved.

    ``values_bytes``: bytes of the float32 leaves the codec compresses.
    ``payload_bytes``: bytes of the compressed files (``.szt``) involved.
    ``total_bytes``: bytes of every leaf, raw ones included (what the user
    saves or restores).  ``kind``: ``"compress"`` or ``"decompress"``.
    """

    values_bytes: int
    payload_bytes: int
    total_bytes: int
    kind: str = ""


def bytes_moved(w: OpWork) -> int:
    """Payload and values, one read and the other written, either way."""
    return w.payload_bytes + w.values_bytes


def roofline_percent(nbytes: float, busy_s: float,
                     peak_bytes_per_s: float) -> "float | None":
    """``100 * (nbytes / peak) / busy_s``; None where nothing ran."""
    if busy_s <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / peak_bytes_per_s / busy_s

"""The plain reference, and the comparison that decides ``correct``.

The codec's contract (cuSZ dual quantization, as the configuration states
it): a float32 tensor ``x`` compressed at relative error bound ``eb`` comes
back as

    e  = eb * (max(x) - min(x))          (float32 range, float64 product)
    s  = float32(2 * float32(e))
    x' = float32(round_half_even(x / s)) * s      (float32 arithmetic)

so every value lies within ``e`` of the input, up to one float32 rounding
of the product.  Tensors the configuration leaves uncompressed (under
``compress_min_size`` values) come back bit for bit.  The reference below
computes ``x'`` in NumPy, on the host, from the input alone: it imports
nothing of the program and takes none of its tables or bounds.  It works
through a tensor in blocks, so that a 512**3 field needs a few blocks'
worth of host memory beyond the two arrays compared.

``precision="bfloat16"`` is the control: the same reference one precision
step down, put in the program's place, which the comparison has to fail.
"""

from __future__ import annotations

import numpy as np

BLOCK = 1 << 22          # values per block of the comparison


def compressed_leaf(shape, spec: dict) -> bool:
    """Whether the configuration compresses a leaf of this shape: every
    leaf of ``compress_min_size`` values or more (every leaf without it)."""
    return int(np.prod(shape)) >= int(spec.get("compress_min_size", 1))


def bound(x: np.ndarray, eb: float) -> "tuple[float, float]":
    """``(e, e_eff)``: the absolute bound, and the bound plus one float32
    rounding step of the reconstruction at max |x|."""
    span = float(np.float32(x.max()) - np.float32(x.min()))
    e = eb * (span if span > 0 else 1.0)
    e_eff = e + float(np.spacing(np.float32(float(np.abs(x).max()) + e)))
    return e, e_eff


def _blocks(n: int):
    for i in range(0, n, BLOCK):
        yield slice(i, min(i + BLOCK, n))


def reconstruct_block(xb: np.ndarray, e: float,
                      precision: str = "float32") -> np.ndarray:
    """The reference's ``x'`` for a block of ``x``, as float32."""
    if precision == "float32":
        s = np.float32(2 * np.float32(e))
        return np.round(xb / s).astype(np.float32) * s
    if precision == "bfloat16":
        import ml_dtypes

        bf = ml_dtypes.bfloat16
        s = bf(2 * np.float32(e))
        q = np.round((xb.astype(bf) / s).astype(bf)).astype(bf)
        return (q * s).astype(bf).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def control_answer(x: np.ndarray, compressed: bool, eb: float,
                   precision: str = "bfloat16") -> np.ndarray:
    """The reference computed at ``precision``, in the program's place."""
    x = np.asarray(x, np.float32)
    flat = x.reshape(-1)
    if not compressed:
        import ml_dtypes

        return flat.astype(ml_dtypes.bfloat16).astype(np.float32).reshape(
            x.shape)
    e, _ = bound(x, eb)
    out = np.empty_like(flat)
    for sl in _blocks(flat.size):
        out[sl] = reconstruct_block(flat[sl], e, precision)
    return out.reshape(x.shape)


class Tally:
    """The numbers compared over one answer, leaf by leaf."""

    def __init__(self):
        self.missing = 0          # leaves absent, or of another shape/dtype
        self.raw_mismatch = 0     # values of uncompressed leaves not equal
        self.err_over_eb = 0.0    # worst |x' - x| / e_eff, compressed leaves
        self.lattice_bad = 0      # compressed values != the reference's
        self.lattice_n = 0

    def add_leaf(self, x, y, compressed: bool, eb: float):
        x = np.asarray(x)
        if y is None:
            self.missing += 1
            return
        y = np.asarray(y)
        if y.shape != x.shape or y.dtype != x.dtype:
            self.missing += 1
            return
        xf, yf = x.reshape(-1), y.reshape(-1)
        if not compressed:
            self.raw_mismatch += int(np.count_nonzero(
                xf.view(np.uint32) != yf.view(np.uint32)))
            return
        e, e_eff = bound(x, eb)
        worst = 0.0
        for sl in _blocks(xf.size):
            xb, yb = xf[sl], yf[sl]
            want = reconstruct_block(xb, e)
            # Compared as numbers: the reference's -0.0 is the codec's 0.0.
            self.lattice_bad += int(np.count_nonzero(want != yb))
            d = np.abs(yb.astype(np.float64) - xb)
            worst = max(worst, float(d.max()) if np.all(np.isfinite(d))
                        else np.inf)
        self.err_over_eb = max(self.err_over_eb, worst / e_eff)
        self.lattice_n += xf.size

    @property
    def lattice_mismatch(self) -> float:
        return self.lattice_bad / max(self.lattice_n, 1)


def compare(inputs: dict, answer: dict, spec: dict, eb: float) -> Tally:
    """Tally an answer ``{name: array}`` against the inputs it came from."""
    t = Tally()
    for name, x in inputs.items():
        x = np.asarray(x)
        t.add_leaf(x, answer.get(name), compressed_leaf(x.shape, spec), eb)
    t.missing += len(set(answer) - set(inputs))
    return t


def checks(tally: Tally, limits: dict, *, missing: int = 0, stale: int = 0,
           differing: int = 0) -> dict:
    """Every number that decides ``correct``, as ``{name: (value, limit)}``:
    the tally of the answer compared in full, and the window's own counts
    (answers missing leaves, restores of an older step, answers whose
    fingerprint differs from the one compared)."""
    return {
        "missing": (missing + tally.missing, 0),
        "stale": (stale, 0),
        "differing": (differing, 0),
        "raw_mismatch": (tally.raw_mismatch, 0),
        "err_over_eb": (tally.err_over_eb, float(limits["err_over_eb"])),
        "lattice_mismatch": (tally.lattice_mismatch,
                             float(limits["lattice_mismatch"])),
    }


def passes(numbers: dict) -> bool:
    """Whether every number of :func:`checks` lies within its limit."""
    return all(v <= lim for v, lim in numbers.values())

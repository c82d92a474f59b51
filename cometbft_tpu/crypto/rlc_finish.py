"""The last step of the RLC batch verdict, on the host.

The RLC programs (``ops/rlc.py``) do the work that grows with the batch
on the chip and return the per-window lane sums; what is left of

    [8]( [Σ zᵢsᵢ]B  +  Σ_w 16^w·(S_A[w] + S_R[w]) )  ==  identity

is a fold of 96 points and one scalar: 252 doublings one after another
whatever the batch.  On the chip that is a one-lane loop of sub-
microsecond ops (62 ms a dispatch on a v5e, at 256 lanes or at 4,096);
with the native library's 64-bit field arithmetic it is ~0.1 ms, so it
runs here.  Trust does not move: the host already draws the
coefficients and takes the verdict.

The packed array, one int32 ``(NLIMBS, COLS)`` transfer (~31 kB), limb-
major like the kernel's field elements (13-bit limbs, column c is one
element):

- columns ``[A0, A0 + 256)``: ``S_A``'s cached coordinates
  ``(Y+X, Y-X, 2Z, 2dT)``, 64 windows each, window w at ``k*64 + w``
- columns ``[R0, R0 + 128)``: ``S_R``'s, 32 windows each
- column ``ZS``: ``Σ zᵢsᵢ`` (correct mod L, below 2^256)
- column ``OK``: ``lanes_ok`` (1 or 0, broadcast down the column)

The chip does NOT freeze the sums: limbs arrive in ``fe_lm``'s loose
form (at most ``fe.LIMB_MAX``), and both folds take any non-negative
int32 limbs, reading an element as ``Σ limbᵢ·2^(13i) mod p``.  A
negative limb is outside the kernel's contract and refutes the batch
(the caller then localizes with the per-lane kernel).

The fold runs in ``native/ed25519.cpp`` (``ed25519_rlc_fold``, with the
GIL held: ``_native_ed25519._rlc_fold_fn`` says why) or, where the
library cannot be built, on Python integers with ``_ed25519_py``'s
point arithmetic: a few ms, and the tests' oracle.  ``crypto_rlc_finish_total{impl}`` says which ran.
"""

from __future__ import annotations

import functools

import numpy as np

from . import _ed25519_py as ref
from . import _native_ed25519 as native

NLIMBS, RADIX = 20, 13
NW_A, NW_R = 64, 32
A0, R0 = 0, 4 * NW_A
ZS = R0 + 4 * NW_R
OK = ZS + 1
COLS = OK + 1
SHAPE = (NLIMBS, COLS)

_INV2 = pow(2, ref.P - 2, ref.P)
_INV2D = pow(2 * ref.D, ref.P - 2, ref.P)


@functools.cache
def _counter():
    from ..libs import metrics as m

    return m.counter("crypto_rlc_finish_total",
                     "RLC verdicts finished on the host, by implementation "
                     "(native | python: python means the native library "
                     "is missing)")


def pack(sum_a, sum_r, zs_sum: int, lanes_ok: bool) -> np.ndarray:
    """Extended points (Python ints) -> the kernel's packed layout, with
    canonical limbs.  ``sum_a`` 64 and ``sum_r`` 32 points, window 0
    first.  For tests and stand-ins of the compiled programs."""
    out = np.zeros(SHAPE, np.int32)

    def put(col, v):
        for i in range(NLIMBS):
            out[i, col] = (v >> (RADIX * i)) & ((1 << RADIX) - 1)

    for base, nw, pts in ((A0, NW_A, sum_a), (R0, NW_R, sum_r)):
        assert len(pts) == nw
        for w, (x, y, z, t) in enumerate(pts):
            cached = ((y + x) % ref.P, (y - x) % ref.P, 2 * z % ref.P,
                      2 * ref.D * t % ref.P)
            for k, v in enumerate(cached):
                put(base + k * nw + w, v)
    put(ZS, zs_sum)
    out[:, OK] = int(bool(lanes_ok))
    return out


def verdict(ok: bool) -> np.ndarray:
    """A packed array that folds to ``ok``: every sum the identity."""
    return pack([ref.IDENTITY] * NW_A, [ref.IDENTITY] * NW_R, 0, ok)


def fold_python(packed: np.ndarray) -> bool:
    """The group equation over a packed array, on Python integers."""
    weights = np.array([1 << (RADIX * i) for i in range(NLIMBS)], object)
    vals = (packed.astype(object) * weights[:, None]).sum(axis=0)

    def point(base, nw, w):
        ypx, ymx, z2, t2d = (int(vals[base + k * nw + w]) for k in range(4))
        return ((ypx - ymx) * _INV2 % ref.P, (ypx + ymx) * _INV2 % ref.P,
                z2 * _INV2 % ref.P, t2d * _INV2D % ref.P)

    acc = ref.IDENTITY
    for w in reversed(range(NW_A)):
        for _ in range(4):
            acc = ref.pt_double(acc)
        acc = ref.pt_add(acc, point(A0, NW_A, w))
        if w < NW_R:
            acc = ref.pt_add(acc, point(R0, NW_R, w))
    acc = ref.pt_add(acc, ref.pt_mul(int(vals[ZS]) % ref.L, ref.BASE))
    for _ in range(3):
        acc = ref.pt_double(acc)
    return ref.pt_equal(acc, ref.IDENTITY)


def finish(packed) -> tuple[bool, bool]:
    """An RLC program's output -> ``(verdict, ran_native)``: True iff
    every active lane decoded and the cofactored equation holds."""
    packed = np.ascontiguousarray(packed, np.int32)
    if packed.shape != SHAPE:
        raise ValueError(f"RLC sums of shape {packed.shape}, not {SHAPE}")
    ran_native = native.available()
    if not packed[0, OK] or packed.min() < 0:
        ok = False
    elif ran_native:
        ok = native.rlc_fold(packed)
    else:
        ok = fold_python(packed)
    _counter().inc(impl="native" if ran_native else "python")
    return ok, ran_native

"""Limb-major GF(2^255 - 19) field arithmetic: elements are (20, B) int32.

The batch-major layout (``ops/fe.py``, elements ``(B, 20)``) puts the
20-limb axis on the TPU's 128-wide vector lane dimension — ~16% lane
utilization — and its einsum multiply materializes a ``(B, 20, 39)``
Toeplitz intermediate that falls out of VMEM past ~4k lanes (measured
on a v5e in round 4).  This module flips the layout: the BATCH rides
the vector lanes, limbs ride the sublane axis, and the multiply is 20
statically-shifted row-accumulations with no Toeplitz intermediate.
Measured on the full verify pipeline (CPU rehearsal,
scripts/kern_layout_probe.py): 1.26x at 1024 lanes to 1.63x at 4096,
growing with batch size — which is why this is the production layout for
the point arithmetic (``ops/ed25519.py``) as of round 5.

Same representation as ``ops/fe.py`` (20 limbs of 13 bits, loose-form
bound LIMB_MAX, carries via parallel passes with the 2^260 ≡ 608 fold);
only the axis convention differs.  Byte-unpack utilities and the
scalar/SHA pipelines stay batch-major in their own modules — their
outputs feed the ladder purely as (B,) gather indices, which are
layout-agnostic.

Layout hooks consumed by ``ops/group.py`` (the layout-generic point
formulas): ``const``, ``bcast``, ``sign_bit``, ``limb0``,
``from_bytes32``.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import fe

RADIX, MASK, NL, NC, FOLD = fe.RADIX, fe.MASK, fe.NLIMBS, fe.NCOLS, fe.FOLD
P_INT, D_INT = fe.P_INT, fe.D_INT
LIMB_MAX = fe.LIMB_MAX


def const(x: int) -> jnp.ndarray:
    """Python int -> (20, 1) int32 limb column (broadcasts over lanes)."""
    return jnp.asarray(fe.limbs_from_int(x % P_INT).reshape(NL, 1))


def bcast(c, lane_shape) -> jnp.ndarray:
    """Broadcast a (20, 1) constant over a 1-D lane shape -> (20, n)."""
    (n,) = tuple(lane_shape)
    return jnp.broadcast_to(c, (NL, n))


def sign_bit(enc):
    """(32, B) encoded bytes -> (B,) Edwards sign bit."""
    return (enc[31].astype(jnp.int32) >> 7) & 1


def limb0(x):
    """Lowest limb, (B,) — parity source for frozen elements."""
    return x[0]


SUB_OFF = jnp.asarray(np.asarray(fe.SUB_OFF, np.int32).reshape(NL, 1))
SQRT_M1 = const(fe.SQRT_M1_INT)


def _wrap_carry(x, passes: int):
    """Parallel carry passes on (20, …) with the 2^260 ≡ 608 wraparound."""
    for _ in range(passes):
        lo = x & MASK
        hi = x >> RADIX
        wrapped = jnp.concatenate([hi[-1:] * FOLD, hi[:-1]], axis=0)
        x = lo + wrapped
    return x


def add(a, b):
    return _wrap_carry(a + b, 1)


def sub(a, b):
    return _wrap_carry(a + SUB_OFF - b, 2)


def neg(a):
    return sub(jnp.zeros_like(a), a)


def _reduce_columns(cols):
    """(39, B) int32 product columns -> loose (20, B)."""
    lo = cols & MASK
    hi = cols >> RADIX
    limbs40 = jnp.concatenate([lo, jnp.zeros_like(lo[:1])],
                              axis=0).at[1:].add(hi)
    folded = limbs40[:NL] + FOLD * limbs40[NL:]
    return _wrap_carry(folded, 3)


def mul(a, b):
    """Shifted accumulation: 20 statically-placed partial products into
    the 39 columns — a fully fusable elementwise graph, no (B, 20, 39)
    intermediate (the batch-major layout's HBM hazard)."""
    out = jnp.zeros((NC,) + jnp.broadcast_shapes(a.shape[1:], b.shape[1:]),
                    jnp.int32)
    for i in range(NL):
        out = out.at[i:i + NL].add(a[i:i + 1] * b)
    return _reduce_columns(out)


def square(a):
    return mul(a, a)


def mul_small(a, k: int):
    """Multiply by a small constant k < 2^15 (loose in, loose out)."""
    assert 0 < k < (1 << 15)
    return _wrap_carry(a * jnp.int32(k), 3)


def select(mask, a, b):
    """mask (B,) bool -> limbs from a where true else b."""
    return jnp.where(mask[None, :], a, b)


def freeze(a):
    """Loose -> canonical in [0, p); mirrors fe.freeze on axis 0."""
    limbs = []
    c = jnp.zeros_like(a[0])
    for i in range(NL):
        t = a[i] + c
        limbs.append(t & MASK)
        c = t >> RADIX
    t = limbs[0] + c * FOLD
    limbs[0] = t & MASK
    c = t >> RADIX
    for i in range(1, NL):
        t = limbs[i] + c
        limbs[i] = t & MASK
        c = t >> RADIX
    limbs[0] = limbs[0] + c * FOLD
    q = limbs[19] >> 8
    limbs[19] = limbs[19] & 255
    c = q * 19
    for i in range(NL):
        t = limbs[i] + c
        limbs[i] = t & MASK
        c = t >> RADIX
    x = jnp.stack(limbs, axis=0)
    borrow = jnp.zeros_like(x[0])
    diff = []
    for i in range(NL):
        t = x[i] - jnp.int32(int(fe.P_LIMBS[i])) - borrow
        diff.append(t & MASK)
        borrow = (t >> RADIX) & 1
    d = jnp.stack(diff, axis=0)
    return select(borrow == 0, d, x)


def is_zero(a):
    return jnp.all(freeze(a) == 0, axis=0)


def eq(a, b):
    return is_zero(sub(a, b))


def from_bytes32(bt, mask_bit255: bool = True):
    """(32, B) little-endian bytes -> (20, B) limbs of the raw 255-bit
    value (not reduced mod p; ZIP-215 decoding reduces lazily)."""
    bt = bt.astype(jnp.int32)
    limbs = []
    for i in range(NL):
        bit0 = RADIX * i
        acc = jnp.zeros_like(bt[0])
        for j in range(bit0 // 8, min((bit0 + RADIX + 7) // 8, 32)):
            shift = 8 * j - bit0
            byte = bt[j]
            if mask_bit255 and j == 31:
                byte = byte & 127
            acc = acc + (byte << shift if shift >= 0 else byte >> -shift)
        limbs.append(acc & MASK)
    return jnp.stack(limbs, axis=0)


def _sq_n(a, n: int):
    """Rolled squarings: compile one body regardless of n (see fe._sq_n)."""
    if n <= 1:
        return square(a) if n else a
    return jax.lax.fori_loop(0, n, lambda _, x: square(x), a)


def _pow_chain(z):
    """Shared ref10 ladder: returns (z^(2^250 - 1), z^11)."""
    z2 = square(z)
    z9 = mul(z, _sq_n(z2, 2))
    z11 = mul(z2, z9)
    z_5_0 = mul(z9, square(z11))
    z_10_0 = mul(_sq_n(z_5_0, 5), z_5_0)
    z_20_0 = mul(_sq_n(z_10_0, 10), z_10_0)
    z_40_0 = mul(_sq_n(z_20_0, 20), z_20_0)
    z_50_0 = mul(_sq_n(z_40_0, 10), z_10_0)
    z_100_0 = mul(_sq_n(z_50_0, 50), z_50_0)
    z_200_0 = mul(_sq_n(z_100_0, 100), z_100_0)
    z_250_0 = mul(_sq_n(z_200_0, 50), z_50_0)
    return z_250_0, z11


def pow22523(z):
    """z^((p-5)/8), ref10 addition chain."""
    z_250_0, _ = _pow_chain(z)
    return mul(_sq_n(z_250_0, 2), z)


def invert(z):
    """z^(p-2) = z^(2^255 - 21)."""
    z_250_0, z11 = _pow_chain(z)
    return mul(_sq_n(z_250_0, 5), z11)


def sqrt_ratio(u, v):
    """x with x^2 = u/v if it exists: (x, ok).  RFC 8032 decompression."""
    v3 = mul(square(v), v)
    uv3 = mul(u, v3)
    uv7 = mul(uv3, square(square(v)))
    x = mul(uv3, pow22523(uv7))
    vxx = mul(v, square(x))
    ok_direct = eq(vxx, u)
    ok_flip = eq(vxx, neg(u))
    x = select(ok_direct, x, mul(x, SQRT_M1))
    return x, ok_direct | ok_flip

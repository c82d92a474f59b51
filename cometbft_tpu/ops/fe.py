"""GF(2^255 - 19) field arithmetic on int32 limbs, for TPU.

Replaces the field arithmetic of curve25519-voi (the reference's Ed25519
backend, ``go.mod:50``) with a representation chosen for TPU vector units:
**20 limbs of 13 bits (radix 2^13) held in int32**.  With 13-bit limbs a
schoolbook product column is at most ``20 * (2^13)^2 < 2^31``, so the whole
multiplier runs in native int32 with no 64-bit widening — TPUs have no
native 64-bit integer multiply, which rules out the classical 25.5-bit-limb
(Go/C) layout.

Representation invariant ("loose" form): limbs are non-negative int32 with
``limb <= LIMB_MAX`` (9407).  All public ops accept and return loose form;
``freeze`` produces the canonical representative in ``[0, p)``.  Carrying is
done with *parallel* carry passes (every limb masked/shifted simultaneously,
overflow limb folded back through ``2^260 ≡ 608 (mod p)``) instead of a
sequential chain, so a carry costs ~3 vector ops rather than a 20-deep
dependency chain.

Shapes: field elements are int32 arrays ``(..., 20)``; all ops broadcast over
leading batch axes (the signature batch).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

RADIX = 13
MASK = (1 << RADIX) - 1          # 8191
NLIMBS = 20
NCOLS = 2 * NLIMBS - 1           # 39 product columns
# 2^260 = 2^(13*20) ≡ 2^5 * 19 = 608 (mod p)
FOLD = 608
LIMB_MAX = MASK + 1216           # loose-form bound; 20 * LIMB_MAX^2 < 2^31

P_INT = 2**255 - 19
D_INT = (-121665 * pow(121666, P_INT - 2, P_INT)) % P_INT
SQRT_M1_INT = pow(2, (P_INT - 1) // 4, P_INT)


def limbs_from_int(x: int) -> np.ndarray:
    """Python int -> canonical (20,) int32 limb array (host-side, constants)."""
    assert 0 <= x < 2**260
    return np.array([(x >> (RADIX * i)) & MASK for i in range(NLIMBS)],
                    dtype=np.int32)


def int_from_limbs(limbs) -> int:
    """(…,20) limbs -> Python int (host-side, tests)."""
    arr = np.asarray(limbs)
    return sum(int(arr[..., i]) << (RADIX * i) for i in range(NLIMBS))


P_LIMBS = limbs_from_int(P_INT)
D_LIMBS = limbs_from_int(D_INT)
D2_LIMBS = limbs_from_int(2 * D_INT % P_INT)
SQRT_M1_LIMBS = limbs_from_int(SQRT_M1_INT)
ONE_LIMBS = limbs_from_int(1)
ZERO_LIMBS = limbs_from_int(0)

# Subtraction offset: a multiple of p whose limb decomposition has every limb
# >= 2^14, so per-limb (a + SUB_OFF - b) never goes negative for loose a, b.
# We need  96p - 2^14 * sum(2^(13i))  to decompose into 13-bit limbs.
_U = (2**260 - 1) // MASK        # sum of 2^(13i), i in [0, 20)
_rem = 96 * P_INT - (1 << 14) * _U
assert 0 <= _rem < 2**260, "96p offset decomposition failed"
SUB_OFF = limbs_from_int(_rem) + np.int32(1 << 14)
assert int_from_limbs(SUB_OFF) == 96 * P_INT
assert SUB_OFF.min() >= 1 << 14 and SUB_OFF.max() <= MASK + (1 << 14)


def _wrap_carry(x, passes: int):
    """Parallel carry passes on (…,20) with 2^260 ≡ 608 wraparound."""
    for _ in range(passes):
        lo = x & MASK
        hi = x >> RADIX
        wrapped = jnp.concatenate(
            [hi[..., -1:] * FOLD, hi[..., :-1]], axis=-1)
        x = lo + wrapped
    return x


def add(a, b):
    """a + b (loose in, loose out)."""
    return _wrap_carry(a + b, 1)


def sub(a, b):
    """a - b (loose in, loose out); offsets by 96p to stay non-negative."""
    return _wrap_carry(a + jnp.asarray(SUB_OFF) - b, 2)


def neg(a):
    return sub(jnp.zeros_like(a), a)


def _reduce_columns(cols):
    """(…,39) int32 product columns -> loose (…,20)."""
    lo = cols & MASK
    hi = cols >> RADIX
    # one non-wrapping pass -> 40 limbs, each <= MASK + 2^18
    limbs40 = jnp.concatenate(
        [lo, jnp.zeros_like(lo[..., :1])], axis=-1
    ).at[..., 1:].add(hi)
    folded = limbs40[..., :NLIMBS] + FOLD * limbs40[..., NLIMBS:]
    return _wrap_carry(folded, 3)


# Toeplitz gather pattern: column k of the product takes b[k - i] from limb i.
_MUL_IDX = np.zeros((NLIMBS, NCOLS), np.int32)
_MUL_MSK = np.zeros((NLIMBS, NCOLS), np.int32)
for _i in range(NLIMBS):
    for _k in range(NCOLS):
        if 0 <= _k - _i < NLIMBS:
            _MUL_IDX[_i, _k] = _k - _i
            _MUL_MSK[_i, _k] = 1


def _mul_einsum(a, b):
    """One gather builds the (…,20,39) Toeplitz matrix of b, one int32
    contraction produces all 39 product columns — 3 XLA ops instead of
    an unrolled 400-MAC graph."""
    bmat = b[..., jnp.asarray(_MUL_IDX)] * jnp.asarray(_MUL_MSK)
    cols = jnp.einsum("...i,...ik->...k", a, bmat,
                      preferred_element_type=jnp.int32)
    return _reduce_columns(cols)


def _mul_shift(a, b):
    """Shifted accumulation: 20 statically-sliced partial products into
    the 39 columns, no (…,20,39) intermediate.  Candidate fix for the
    measured large-batch HBM cliff (TPU v5e: einsum throughput halves
    past ~4k lanes because the 32MB-per-mul Toeplitz intermediate falls
    out of VMEM, round 4); fully fusable elementwise graph instead."""
    out = jnp.zeros(a.shape[:-1] + (NCOLS,), jnp.int32)
    for i in range(NLIMBS):
        out = out.at[..., i:i + NLIMBS].add(a[..., i:i + 1] * b)
    return _reduce_columns(out)


# Selected at import: the einsum form is the measured default; the shift
# form is promotable once hardware numbers exist for it (see
# scripts/kern_layout_probe.py).
_MUL_IMPL = {"einsum": _mul_einsum, "shift": _mul_shift}


def mul(a, b):
    """Field multiply (loose in, loose out)."""
    return _mul_active(a, b)


import os as _os  # noqa: E402  (grouped with the selection it serves)

_mul_choice = _os.environ.get("COMETBFT_TPU_FE_MUL", "").strip().lower()
if _mul_choice and _mul_choice not in _MUL_IMPL:
    # a typo here would silently measure the WRONG kernel during a
    # scarce hardware window — fail loudly instead
    raise ValueError(
        f"COMETBFT_TPU_FE_MUL={_mul_choice!r}: expected one of "
        f"{sorted(_MUL_IMPL)}")
_mul_active = _MUL_IMPL.get(_mul_choice, _mul_einsum)


def square(a):
    return mul(a, a)


def mul_small(a, k: int):
    """Multiply by a small constant k (loose in, loose out).

    k < 2^15 keeps products < 9407 * 32767 < 2^31; three carry passes restore
    the loose bound from that magnitude (two are not enough above k ~ 40000).
    """
    assert 0 < k < (1 << 15)
    return _wrap_carry(a * jnp.int32(k), 3)


def select(mask, a, b):
    """Per-element select: mask (…,) bool -> limbs from a where true else b."""
    return jnp.where(mask[..., None], a, b)


# Layout hooks consumed by ops/group.py (the layout-generic point
# formulas); ops/fe_lm.py provides the limb-major counterparts.

def const(x: int) -> jnp.ndarray:
    """Python int -> (20,) int32 limb constant (broadcasts over lanes)."""
    return jnp.asarray(limbs_from_int(x % P_INT))


def bcast(c, lane_shape) -> jnp.ndarray:
    """Broadcast a (20,) constant over a lane shape -> lane_shape + (20,)."""
    return jnp.broadcast_to(c, tuple(lane_shape) + (NLIMBS,))


def sign_bit(enc):
    """(…, 32) encoded bytes -> (…,) Edwards sign bit."""
    return (enc[..., 31].astype(jnp.int32) >> 7) & 1


def limb0(x):
    """Lowest limb, (…,) — parity source for frozen elements."""
    return x[..., 0]


def freeze(a):
    """Loose -> canonical representative in [0, p). Sequential exact carry."""
    # exact carry chain; value < 20 * LIMB_MAX * 2^247 < 2^261
    limbs = []
    c = jnp.zeros_like(a[..., 0])
    for i in range(NLIMBS):
        t = a[..., i] + c
        limbs.append(t & MASK)
        c = t >> RADIX
    # overflow c (<= 1) folds via 2^260 ≡ 608.  The ripple can cascade through
    # every limb (e.g. value 2^260 - 1), and can even overflow limb 19 again —
    # in which case the remaining value is < 608 and a second fold cannot
    # cascade (608 + 607 < 2^13), so one full ripple + one add suffices.
    t = limbs[0] + c * FOLD
    limbs[0] = t & MASK
    c = t >> RADIX
    for i in range(1, NLIMBS):
        t = limbs[i] + c
        limbs[i] = t & MASK
        c = t >> RADIX
    limbs[0] = limbs[0] + c * FOLD
    # clear bits >= 255: q = value >> 255 (limb 19 bits 8..12), add 19q
    q = limbs[19] >> 8
    limbs[19] = limbs[19] & 255
    c = q * 19
    for i in range(NLIMBS):
        t = limbs[i] + c
        limbs[i] = t & MASK
        c = t >> RADIX
    # now value < p + 608: one conditional subtract of p
    x = jnp.stack(limbs, axis=-1)
    borrow = jnp.zeros_like(x[..., 0])
    diff = []
    for i in range(NLIMBS):
        t = x[..., i] - jnp.int32(int(P_LIMBS[i])) - borrow
        diff.append(t & MASK)
        borrow = (t >> RADIX) & 1   # t in (-2^13, 2^13): borrow is 0 or 1
    d = jnp.stack(diff, axis=-1)
    ge_p = borrow == 0
    return select(ge_p, d, x)


def is_zero(a):
    """(…,) bool: a ≡ 0 (mod p)."""
    return jnp.all(freeze(a) == 0, axis=-1)


def eq(a, b):
    return is_zero(sub(a, b))


def parity(a):
    """Canonical low bit (…,) int32 in {0,1}."""
    return freeze(a)[..., 0] & 1


def bytes_to_limbs(b, nlimbs: int, mask_top_bit: bool = False):
    """(…,nbytes) uint8/int32 little-endian bytes -> canonical 13-bit limbs.

    Shared unpack used for field elements (32 bytes -> 20 limbs), scalars
    (32 -> 20) and 512-bit hashes (64 -> 40).  ``mask_top_bit`` drops the
    highest bit of the last byte (the Edwards sign bit).
    """
    nbytes = b.shape[-1]
    b = b.astype(jnp.int32)
    limbs = []
    for i in range(nlimbs):
        bit0 = RADIX * i
        acc = jnp.zeros_like(b[..., 0])
        for j in range(bit0 // 8, min((bit0 + RADIX + 7) // 8, nbytes)):
            shift = 8 * j - bit0
            byte = b[..., j]
            if mask_top_bit and j == nbytes - 1:
                byte = byte & 127
            if shift >= 0:
                acc = acc + (byte << shift)
            else:
                acc = acc + (byte >> (-shift))
        limbs.append(acc & MASK)
    return jnp.stack(limbs, axis=-1)


def from_bytes32(b, mask_bit255: bool = True):
    """(…,32) LE bytes -> limbs of the raw 255-bit integer (not reduced mod
    p; the value is < 2^255 so loose-form bounds hold — ZIP-215 decoding
    reduces lazily via field ops)."""
    return bytes_to_limbs(b, NLIMBS, mask_top_bit=mask_bit255)


def to_bytes32(a):
    """Canonical little-endian encoding (…,32) int32 in [0,256). Freezes."""
    x = freeze(a)
    out = []
    for j in range(32):
        bit0 = 8 * j
        acc = jnp.zeros_like(x[..., 0])
        for i in range(bit0 // RADIX, min((bit0 + 7) // RADIX + 1, NLIMBS)):
            shift = bit0 - RADIX * i
            if shift >= 0:
                acc = acc | (x[..., i] >> shift)
            else:
                acc = acc | (x[..., i] << (-shift))
        out.append(acc & 255)
    return jnp.stack(out, axis=-1)


def _sq_n(a, n: int):
    """n successive squarings; rolled into fori_loop to keep graphs small
    (compile time scales superlinearly with unrolled op count)."""
    if n <= 1:
        return square(a) if n else a
    return jax.lax.fori_loop(0, n, lambda _, x: square(x), a)


def _pow_chain(z):
    """Shared ref10 ladder: returns (z^(2^250 - 1), z^11)."""
    z2 = square(z)                     # 2
    z9 = mul(z, _sq_n(z2, 2))          # 9
    z11 = mul(z2, z9)                  # 11
    z_5_0 = mul(z9, square(z11))       # 2^5 - 2^0
    z_10_0 = mul(_sq_n(z_5_0, 5), z_5_0)
    z_20_0 = mul(_sq_n(z_10_0, 10), z_10_0)
    z_40_0 = mul(_sq_n(z_20_0, 20), z_20_0)
    z_50_0 = mul(_sq_n(z_40_0, 10), z_10_0)
    z_100_0 = mul(_sq_n(z_50_0, 50), z_50_0)
    z_200_0 = mul(_sq_n(z_100_0, 100), z_100_0)
    z_250_0 = mul(_sq_n(z_200_0, 50), z_50_0)
    return z_250_0, z11


def pow22523(z):
    """z^((p-5)/8) = z^(2^252 - 3), ref10 addition chain."""
    z_250_0, _ = _pow_chain(z)
    return mul(_sq_n(z_250_0, 2), z)


def invert(z):
    """z^(p-2) = z^(2^255 - 21)."""
    z_250_0, z11 = _pow_chain(z)
    return mul(_sq_n(z_250_0, 5), z11)


def sqrt_ratio(u, v):
    """x with x^2 = u/v, if it exists (RFC 8032 decompression core).

    Returns ``(x, ok)``: ok is False where u/v is a non-square.  The returned
    x is an arbitrary root (caller fixes parity).
    """
    v3 = mul(square(v), v)
    uv3 = mul(u, v3)
    uv7 = mul(uv3, square(square(v)))
    x = mul(uv3, pow22523(uv7))
    vxx = mul(v, square(x))
    ok_direct = eq(vxx, u)
    ok_flip = eq(vxx, neg(u))
    x_flip = mul(x, jnp.asarray(SQRT_M1_LIMBS))
    x = select(ok_direct, x, x_flip)
    return x, ok_direct | ok_flip

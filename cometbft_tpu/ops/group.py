"""Twisted-Edwards (ed25519 curve) point formulas, written once and
parameterized over a field-arithmetic module.

Extended homogeneous coordinates (X:Y:Z:T), a = -1, the hwcd-2008
unified addition/doubling family — the same formulas curve25519-voi and
ref10 use (reference seam: the curve math behind
``crypto/ed25519/ed25519.go``), chosen because they are branch-free and
vectorize cleanly over the signature batch.

The formulas are pure compositions of field ops, so the data layout is
entirely the field module's business: :func:`make_group` instantiates
the whole group layer for either ``ops.fe`` (batch-major ``(B, 20)`` —
kept for the oracle-differential tests) or ``ops.fe_lm`` (limb-major
``(20, B)`` — the production kernel layout, see ``fe_lm``'s module doc
for the measured rationale).  A field module provides the arithmetic
(add/sub/neg/mul/square/select/freeze/is_zero/eq/sqrt_ratio) plus four
layout hooks: ``const`` (int -> broadcastable limb constant), ``bcast``
(constant x lane shape -> full array), ``sign_bit`` and ``limb0``
(byte/limb accessors), and ``from_bytes32``.

Representations (each component a limb array in the field layout):
- extended: ``(X, Y, Z, T)``  with x = X/Z, y = Y/Z, T = XY/Z
- cached:   ``(Y+X, Y-X, 2Z, 2dT)``   (general addition operand)
- niels:    ``(Y+X, Y-X, 2dXY)``      (affine table entry, Z = 1)
- tree:     ``(Y+X, Y-X, Z, kT)``, k = sqrt(2d)  (the RLC lane tree's
  node form, ``add_tree``: kT·kT' is 2d·TT' with no constant)
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Ext(NamedTuple):
    x: jnp.ndarray
    y: jnp.ndarray
    z: jnp.ndarray
    t: jnp.ndarray


class Cached(NamedTuple):
    ypx: jnp.ndarray
    ymx: jnp.ndarray
    z2: jnp.ndarray
    t2d: jnp.ndarray


class Niels(NamedTuple):
    ypx: jnp.ndarray
    ymx: jnp.ndarray
    t2d: jnp.ndarray


class Tree(NamedTuple):
    ypx: jnp.ndarray
    ymx: jnp.ndarray
    z: jnp.ndarray
    tk: jnp.ndarray


def sqrt_mod_p(a: int, p: int) -> int:
    """The even square root of ``a`` mod ``p`` (p ≡ 5 mod 8), host-side,
    for constants; raises if ``a`` is not a square."""
    x = pow(a, (p + 3) // 8, p)
    if x * x % p != a % p:
        x = x * pow(2, (p - 1) // 4, p) % p
    if x * x % p != a % p:
        raise ValueError("not a square mod p")
    return p - x if x & 1 else x


def make_group(f) -> SimpleNamespace:
    """Instantiate the point ops over field module ``f``."""
    P, D = f.P_INT, f.D_INT
    ONE_C = f.const(1)
    ZERO_C = f.const(0)
    D_C = f.const(D)
    D2_C = f.const(2 * D % P)
    INV2_C = f.const(pow(2, P - 2, P))
    INV2D_C = f.const(pow(2 * D % P, P - 2, P))
    K = sqrt_mod_p(2 * D % P, P)                  # k² ≡ 2d: 2d is a square
    K_C = f.const(K)
    K_INV2_C = f.const(2 * pow(K, P - 2, P))      # 2/k

    def identity(lane_shape=()) -> Ext:
        zero = f.bcast(ZERO_C, lane_shape)
        one = f.bcast(ONE_C, lane_shape)
        return Ext(zero, one, one, zero)

    def cache(p: Ext) -> Cached:
        return Cached(f.add(p.y, p.x), f.sub(p.y, p.x), f.add(p.z, p.z),
                      f.mul(p.t, D2_C))

    def neg_ext(p: Ext) -> Ext:
        return Ext(f.neg(p.x), p.y, p.z, f.neg(p.t))

    def dbl(p: Ext) -> Ext:
        a = f.square(p.x)
        b = f.square(p.y)
        c = f.add(f.square(p.z), f.square(p.z))
        h = f.add(a, b)
        e = f.sub(h, f.square(f.add(p.x, p.y)))
        g = f.sub(a, b)
        ff = f.add(c, g)
        return Ext(f.mul(e, ff), f.mul(g, h), f.mul(ff, g), f.mul(e, h))

    def add_cached(p: Ext, q: Cached) -> Ext:
        a = f.mul(f.sub(p.y, p.x), q.ymx)
        b = f.mul(f.add(p.y, p.x), q.ypx)
        c = f.mul(p.t, q.t2d)
        d = f.mul(p.z, q.z2)
        e = f.sub(b, a)
        ff = f.sub(d, c)
        g = f.add(d, c)
        h = f.add(b, a)
        return Ext(f.mul(e, ff), f.mul(g, h), f.mul(ff, g), f.mul(e, h))

    def add_niels(p: Ext, q: Niels) -> Ext:
        a = f.mul(f.sub(p.y, p.x), q.ymx)
        b = f.mul(f.add(p.y, p.x), q.ypx)
        c = f.mul(p.t, q.t2d)
        d = f.add(p.z, p.z)
        e = f.sub(b, a)
        ff = f.sub(d, c)
        g = f.add(d, c)
        h = f.add(b, a)
        return Ext(f.mul(e, ff), f.mul(g, h), f.mul(ff, g), f.mul(e, h))

    def add_cc(p: Cached, q: Cached) -> Cached:
        """Cached x Cached -> Cached, for tree reductions (the RLC batch
        multiscalar, ``ops/rlc.py``): gathered table entries are already
        in cached form, and emitting cached form feeds the next tree
        level without a per-level ``cache()`` conversion.  Recovers the
        add_cached operands via the constant factors 1/2 and 1/(2d):
        T1*2dT2 = t2d_p*t2d_q/(2d), Z1*2Z2 = z2_p*z2_q/2."""
        a = f.mul(p.ymx, q.ymx)
        b = f.mul(p.ypx, q.ypx)
        c = f.mul(f.mul(p.t2d, q.t2d), INV2D_C)
        d = f.mul(f.mul(p.z2, q.z2), INV2_C)
        e = f.sub(b, a)
        ff = f.sub(d, c)
        g = f.add(d, c)
        h = f.add(b, a)
        x3 = f.mul(e, ff)
        y3 = f.mul(g, h)
        z3 = f.mul(ff, g)
        t3 = f.mul(e, h)
        return Cached(f.add(y3, x3), f.sub(y3, x3), f.add(z3, z3),
                      f.mul(t3, D2_C))

    def tree_identity(lane_shape=()) -> Tree:
        zero = f.bcast(ZERO_C, lane_shape)
        one = f.bcast(ONE_C, lane_shape)
        return Tree(one, one, one, zero)

    def cached_to_tree(p: Cached) -> Tree:
        """Cached -> tree form of the same point scaled by 2:
        (2(Y+X), 2(Y-X), 2Z, 2kT), with 2kT = 2dT·(2/k)."""
        return Tree(f.add(p.ypx, p.ypx), f.add(p.ymx, p.ymx), p.z2,
                    f.mul(p.t2d, K_INV2_C))

    def tree_to_cached(p: Tree) -> Cached:
        """Tree -> cached: 2Z by an add, 2dT = k·(kT)."""
        return Cached(p.ypx, p.ymx, f.add(p.z, p.z), f.mul(p.tk, K_C))

    def add_tree(p: Tree, q: Tree) -> Tree:
        """Tree x Tree -> Tree, the RLC lane tree's node.  The hwcd-2008
        unified addition with C = kT1·kT2 = 2d·T1T2 and D = 2·Z1Z2, so the
        operands need no constant factor: 9 field multiplications a node
        (A, B, C, D, X3, Y3, Z3, T3 and k·T3) against ``add_cc``'s 11,
        which spends 3 on the constants 1/(2d), 1/2 and 2d."""
        a = f.mul(p.ymx, q.ymx)
        b = f.mul(p.ypx, q.ypx)
        c = f.mul(p.tk, q.tk)
        d = f.mul(p.z, q.z)
        d = f.add(d, d)
        e = f.sub(b, a)
        ff = f.sub(d, c)
        g = f.add(d, c)
        h = f.add(b, a)
        x3 = f.mul(e, ff)
        y3 = f.mul(g, h)
        return Tree(f.add(y3, x3), f.sub(y3, x3), f.mul(ff, g),
                    f.mul(f.mul(e, h), K_C))

    def cached_to_ext(p: Cached) -> Ext:
        """Cached -> extended (X = (ypx-ymx)/2, Y = (ypx+ymx)/2,
        Z = z2/2, T = t2d/(2d)); used once at the end of a tree."""
        return Ext(f.mul(f.sub(p.ypx, p.ymx), INV2_C),
                   f.mul(f.add(p.ypx, p.ymx), INV2_C),
                   f.mul(p.z2, INV2_C),
                   f.mul(p.t2d, INV2D_C))

    @jax.named_scope("decompress")
    def decompress_zip215(enc):
        """ZIP-215 (permissive) point decoding: non-canonical y >= p
        accepted, x = 0 with sign bit 1 accepted, small/mixed-order
        points fine; the only failure is a non-square x^2 candidate.
        Returns ``(Ext, ok)``; failed rows hold arbitrary but
        arithmetic-safe content (callers mask with ``ok``)."""
        sign = f.sign_bit(enc)
        y = f.from_bytes32(enc, True)
        yy = f.square(y)
        u = f.sub(yy, f.bcast(ONE_C, sign.shape))
        v = f.add(f.mul(yy, D_C), f.bcast(ONE_C, sign.shape))
        x, ok = f.sqrt_ratio(u, v)
        x = f.freeze(x)
        flip = (f.limb0(x) & 1) != sign
        x = f.select(flip, f.neg(x), x)
        return Ext(x, y, f.bcast(ONE_C, sign.shape), f.mul(x, y)), ok

    def mul_by_cofactor(p: Ext) -> Ext:
        return jax.lax.fori_loop(0, 3, lambda _, q: dbl(q), p)

    def is_identity(p: Ext):
        """Projective identity check: X == 0 and Y == Z (mod p)."""
        return f.is_zero(p.x) & f.eq(p.y, p.z)

    return SimpleNamespace(
        f=f, identity=identity, cache=cache, neg_ext=neg_ext, dbl=dbl,
        add_cached=add_cached, add_niels=add_niels, add_cc=add_cc,
        tree_identity=tree_identity, cached_to_tree=cached_to_tree,
        tree_to_cached=tree_to_cached, add_tree=add_tree, K=K,
        cached_to_ext=cached_to_ext, decompress_zip215=decompress_zip215,
        mul_by_cofactor=mul_by_cofactor, is_identity=is_identity)

"""Batched SHA-512 in JAX on uint32 pairs, for TPU.

Ed25519 verification needs ``h = SHA-512(R || A || M)`` per signature; this
module computes it on-device for the whole batch (reference hot path:
``crypto/ed25519/ed25519.go`` via curve25519-voi, which hashes on CPU —
here the hash rides the same TPU batch as the curve math).

TPUs have no 64-bit integer units, so a u64 is a pair of uint32 lanes
``(hi, lo)``; adds carry via an unsigned compare, rotates recombine across the
pair.  Messages are padded host-side (cheap numpy) into fixed 128-byte blocks;
on device every lane runs the same static number of block compressions with a
per-lane active-block count masking the tail — XLA sees static shapes, the
batch stays dense.

Round constants/IV are derived from first principles (frac of cube/square
roots of primes) at import and cross-checked against hashlib in tests.
"""

from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["sha512_blocks", "host_pad", "max_blocks_for_len"]


def _primes(n: int):
    ps, c = [], 2
    while len(ps) < n:
        if all(c % q for q in ps if q * q <= c):
            ps.append(c)
        c += 1
    return ps


def _icbrt(x: int) -> int:
    r = int(round(x ** (1 / 3)))
    while r * r * r > x:
        r -= 1
    while (r + 1) ** 3 <= x:
        r += 1
    return r


_M64 = (1 << 64) - 1
_K64 = [_icbrt(p << 192) & _M64 for p in _primes(80)]
_IV64 = [math.isqrt(p << 128) & _M64 for p in _primes(8)]

K = np.array([[k >> 32, k & 0xFFFFFFFF] for k in _K64], dtype=np.uint32)
IV = np.array([[v >> 32, v & 0xFFFFFFFF] for v in _IV64], dtype=np.uint32)


def _add64(a, b):
    lo = a[1] + b[1]
    carry = (lo < b[1]).astype(jnp.uint32)
    return (a[0] + b[0] + carry, lo)


def _add64n(*xs):
    acc = xs[0]
    for x in xs[1:]:
        acc = _add64(acc, x)
    return acc


def _ror64(x, n: int):
    hi, lo = x
    if n == 32:
        return (lo, hi)
    if n < 32:
        return ((hi >> n) | (lo << (32 - n)), (lo >> n) | (hi << (32 - n)))
    m = n - 32
    return ((lo >> m) | (hi << (32 - m)), (hi >> m) | (lo << (32 - m)))


def _shr64(x, n: int):
    hi, lo = x
    if n < 32:
        return (hi >> n, (lo >> n) | (hi << (32 - n)))
    return (jnp.zeros_like(hi), hi >> (n - 32))


def _xor64(*xs):
    hi, lo = xs[0]
    for x in xs[1:]:
        hi, lo = hi ^ x[0], lo ^ x[1]
    return (hi, lo)


def _big_sigma0(x):
    return _xor64(_ror64(x, 28), _ror64(x, 34), _ror64(x, 39))


def _big_sigma1(x):
    return _xor64(_ror64(x, 14), _ror64(x, 18), _ror64(x, 41))


def _sm_sigma0(x):
    return _xor64(_ror64(x, 1), _ror64(x, 8), _shr64(x, 7))


def _sm_sigma1(x):
    return _xor64(_ror64(x, 19), _ror64(x, 61), _shr64(x, 6))


def _ch(e, f, g):
    return ((e[0] & f[0]) ^ (~e[0] & g[0]), (e[1] & f[1]) ^ (~e[1] & g[1]))


def _maj(a, b, c):
    return ((a[0] & b[0]) ^ (a[0] & c[0]) ^ (b[0] & c[0]),
            (a[1] & b[1]) ^ (a[1] & c[1]) ^ (b[1] & c[1]))


def _compress(state, block):
    """One SHA-512 compression. state (…,8,2) u32, block (…,32) u32 BE words."""
    w = block.reshape(block.shape[:-1] + (16, 2))
    kc = jnp.asarray(K)

    def round_body(t, carry):
        av, w = carry
        a, b, c, d, e, f, g, h = [(av[..., i, 0], av[..., i, 1])
                                  for i in range(8)]
        idx = t % 16
        wt_arr = jax.lax.dynamic_index_in_dim(w, idx, axis=w.ndim - 2,
                                              keepdims=False)
        wt = (wt_arr[..., 0], wt_arr[..., 1])
        # schedule extension for t >= 16 (computed always, selected by mask)
        w2 = jax.lax.dynamic_index_in_dim(w, (t + 14) % 16, axis=w.ndim - 2,
                                          keepdims=False)
        w7 = jax.lax.dynamic_index_in_dim(w, (t + 9) % 16, axis=w.ndim - 2,
                                          keepdims=False)
        w15 = jax.lax.dynamic_index_in_dim(w, (t + 1) % 16, axis=w.ndim - 2,
                                           keepdims=False)
        ext = _add64n(_sm_sigma1((w2[..., 0], w2[..., 1])),
                      (w7[..., 0], w7[..., 1]),
                      _sm_sigma0((w15[..., 0], w15[..., 1])),
                      wt)
        use_ext = (t >= 16).astype(jnp.uint32)
        wt = (wt[0] * (1 - use_ext) + ext[0] * use_ext,
              wt[1] * (1 - use_ext) + ext[1] * use_ext)
        w = jax.lax.dynamic_update_index_in_dim(
            w, jnp.stack(wt, axis=-1), idx, axis=w.ndim - 2)

        kt_arr = jax.lax.dynamic_index_in_dim(kc, t, axis=0, keepdims=False)
        kt = (jnp.broadcast_to(kt_arr[0], wt[0].shape),
              jnp.broadcast_to(kt_arr[1], wt[1].shape))
        t1 = _add64n(h, _big_sigma1(e), _ch(e, f, g), kt, wt)
        t2 = _add64(_big_sigma0(a), _maj(a, b, c))
        new = [_add64(t1, t2), a, b, c, _add64(d, t1), e, f, g]
        av = jnp.stack([jnp.stack(p, axis=-1) for p in new], axis=-2)
        return (av, w)

    final, _ = jax.lax.fori_loop(0, 80, round_body, (state, w))
    # feed-forward add
    hi = state[..., 0] + final[..., 0]
    lo = state[..., 1] + final[..., 1]
    carry = (lo < state[..., 1]).astype(jnp.uint32)
    return jnp.stack([hi + carry, lo], axis=-1)


@jax.named_scope("sha512")
def sha512_blocks(blocks, nblocks_active):
    """Batched SHA-512 over prepadded blocks.

    blocks: (…, NB, 32) uint32 big-endian words (NB static);
    nblocks_active: (…,) int32 — per-lane number of real blocks (rest masked).
    Returns the digest as (…, 64) int32 bytes.
    """
    nb = blocks.shape[-2]
    state = jnp.broadcast_to(jnp.asarray(IV), blocks.shape[:-2] + (8, 2))
    for j in range(nb):
        new = _compress(state, blocks[..., j, :])
        mask = (j < nblocks_active)[..., None, None]
        state = jnp.where(mask, new, state)
    # big-endian byte unpack: per u64, hi word then lo word
    out = []
    for i in range(8):
        for word in (state[..., i, 0], state[..., i, 1]):
            for sh in (24, 16, 8, 0):
                out.append(((word >> sh) & 255).astype(jnp.int32))
    return jnp.stack(out, axis=-1)


def max_blocks_for_len(msg_len: int) -> int:
    """Blocks needed for a message of msg_len bytes (incl. 17-byte padding)."""
    return (msg_len + 17 + 127) // 128


def host_pad(msgs: np.ndarray, lens: np.ndarray, nb: int):
    """Host-side SHA-512 padding into fixed (B, nb, 32) uint32 blocks.

    msgs: (B, L) uint8 (rows zero-filled past their length);
    lens: (B,) actual byte lengths;  nb: static block count >= per-row need.
    Returns (blocks (B, nb, 32) uint32, active (B,) int32).
    """
    msgs = np.asarray(msgs, dtype=np.uint8)
    lens = np.asarray(lens, dtype=np.int64)
    bsz, pad_len = msgs.shape[0], nb * 128
    assert int((lens + 17).max(initial=0)) <= pad_len, "bucket too small"
    buf = np.zeros((bsz, pad_len), np.uint8)
    buf[:, :msgs.shape[1]] = msgs
    # zero anything past each row's length, set 0x80 terminator
    col = np.arange(pad_len)
    buf[col[None, :] >= lens[:, None]] = 0
    buf[np.arange(bsz), lens] = 0x80
    # 128-bit big-endian bit length at the end of each row's final block
    active = ((lens + 17 + 127) // 128).astype(np.int64)
    bitlen = lens * 8
    for k in range(8):
        buf[np.arange(bsz), active * 128 - 1 - k] = (bitlen >> (8 * k)) & 255
    words = buf.reshape(bsz, nb, 32, 4)
    blocks = ((words[..., 0].astype(np.uint32) << 24)
              | (words[..., 1].astype(np.uint32) << 16)
              | (words[..., 2].astype(np.uint32) << 8)
              | words[..., 3].astype(np.uint32))
    return blocks, active.astype(np.int32)

"""Differential fuzzer: native/secp256k1.cpp vs the OpenSSL-backed
Python path.

Every triple is derived from a seeded PRNG and RFC 6979 signing, so ANY
mismatch is replayable from the printed (seed, index) alone — the exact
failure mode the r3 flake investigation lacked.

Run standalone:   python tests/fuzz_secp256k1.py [N] [seed]
Run in-process:   pytest tests/test_secp256k1.py -k fuzz   (small N, same
process as the rest of the suite, catching cross-library state effects)

Case classes per triple:
  - the valid signature itself (must accept on both paths)
  - single-bit flip at a random position in sig (identity-proof tamper)
  - last-byte SET (the r3 flake shape, including the identity case)
  - random 64-byte garbage sig
  - boundary r/s: 0, 1, n-1, n, half_n, half_n+1 substituted into a
    valid signature
  - message tamper (flip one bit of the message)
  - wrong pubkey (valid sig checked against a different key)
"""

from __future__ import annotations

import os
import random
import secrets
import sys
import unittest.mock as mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cometbft_tpu.crypto import secp256k1 as s


def _oracle(pub: "s.Secp256k1PubKey", m: bytes, sig: bytes) -> bool:
    with mock.patch.object(s, "_native_lib", lambda: None):
        return pub.verify_signature(m, sig)


def _check(pub, m, sig, ctx):
    native = s._native_verify(pub.bytes(), m, sig)
    oracle = _oracle(pub, m, sig)
    if bool(native) != bool(oracle):
        raise AssertionError(
            f"DIVERGENCE [{ctx}]: native={native} oracle={oracle}\n"
            f"  pub={pub.bytes().hex()}\n  msg={m.hex()}\n"
            f"  sig={sig.hex()}")
    return bool(native)


def fuzz(n_triples: int = 2000, seed: int = 1, progress: bool = False):
    assert s._native_lib() is not None, \
        "native secp256k1 unavailable — nothing to differential-test"
    rng = random.Random(seed)
    n_checked = 0
    bounds = [0, 1, s._N - 1, s._N, s._HALF_N, s._HALF_N + 1]
    for i in range(n_triples):
        sk = s.Secp256k1PrivKey.from_secret(b"fuzz-%d-%d" % (seed, i))
        pub = sk.pub_key()
        m = bytes(rng.getrandbits(8) for _ in range(rng.randrange(0, 120)))
        sig = sk.sign(m)

        assert _check(pub, m, sig, f"valid i={i}"), \
            f"valid sig rejected at i={i}"
        n_checked += 1
        bit = rng.randrange(512)
        flipped = bytearray(sig)
        flipped[bit // 8] ^= 1 << (bit % 8)
        _check(pub, m, bytes(flipped), f"bitflip i={i} bit={bit}")
        n_checked += 1
        setlast = sig[:-1] + bytes([rng.randrange(256)])
        _check(pub, m, setlast, f"setlast i={i}")
        n_checked += 1
        _check(pub, m, secrets.token_bytes(64), f"garbage i={i}")
        n_checked += 1
        which, v = rng.randrange(2), rng.choice(bounds)
        bsig = (v.to_bytes(32, "big") + sig[32:] if which == 0
                else sig[:32] + v.to_bytes(32, "big"))
        _check(pub, m, bsig, f"boundary i={i} {'r' if which == 0 else 's'}")
        n_checked += 1
        if m:
            mbit = rng.randrange(len(m) * 8)
            m2 = bytearray(m)
            m2[mbit // 8] ^= 1 << (mbit % 8)
            _check(pub, bytes(m2), sig, f"msgflip i={i}")
            n_checked += 1
        other = s.Secp256k1PrivKey.from_secret(b"other-%d-%d" % (seed, i))
        _check(other.pub_key(), m, sig, f"wrongkey i={i}")
        n_checked += 1
        if progress and (i + 1) % 500 == 0:
            print(f"  {i + 1}/{n_triples} triples, {n_checked} checks, "
                  "0 divergences", flush=True)
    return n_checked


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2000
    seed = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    checked = fuzz(n, seed, progress=True)
    print(f"OK: {n} triples / {checked} checks, native == oracle on all")

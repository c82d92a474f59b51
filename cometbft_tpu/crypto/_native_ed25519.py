"""ctypes binding for the native (C++) ZIP-215 ed25519 verifier.

``native/ed25519.cpp`` implements single and random-linear-combination
batch verification — the host CPU analogue of the reference's
curve25519-voi batch path (``crypto/ed25519/ed25519.go:188-221``), which
SURVEY §2.9-1 requires to be native, never a Python stand-in.  The batch
entry verifies n signatures as ONE Pippenger multiscalar multiplication,
~5x a single-verify loop at commit scale.  It also hosts the native
canonical vote sign-bytes builder (SURVEY §2.9-4) used by the dense
VerifyCommit fast path, and the last step of the device's RLC verdict
(``rlc_fold``: the fold of the per-window sums the chip returns).

Degrades gracefully: if the on-demand g++ build fails, every function
returns None and callers keep their pure-host path.
"""

from __future__ import annotations

import ctypes
import functools
import os

_U64P = ctypes.POINTER(ctypes.c_uint64)
_I64P = ctypes.POINTER(ctypes.c_int64)
_U8P = ctypes.POINTER(ctypes.c_uint8)


@functools.cache
def _lib():
    try:
        from ..native import lib_path

        lib = ctypes.CDLL(lib_path("ed25519"))
        lib.ed25519_verify.restype = ctypes.c_int
        lib.ed25519_verify.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_uint64]
        lib.ed25519_batch_verify.restype = ctypes.c_int
        lib.ed25519_batch_verify.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            _U64P, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64]
        lib.build_vote_sign_bytes.restype = ctypes.c_uint64
        lib.build_vote_sign_bytes.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64,      # pre_commit
            ctypes.c_char_p, ctypes.c_uint64,      # pre_nil
            ctypes.c_char_p, ctypes.c_uint64,      # post
            _I64P, ctypes.c_char_p, ctypes.c_uint64,   # ts, flags, n
            _U8P, ctypes.c_uint64, _U64P]          # out, stride, lens
        lib.ed25519_pubkey.restype = None
        lib.ed25519_pubkey.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.ed25519_sign.restype = None
        lib.ed25519_sign.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p]
        return lib
    except Exception:
        return None


def available() -> bool:
    return _lib() is not None


def public_key(seed: bytes) -> bytes | None:
    """RFC 8032 public key from a 32-byte seed; None without the lib.
    The host fallback for images without the ``cryptography`` wheel
    (the pure-Python ladder is ~10 ms per key — unusable at valset
    scale)."""
    lib = _lib()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(32)
    lib.ed25519_pubkey(seed, out)
    return out.raw


def sign(seed: bytes, msg: bytes) -> bytes | None:
    """RFC 8032 deterministic signature from a 32-byte seed; None
    without the lib."""
    lib = _lib()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(64)
    lib.ed25519_sign(seed, msg, len(msg), out)
    return out.raw


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool | None:
    """Exact single ZIP-215 verification; None if the lib is unavailable."""
    lib = _lib()
    if lib is None:
        return None
    if len(pub) != 32 or len(sig) != 64:
        return False
    return bool(lib.ed25519_verify(pub, sig, msg, len(msg)))


def batch_verify(pubs: list[bytes], msgs: list[bytes],
                 sigs: list[bytes]) -> bool | None:
    """One RLC batch check over the whole list: True means EVERY signature
    is valid; False means at least one is not (caller localizes with
    single verifies); None when the native lib is unavailable.

    Inputs must be pre-validated: 32-byte pubs, 64-byte sigs.
    """
    lib = _lib()
    if lib is None:
        return None
    n = len(pubs)
    if n == 0:
        return False
    lens = (ctypes.c_uint64 * n)(*[len(m) for m in msgs])
    return bool(lib.ed25519_batch_verify(
        b"".join(pubs), b"".join(sigs), b"".join(msgs), lens, n,
        os.urandom(32), 0))


def batch_verify_dense(pubs, sigs, msgs, lens) -> bool | None:
    """Dense-array RLC batch: ``pubs`` (n,32) u8, ``sigs`` (n,64) u8,
    ``msgs`` (n,stride) u8 zero-padded rows, ``lens`` (n,) — the exact
    matrices the TPU packing path builds, verified without any repacking.
    Arrays must be C-contiguous numpy uint8 (lens any int dtype)."""
    import numpy as np

    lib = _lib()
    if lib is None:
        return None
    n = pubs.shape[0]
    if n == 0:
        return False
    lens64 = np.ascontiguousarray(lens, np.uint64)
    return bool(lib.ed25519_batch_verify(
        pubs.ctypes.data_as(ctypes.c_char_p),
        sigs.ctypes.data_as(ctypes.c_char_p),
        msgs.ctypes.data_as(ctypes.c_char_p),
        lens64.ctypes.data_as(_U64P), n, os.urandom(32), msgs.shape[1]))


@functools.cache
def _rlc_fold_fn():
    """``ed25519_rlc_fold`` through ``ctypes.PyDLL``: the GIL is HELD for
    the call.  It runs ~0.15 ms on the device-owner thread; dropping the
    GIL for it (``CDLL``) hands the interpreter to a caller thread that is
    building its next window's rows in Python, and taking it back then
    waits out the 5 ms switch interval: 5.5 ms a fold against 0.19 ms,
    measured with one busy thread beside it (``PERF.md`` §6, PR 27)."""
    from ..native import lib_path

    fn = ctypes.PyDLL(lib_path("ed25519")).ed25519_rlc_fold
    fn.restype = ctypes.c_int
    fn.argtypes = [ctypes.c_void_p]
    return fn


def rlc_fold(packed) -> bool | None:
    """The group equation over a device RLC program's packed sums
    (``crypto/rlc_finish.py`` has the layout and checks the shape):
    a C-contiguous int32 array of non-negative limbs.  None when the
    lib is unavailable."""
    if _lib() is None:
        return None
    if packed.dtype.str != "<i4" or not packed.flags.c_contiguous \
            or packed.shape != (20, 386):
        raise ValueError("rlc_fold wants a C-contiguous (20, 386) int32")
    return bool(_rlc_fold_fn()(packed.ctypes.data))


def build_vote_sign_bytes(pre_commit: bytes, pre_nil: bytes, post: bytes,
                          ts_ns, flags):
    """Assemble one commit's canonical vote sign-bytes rows natively.

    ``ts_ns`` int64 array (n,), ``flags`` uint8 array (n,) with 2 =
    commit-variant prefix, else nil-variant.  Returns ``(msgs, lens)`` —
    (n, stride) uint8 rows + true lengths — or None when unavailable.
    """
    import numpy as np

    lib = _lib()
    if lib is None:
        return None
    n = len(ts_ns)
    stride = 5 + max(len(pre_commit), len(pre_nil)) + 19 + len(post)
    out = np.zeros((n, stride), np.uint8)
    lens = np.zeros((n,), np.uint64)
    ts64 = np.ascontiguousarray(ts_ns, np.int64)
    fl8 = np.ascontiguousarray(flags, np.uint8)
    rc = lib.build_vote_sign_bytes(
        pre_commit, len(pre_commit), pre_nil, len(pre_nil),
        post, len(post),
        ts64.ctypes.data_as(_I64P),
        fl8.ctypes.data_as(ctypes.c_char_p), n,
        out.ctypes.data_as(_U8P), stride,
        lens.ctypes.data_as(_U64P))
    if rc != 0:                      # stride undersized (can't happen with
        raise RuntimeError("sign-bytes stride miscomputed")  # our formula)
    return out, lens.astype(np.int64)

"""The host's last step of the device RLC verdict (crypto/rlc_finish.py):
the native fold (native/ed25519.cpp ``ed25519_rlc_fold``) against the fold
on Python integers and against the expected verdict, over window sums
built in Python integers the way the kernel builds them on the chip
(``ops/rlc.py _rlc_sums``).  No JAX program compiles here."""

import hashlib

import numpy as np
import pytest

from cometbft_tpu.crypto import _ed25519_py as ref
from cometbft_tpu.crypto import _native_ed25519 as native
from cometbft_tpu.crypto import batch as B
from cometbft_tpu.crypto import rlc_finish as F
from cometbft_tpu.libs import tracing

P, L = ref.P, ref.L

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="the native library did not build")


# ------------------------------------------------- the kernel's sums, by hand

def _signed(n, seed, msg_len=50):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        sd, msg = rng.bytes(32), rng.bytes(msg_len)
        out.append((ref.public_key_from_seed(sd), msg, ref.sign(sd, msg)))
    return out


def _torsion_lanes(seed=46):
    """The two ZIP-215 lanes of tests/test_rlc.py: a mixed-order A with a
    signature over the mixed encoding, and a small-order R under the
    non-canonical identity A.  Their defects are pure torsion."""
    rng = np.random.default_rng(seed)
    while True:
        pt = ref.pt_decompress_zip215(rng.bytes(32))
        if pt is None:
            continue
        t8 = ref.pt_mul(L, pt)
        if not ref.pt_equal(ref.pt_mul(4, t8), ref.IDENTITY):
            break
    h0 = hashlib.sha512(rng.bytes(32)).digest()
    a_sc, prefix = ref._clamp(h0[:32]), h0[32:]
    mixed = ref.pt_compress(ref.pt_add(ref.pt_mul(a_sc, ref.BASE), t8))
    msg = rng.bytes(50)
    r_sc = ref.sc_reduce64(hashlib.sha512(prefix + msg).digest())
    r_enc = ref.pt_compress(ref.pt_mul(r_sc, ref.BASE))
    k_sc = ref.sc_reduce64(hashlib.sha512(r_enc + mixed + msg).digest())
    sig = r_enc + ((r_sc + k_sc * a_sc) % L).to_bytes(32, "little")
    ident_nc = (1 + P).to_bytes(32, "little")
    sig_t = ref.pt_compress(t8) + bytes(32)
    lanes = [(mixed, msg, sig), (ident_nc, b"x", sig_t)]
    assert all(ref.verify_zip215(*lane) for lane in lanes)
    return lanes


def _sums(lanes, seed=5):
    """``(sum_a, sum_r, zs_sum, lanes_ok)`` as ``_rlc_sums`` defines them:
    per 4-bit window w, Σᵢ [nibble_w(zᵢhᵢ)](-Aᵢ) and Σᵢ [nibble_w(zᵢ)](-Rᵢ),
    then Σᵢ zᵢsᵢ mod L, with 128-bit zᵢ."""
    rng = np.random.default_rng(seed)
    sum_a = [ref.IDENTITY] * F.NW_A
    sum_r = [ref.IDENTITY] * F.NW_R
    zs, ok = 0, True
    for pub, msg, sig in lanes:
        a = ref.pt_decompress_zip215(pub)
        r = ref.pt_decompress_zip215(sig[:32])
        s = int.from_bytes(sig[32:], "little")
        if a is None or r is None or s >= L:
            ok = False
            continue
        z = int.from_bytes(rng.bytes(16), "little") | 1
        zh = z * ref.sc_reduce64(
            hashlib.sha512(sig[:32] + pub + msg).digest()) % L
        zs = (zs + z * s) % L
        for w in range(F.NW_A):
            sum_a[w] = ref.pt_add(
                sum_a[w], ref.pt_mul((zh >> 4 * w) & 15, ref.pt_neg(a)))
        for w in range(F.NW_R):
            sum_r[w] = ref.pt_add(
                sum_r[w], ref.pt_mul((z >> 4 * w) & 15, ref.pt_neg(r)))
    return sum_a, sum_r, zs, ok


def _flip(lane, surface):
    """One bit of one lane flipped, so that every point still decodes:
    what refutes the batch is the group equation, not ``lanes_ok``."""
    pub, msg, sig = lane
    if surface == "m":
        return pub, bytes([msg[0] ^ 1]) + msg[1:], sig
    if surface == "s":
        return pub, msg, sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
    enc = pub if surface == "a" else sig[:32]
    for bit in range(256):
        bad = bytearray(enc)
        bad[bit // 8] ^= 1 << (bit % 8)
        if ref.pt_decompress_zip215(bytes(bad)) is not None:
            break
    if surface == "a":
        return bytes(bad), msg, sig
    return pub, msg, bytes(bad) + sig[32:]


def _loosen(packed, seed=9):
    """The same elements in limbs no carry chain would leave: a multiple of
    the modulus added (p for the coordinates, L for the scalar), then
    carries pushed back DOWN at random, limbs up to 2^31 - 1."""
    rng = np.random.default_rng(seed)
    out = np.zeros(F.SHAPE, np.int64)
    for col in range(F.OK):
        v = sum(int(packed[i, col]) << (F.RADIX * i) for i in range(F.NLIMBS))
        v += int(rng.integers(0, 1 << 20)) * (L if col == F.ZS else P)
        limbs = [(v >> (F.RADIX * i)) & 8191 for i in range(F.NLIMBS - 1)]
        limbs.append(v >> (F.RADIX * (F.NLIMBS - 1)))
        for i in range(F.NLIMBS - 1, 0, -1):
            room = ((1 << 31) - 1 - limbs[i - 1]) >> F.RADIX
            m = int(rng.integers(0, min(limbs[i], room) + 1))
            limbs[i] -= m
            limbs[i - 1] += m << F.RADIX
        out[:, col] = limbs
    out[:, F.OK] = packed[:, F.OK]
    assert out.max() > 1 << 30 and out.min() >= 0
    return out.astype(np.int32)


EXPECTED = {"valid": True, "tamper_s": False, "tamper_r": False,
            "tamper_a": False, "tamper_m": False, "zip215_torsion": True,
            "zip215_torsion_tampered": False}


@pytest.fixture(scope="module")
def valid():
    return _signed(3, seed=1)


@pytest.fixture(scope="module")
def packed(valid):
    v = valid
    lanes = {
        "valid": v,
        "tamper_s": [v[0], _flip(v[1], "s"), v[2]],
        "tamper_r": [v[0], _flip(v[1], "r"), v[2]],
        "tamper_a": [_flip(v[0], "a"), v[1], v[2]],
        "tamper_m": [v[0], v[1], _flip(v[2], "m")],
        "zip215_torsion": _torsion_lanes() + v[:1],
        "zip215_torsion_tampered": _torsion_lanes() + [_flip(v[0], "s")],
    }
    assert set(lanes) == set(EXPECTED)
    out = {}
    for name, ls in lanes.items():
        sums = _sums(ls)
        assert sums[3], "every lane of these cases decodes"
        out[name] = F.pack(*sums)
    return out


# --------------------------------------------------------------- the folds

@pytest.mark.parametrize("name", list(EXPECTED))
def test_python_fold_gives_the_expected_verdict(packed, name):
    assert F.fold_python(packed[name]) is EXPECTED[name]


@needs_native
@pytest.mark.parametrize("name", list(EXPECTED))
def test_native_fold_agrees_with_the_python_fold(packed, name):
    assert native.rlc_fold(packed[name]) is EXPECTED[name]
    assert F.finish(packed[name]) == (EXPECTED[name], True)


@pytest.mark.parametrize("name", ["valid", "tamper_s", "zip215_torsion"])
def test_loose_limbs_read_as_the_same_elements(packed, name):
    """The chip does not freeze its sums: any non-negative int32 limbs are
    an element, read mod p (the scalar mod L)."""
    loose = _loosen(packed[name])
    assert F.fold_python(loose) is EXPECTED[name]
    if native.available():
        assert native.rlc_fold(loose) is EXPECTED[name]


@pytest.mark.parametrize("name", ["valid", "tamper_s"])
def test_lanes_not_ok_refutes_whatever_the_sums(packed, name):
    arr = packed[name].copy()
    arr[:, F.OK] = 0
    assert F.finish(arr)[0] is False


def test_an_undecodable_lane_refutes_through_lanes_ok(valid):
    """Such a lane drops out of every sum, so the equation alone holds."""
    bad = next(e for e in (bytes([i]) * 32 for i in range(256))
               if ref.pt_decompress_zip215(e) is None)
    sums = _sums(valid + [(bad, b"m", valid[0][2])])
    assert sums[3] is False and F.fold_python(F.pack(*sums[:3], True))
    assert F.finish(F.pack(*sums))[0] is False


def test_a_negative_limb_refutes_and_a_wrong_shape_raises(packed):
    arr = packed["valid"].copy()
    arr[3, 7] = -1
    assert F.finish(arr)[0] is False
    with pytest.raises(ValueError):
        F.finish(packed["valid"][:, :-1])
    assert F.finish(F.verdict(True))[0] and not F.finish(F.verdict(False))[0]


@pytest.mark.parametrize("name", ["valid", "tamper_r"])
def test_without_the_native_library_the_python_fold_answers(
        monkeypatch, packed, name):
    """The device route must not come to depend on g++."""
    monkeypatch.setattr(native, "_lib", lambda: None)
    assert native.rlc_fold(packed[name]) is None
    before = F._counter().value(impl="python")
    assert F.finish(packed[name]) == (EXPECTED[name], False)
    assert F._counter().value(impl="python") == before + 1


# ---------------------------------------------------- the seam that calls it

def _dispatch(monkeypatch, program_output, lanes=130):
    """One chunk of ``lanes`` (>= rlc_min_lanes) through the plain device
    route, the compiled programs replaced by stand-ins; the ring's records
    and the lane counter's routes it moved."""
    from cometbft_tpu.libs import metrics

    monkeypatch.setattr(B, "_compiled_rlc", lambda: lambda *a: program_output)
    monkeypatch.setattr(
        B, "_compiled_verify",
        lambda: lambda *a: np.zeros(np.asarray(a[0]).shape[0], bool))

    def routes():
        return {line.split(" ")[0]: float(line.split(" ")[1])
                for line in metrics.DEFAULT.collect().splitlines()
                if line.startswith("crypto_batch_lanes_total{")}

    before = routes()
    z = np.zeros((lanes, 32), np.uint8)
    tracing.clear()
    tracing.configure(enabled=True)
    try:
        out = B.device_verify_ed25519(z, z, z, np.zeros((lanes, 8), np.uint8),
                                      np.full((lanes,), 8, np.int64))
        recs = tracing.dump()
    finally:
        tracing.configure(enabled=False)
        tracing.clear()
    moved = {k for k, v in routes().items() if v != before.get(k, 0.0)}
    return out, recs, moved


@pytest.mark.parametrize("ok", [True, False])
def test_rlc_dispatch_records_finish_inside_readback(monkeypatch, ok):
    impl = "native" if native.available() else "python"
    before = F._counter().value(impl=impl)
    out, recs, moved = _dispatch(monkeypatch, F.verdict(ok))
    assert out.all() == ok
    launches = [r["attrs"]["kind"] for r in recs if r["name"] == "launch"]
    assert launches == (["rlc"] if ok else ["rlc", "verify"])
    (finish,) = [r for r in recs if r["name"] == "finish"]
    (readback,) = [r for r in recs if r["name"] == "readback"
                   and r["attrs"]["kind"] == "rlc"]
    assert finish["sub"] == "crypto.seam" and finish["parent"] == readback["id"]
    assert finish["attrs"] == {"native": native.available(), "ok": ok}
    assert readback["attrs"] == {"kind": "rlc", "ok": ok}
    assert readback["start_ns"] <= finish["start_ns"] \
        and finish["end_ns"] <= readback["end_ns"]
    assert F._counter().value(impl=impl) == before + 1
    # the fold is no route: a lane finished on the host is still a lane
    # the chip verified (benchmarks/counters.py counts any other route as
    # a lane off the device)
    assert all('route="device' in k for k in moved), moved
    assert (moved == {'crypto_batch_lanes_total{route="device_rlc"}'}) == ok


def test_rlc_dispatch_on_the_python_fold(monkeypatch):
    monkeypatch.setattr(native, "_lib", lambda: None)
    before = F._counter().value(impl="python")
    out, recs, _ = _dispatch(monkeypatch, F.verdict(True))
    assert out.all()
    (finish,) = [r for r in recs if r["name"] == "finish"]
    assert finish["attrs"] == {"native": False, "ok": True}
    assert F._counter().value(impl="python") == before + 1

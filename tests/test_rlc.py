"""RLC batch-verification kernel: one cofactored random-linear-
combination verdict per batch (ops/rlc.py), differential against the
per-lane kernel and the pure-Python oracle.  Reference contract:
curve25519-voi's batch verify (crypto/ed25519/ed25519.go:188-221) —
all-or-nothing verdict, per-lane fallback on reject."""

import hashlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.timeout(900)
slow = pytest.mark.slow     # whole RLC programs: minutes of CPU compile each

from cometbft_tpu.crypto import _ed25519_py as ref
from cometbft_tpu.crypto import _native_ed25519 as native
from cometbft_tpu.crypto import rlc_finish
from cometbft_tpu.ops import ed25519, rlc, scalar, fe
from cometbft_tpu.ops.group import Cached
from cometbft_tpu.testing import dense_signature_batch

L = scalar.L_INT


def _z(n, seed=3):
    rng = np.random.default_rng(seed)
    return rlc.host_rlc_coeffs(n, rng_bytes=rng.bytes(16 * n))


def _ok(sums) -> bool:
    """The verdict of an RLC program's output: the host folds its sums."""
    return rlc_finish.finish(np.asarray(sums))[0]


@slow
def test_mul_mod_l_and_sum_mod_l():
    rng = np.random.default_rng(11)
    xs = [int.from_bytes(rng.bytes(32), "little") for _ in range(24)]
    zs = [int.from_bytes(rng.bytes(16), "little") for _ in range(24)]
    x20 = np.stack([fe.limbs_from_int(v) for v in xs]).astype(np.int32)
    z10 = np.stack([fe.limbs_from_int(v)[:scalar.Z_NLIMBS] for v in zs]
                   ).astype(np.int32)
    prod = np.asarray(jax.jit(scalar.mul_mod_l)(x20, z10))
    for i in range(24):
        got = fe.int_from_limbs(prod[i])
        assert got < 2**256 and got % L == (xs[i] * zs[i]) % L, i
    tot = np.asarray(jax.jit(lambda p: scalar.sum_mod_l(p, axis=0))(prod))
    want = sum(fe.int_from_limbs(prod[i]) for i in range(24))
    got = fe.int_from_limbs(tot)
    assert got < 2**256 and got % L == want % L


@slow
def test_rlc_accepts_valid_batch():
    args, _ = dense_signature_batch(24, msg_len=80, seed=42)
    ok = jax.jit(rlc.verify_batch_rlc)(*args, _z(24))
    assert _ok(ok)


@slow
def test_rlc_sums_fold_the_same_natively_and_in_python():
    """The kernel's real output at the 16-lane bucket: loose limbs (the
    chip does not freeze), one transfer, and both folds agree on it."""
    args, _ = dense_signature_batch(16, msg_len=80, seed=47)
    pub, rb, sb, blocks, active = [np.asarray(a).copy() for a in args]
    fn = jax.jit(rlc.verify_batch_rlc)
    good = np.asarray(fn(pub, rb, sb, blocks, active, _z(16)))
    sb[3, 0] ^= 1
    bad = np.asarray(fn(pub, rb, sb, blocks, active, _z(16)))
    assert good.shape == rlc_finish.SHAPE and good.dtype == np.int32
    assert 0 <= good.min() and fe.MASK < good.max() <= fe.LIMB_MAX
    assert good[0, rlc_finish.OK] == 1 and bad[0, rlc_finish.OK] == 1
    assert rlc_finish.fold_python(good) and not rlc_finish.fold_python(bad)
    if native.available():
        assert native.rlc_fold(good) is True and native.rlc_fold(bad) is False


@slow
def test_rlc_sharded_returns_the_sums_of_the_whole_batch():
    """Two emulated devices, eight lanes each: the partial window sums
    combine into one replicated packed array that folds like the
    single-device program's (the limbs may differ: the fold reads them
    mod p)."""
    from cometbft_tpu.parallel import mesh as M

    args, _ = dense_signature_batch(16, msg_len=80, seed=48)
    pub, rb, sb, blocks, active = [np.asarray(a).copy() for a in args]
    fn = M.sharded_kernel("rlc", jax.devices()[:2])
    good = np.asarray(fn(pub, rb, sb, blocks, active, _z(16)))
    assert good.shape == rlc_finish.SHAPE and good.dtype == np.int32
    assert _ok(good) and rlc_finish.fold_python(good)
    rb[11, 3] ^= 4                              # a lane of the second shard
    assert not _ok(fn(pub, rb, sb, blocks, active, _z(16)))


@slow
def test_rlc_rejects_each_tamper_surface():
    args, _ = dense_signature_batch(24, msg_len=80, seed=43)
    pub, rb, sb, blocks, active = [np.asarray(a).copy() for a in args]
    fn = jax.jit(rlc.verify_batch_rlc)
    z = _z(24)
    for tamper in ("s", "r", "a", "m"):
        p2, r2, s2, b2 = pub.copy(), rb.copy(), sb.copy(), blocks.copy()
        if tamper == "s":
            s2[3, 0] ^= 1
        elif tamper == "r":
            r2[7, 31] ^= 0x40
        elif tamper == "a":
            p2[11, 5] ^= 2
        else:
            b2[13, 0, 0] ^= 1
        assert not _ok(fn(p2, r2, s2, b2, active, z)), tamper
    assert _ok(fn(pub, rb, sb, blocks, active, z))


@slow
def test_rlc_padding_lanes_do_not_contribute():
    """z = 0 lanes (padding) are excluded from the sums: corrupt a
    padding lane's signature and the batch verdict must stay True."""
    args, _ = dense_signature_batch(16, msg_len=80, seed=44)
    pub, rb, sb, blocks, active = [np.asarray(a).copy() for a in args]
    mask = np.ones(16, bool)
    mask[12:] = False                      # lanes 12..15 are padding
    z = rlc.host_rlc_coeffs(16, active_mask=mask,
                            rng_bytes=np.random.default_rng(1).bytes(256))
    assert (z[12:] == 0).all() and (z[:12] != 0).any(axis=1).all()
    sb[13, 0] ^= 1                         # tamper INSIDE the padding
    ok = jax.jit(rlc.verify_batch_rlc)(pub, rb, sb, blocks, active, z)
    assert _ok(ok)
    sb[5, 0] ^= 1                          # tamper an ACTIVE lane
    ok2 = jax.jit(rlc.verify_batch_rlc)(pub, rb, sb, blocks, active, z)
    assert not _ok(ok2)


@slow
def test_rlc_invalid_padding_lane_cannot_veto():
    """Regression (ADVICE r5): the per-lane ok_a/ok_r/ok_s bits must be
    masked to ACTIVE lanes before the all-reduce.  A padding lane whose
    pubkey/R fail decompression or whose s is non-canonical contributes
    identity to every sum (z = 0), but its ok bits are False — pre-fix
    that forced a whole-batch false reject."""
    args, _ = dense_signature_batch(16, msg_len=80, seed=45)
    pub, rb, sb, blocks, active = [np.asarray(a).copy() for a in args]
    mask = np.ones(16, bool)
    mask[12:] = False                      # lanes 12..15 are padding
    z = rlc.host_rlc_coeffs(16, active_mask=mask,
                            rng_bytes=np.random.default_rng(2).bytes(256))
    pub[12] = 0xFF                         # not a curve point: ok_a False
    rb[13] = 0xFF                          # not a curve point: ok_r False
    sb[14] = 0xFF                          # s >= L: ok_s False
    ok = jax.jit(rlc.verify_batch_rlc)(pub, rb, sb, blocks, active, z)
    assert _ok(ok), \
        "garbage padding lane vetoed a fully-valid batch"
    # the same garbage on an ACTIVE lane must still reject
    pub[3] = 0xFF
    ok2 = jax.jit(rlc.verify_batch_rlc)(pub, rb, sb, blocks, active, z)
    assert not _ok(ok2)


@slow
def test_rlc_gather_variant_matches():
    """The cached-table route gives the same verdicts through a valset
    table + scope indices (the steady-state commit path)."""
    n_vals, b = 12, 16
    args, items = dense_signature_batch(b, msg_len=80, seed=45,
                                        n_keys=n_vals)
    pub, rb, sb, blocks, active = [np.asarray(a) for a in args]
    # valset = the distinct keys; scope = each lane's validator index
    uniq, scope = np.unique(pub, axis=0, return_inverse=True)
    tab, ok_a = jax.jit(ed25519.prepare_pubkey_tables)(uniq.astype(np.int32))
    fn = jax.jit(rlc.verify_batch_rlc_gather)
    z = _z(b)
    ok = fn(tab, ok_a, scope.astype(np.int32), rb, sb, blocks, active, z)
    assert _ok(ok)
    sb2 = np.asarray(sb).copy()
    sb2[4, 2] ^= 8
    ok2 = fn(tab, ok_a, scope.astype(np.int32), rb, sb2, blocks, active, z)
    assert not _ok(ok2)


@slow
def test_rlc_accepts_zip215_torsion_edge_cases():
    """Lanes whose defect is pure torsion (mixed-order A, small-order R,
    non-canonical identity A) are ZIP-215-valid and must pass the
    cofactored RLC equation too."""
    rng = np.random.default_rng(46)
    pubs, sigs, msgs = [], [], []

    # mixed-order pubkey: A' + T8, signature over the mixed encoding
    def torsion8():
        while True:
            enc = rng.bytes(32)
            pt = ref.pt_decompress_zip215(enc)
            if pt is None:
                continue
            t = ref.pt_mul(ref.L, pt)
            if not ref.pt_equal(t, ref.IDENTITY) and \
               not ref.pt_equal(ref.pt_mul(4, t), ref.IDENTITY):
                return t

    t8 = torsion8()
    seed2 = rng.bytes(32)
    h0 = hashlib.sha512(seed2).digest()
    a_sc = ref._clamp(h0[:32])
    prefix = h0[32:]
    mixed = ref.pt_compress(ref.pt_add(ref.pt_mul(a_sc, ref.BASE), t8))
    m3 = rng.bytes(50)
    r_sc = ref.sc_reduce64(hashlib.sha512(prefix + m3).digest())
    r_enc = ref.pt_compress(ref.pt_mul(r_sc, ref.BASE))
    k_sc = ref.sc_reduce64(hashlib.sha512(r_enc + mixed + m3).digest())
    sig3 = r_enc + ((r_sc + k_sc * a_sc) % L).to_bytes(32, "little")
    assert ref.verify_zip215(mixed, m3, sig3)
    pubs.append(mixed); sigs.append(sig3); msgs.append(m3)

    # small-order R with non-canonical identity A: S=0, R=T8
    ident_nc = (1 + fe.P_INT).to_bytes(32, "little")
    sig_t = ref.pt_compress(t8) + (0).to_bytes(32, "little")
    assert ref.verify_zip215(ident_nc, b"x", sig_t)
    pubs.append(ident_nc); sigs.append(sig_t); msgs.append(b"x")

    # fill with ordinary valid lanes to a padded width of 4
    while len(pubs) < 4:
        sd = rng.bytes(32)
        m = rng.bytes(50)
        pubs.append(ref.public_key_from_seed(sd))
        sigs.append(ref.sign(sd, m)); msgs.append(m)

    from cometbft_tpu.ops import sha512
    b = len(pubs)
    hin = np.zeros((b, 64 + 50), np.uint8)
    lens = np.zeros(b, np.int64)
    for i, (p, s, m) in enumerate(zip(pubs, sigs, msgs)):
        full = s[:32] + p + m
        hin[i, :len(full)] = np.frombuffer(full, np.uint8)
        lens[i] = len(full)
    blocks, active = sha512.host_pad(hin, lens, 2)
    arr = lambda bs: np.stack(
        [np.frombuffer(x, np.uint8) for x in bs]).astype(np.int32)
    ok = jax.jit(rlc.verify_batch_rlc)(
        arr(pubs), arr([s[:32] for s in sigs]),
        arr([s[32:] for s in sigs]), blocks, active, _z(b))
    assert _ok(ok)


def _gathered_sheet(tab, digits):
    """The construction the selection replaced, kept as the oracle:
    ``take_along_axis`` over tables transposed to (B, 16, 20), then back
    to the lane-major (20, B*NW) sheet."""
    def one(c):
        ct = jnp.transpose(c, (2, 0, 1))
        ent = jnp.take_along_axis(ct, digits[:, :, None], axis=1)
        return jnp.transpose(ent, (2, 0, 1)).reshape(c.shape[1], -1)

    return Cached(*[one(c) for c in tab])


def _selected_sheet(tab, digits):
    sheet, nw = rlc._window_sheet(tab, digits)
    assert nw == digits.shape[1]
    return sheet


def _sheet_inputs(width, nw, seed):
    """Random loose-limb tables (16, 20, width) and digits (width, nw)
    in which every one of the 16 values occurs."""
    rng = np.random.default_rng(seed)
    tab = Cached(*[rng.integers(0, fe.MASK + 1, (16, fe.NLIMBS, width),
                                dtype=np.int32) for _ in Cached._fields])
    digits = rng.permutation(np.arange(width * nw) % 16)
    return tab, digits.reshape(width, nw).astype(np.int32)


@pytest.mark.parametrize("nw", [64, 32], ids=["A", "R"])
@pytest.mark.parametrize("width", [1, 7, 256, 4096])
def test_rlc_window_sheet_selects_what_the_gather_took(width, nw):
    """Each (lane, window) entry of the selected sheet is the table row
    its digit names, in the lane-major order the tree halves."""
    tab, digits = _sheet_inputs(width, nw, seed=width + nw)
    got = jax.jit(_selected_sheet)(tab, digits)
    want = jax.jit(_gathered_sheet)(tab, digits)
    for g, w in zip(got, want):
        assert g.shape == (fe.NLIMBS, width * nw)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("width,nw", [(1, 64), (7, 64), (256, 64),
                                      (4096, 64), (1, 32), (7, 32),
                                      (256, 32)])
def test_rlc_window_sums_match_the_gathered_tree(width, nw):
    """The per-window sums after the tree are the same cached
    coordinates whichever way the sheet was built, identity padding of
    a width that is not a power of two included."""
    tab, digits = _sheet_inputs(width, nw, seed=3 * width + nw)
    tree = jax.jit(rlc._tree_reduce_lanes, static_argnums=1)
    got = tree(jax.jit(_selected_sheet)(tab, digits), nw)
    want = tree(jax.jit(_gathered_sheet)(tab, digits), nw)
    for g, w in zip(got, want):
        assert g.shape == (fe.NLIMBS, nw)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

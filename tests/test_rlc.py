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
from cometbft_tpu.ops.group import Cached, Tree
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

    return type(tab)(*[one(c) for c in tab])


def _selected_sheet(tab, digits):
    sheet, nw = rlc._window_sheet(tab, digits)
    assert nw == digits.shape[1]
    return sheet


def _sheet_inputs(width, nw, seed):
    """Random loose-limb tree-form tables (16, 20, width) and digits
    (width, nw) in which every one of the 16 values occurs."""
    rng = np.random.default_rng(seed)
    tab = Tree(*[rng.integers(0, fe.MASK + 1, (16, fe.NLIMBS, width),
                              dtype=np.int32) for _ in Tree._fields])
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
    coordinates whichever way the tree-form sheet was built, identity padding of
    a width that is not a power of two included."""
    tab, digits = _sheet_inputs(width, nw, seed=3 * width + nw)
    tree = jax.jit(rlc._tree_reduce_lanes, static_argnums=1)
    got = tree(jax.jit(_selected_sheet)(tab, digits), nw)
    want = tree(jax.jit(_gathered_sheet)(tab, digits), nw)
    for g, w in zip(got, want):
        assert g.shape == (fe.NLIMBS, nw)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# --- the tree form of the lane tree (ops/group.py add_tree) -------------

P = fe.P_INT
_gl = ed25519._g


def _val(limbs) -> int:
    return fe.int_from_limbs(limbs) % P


def _cached_affine(ypx, ymx, z2) -> tuple[int, int]:
    """Cached coordinates (ints) -> affine (x, y): x = (ypx - ymx)/z2,
    y = (ypx + ymx)/z2 (the halves of X, Y and Z cancel)."""
    zi = pow(z2, P - 2, P)
    return (ypx - ymx) * zi % P, (ypx + ymx) * zi % P


def _same_point(c1, c2) -> bool:
    """Two cached points (ints) projectively equal: X·Z' - X'·Z = 0 and
    the same for Y, on frozen values; and each one's 2dT consistent
    with its X, Y, Z (2dT·2Z = 2d·(2X)(2Y)/2)."""
    (p1, m1, z1, t1), (p2, m2, z2, t2) = c1, c2
    ok = ((p1 - m1) * z2 - (p2 - m2) * z1) % P == 0
    ok &= ((p1 + m1) * z2 - (p2 + m2) * z1) % P == 0
    for p_, m_, z_, t_ in (c1, c2):
        ok &= z_ % P != 0
        ok &= (t_ * z_ - fe.D_INT * (p_ - m_) * (p_ + m_)) % P == 0
    return bool(ok)


def _ext_limbs(pts, rng) -> "Ext":
    """Affine-equivalent extended points (ints) -> limb-major (20, n)
    Ext with a random projective scale per lane."""
    from cometbft_tpu.ops.group import Ext

    cols = [[], [], [], []]
    for x, y, z, t in pts:
        zi = pow(z, P - 2, P)
        ax, ay = x * zi % P, y * zi % P
        s_ = int(rng.integers(1, 2**62))
        for k, v in enumerate((ax * s_, ay * s_, s_, ax * ay % P * s_)):
            cols[k].append(fe.limbs_from_int(v % P))
    return Ext(*[np.stack(c, axis=1).astype(np.int32) for c in cols])


def _torsion8():
    """A point of order 8 (decoded from a fixed encoding's multiple)."""
    for i in range(1000):
        pt = ref.pt_decompress_zip215(hashlib.sha256(bytes([i])).digest())
        if pt is None:
            continue
        t = ref.pt_mul(ref.L, pt)
        if not ref.pt_equal(ref.pt_mul(4, t), ref.IDENTITY):
            return t
    raise AssertionError("no order-8 point found")


def _node_pairs():
    """(case, P, Q) operand pairs of one group-level batch."""
    rng = np.random.default_rng(41)
    rand = [ref.pt_mul(int(rng.integers(1, 2**62)), ref.BASE)
            for _ in range(6)]
    t8 = _torsion8()
    two = (0, P - 1, 1, 0)                      # the point of order 2
    return [("random", rand[0], rand[1]), ("random", rand[2], rand[3]),
            ("double", rand[4], rand[4]), ("double", t8, t8),
            ("inverse", rand[5], ref.pt_neg(rand[5])),
            ("inverse", t8, ref.pt_neg(t8)),
            ("identity", ref.IDENTITY, rand[0]),
            ("identity", ref.IDENTITY, ref.IDENTITY),
            ("small_order", t8, rand[1]), ("small_order", two, rand[2]),
            ("small_order", two, t8)]


@pytest.fixture(scope="module")
def node_results():
    pairs = _node_pairs()
    rng = np.random.default_rng(42)
    p_ext = _ext_limbs([p for _, p, _ in pairs], rng)
    q_ext = _ext_limbs([q for _, _, q in pairs], rng)

    def both(p, q):
        cp, cq = _gl.cache(p), _gl.cache(q)
        old = _gl.add_cc(cp, cq)
        new = _gl.tree_to_cached(_gl.add_tree(_gl.cached_to_tree(cp),
                                              _gl.cached_to_tree(cq)))
        return old, new

    old, new = jax.jit(both)(p_ext, q_ext)
    return pairs, [np.asarray(c) for c in old], [np.asarray(c) for c in new]


def test_tree_form_constant_is_a_square_root_of_2d():
    k = _gl.K
    assert k * k % P == 2 * fe.D_INT % P
    assert k % 2 == 0 and 0 < k < P


@pytest.mark.parametrize("case", ["random", "double", "inverse",
                                  "identity", "small_order"])
def test_add_tree_is_add_cc_as_a_group_element(node_results, case):
    """``add_tree`` between the two conversions gives the point
    ``add_cc`` gives and the affine sum of the pure-Python oracle,
    projectively (the limbs differ: the forms scale differently)."""
    pairs, old, new = node_results
    lanes = [i for i, (c, _, _) in enumerate(pairs) if c == case]
    assert lanes
    for i in lanes:
        _, p, q = pairs[i]
        o = tuple(_val(c[:, i]) for c in old)
        n = tuple(_val(c[:, i]) for c in new)
        assert _same_point(o, n), (case, i)
        want = ref.pt_add(p, q)
        zi = pow(want[2], P - 2, P)
        assert _cached_affine(*n[:3]) == (want[0] * zi % P,
                                          want[1] * zi % P), (case, i)
        assert all(0 <= c[:, i].min() and c[:, i].max() <= fe.LIMB_MAX
                   for c in new)


def _cc_tree(ents: Cached, nw: int) -> Cached:
    """The ``add_cc`` lane tree the tree form replaced, kept as the
    oracle: the cached sheet halved with identity padding."""
    w = ents.ypx.shape[1] // nw
    p2 = 1 << (w - 1).bit_length()
    if p2 != w:
        idc = _gl.cache(_gl.identity(((p2 - w) * nw,)))
        ents = Cached(*[jnp.concatenate([c, i_c], axis=1)
                        for c, i_c in zip(ents, idc)])
        w = p2
    while w > 1:
        h = (w // 2) * nw
        ents = _gl.add_cc(Cached(*[c[:, :h] for c in ents]),
                          Cached(*[c[:, h:] for c in ents]))
        w //= 2
    return ents


_TREE_LANES = 1024


@pytest.fixture(scope="module")
def real_tables():
    """Cached [j](-A) tables (16, 20, 1024) of real keys
    (``prepare_pubkey_tables``: decompression + ``_build_neg_a_table``)
    and each lane's scalar mod L: 8 keys [a]B, the identity, a point of
    order 8 and a mixed-order key [a]B + T8 (their torsion dies under
    the fold's cofactor)."""
    rng = np.random.default_rng(44)
    t8 = _torsion8()
    pool = []
    for _ in range(8):
        a = int(rng.integers(1, 2**62)) * int(rng.integers(1, 2**62))
        pool.append((ref.pt_compress(ref.pt_mul(a, ref.BASE)), a))
    a = int(rng.integers(1, 2**62))
    pool += [(ref.pt_compress(ref.IDENTITY), 0), (ref.pt_compress(t8), 0),
             (ref.pt_compress(ref.pt_add(ref.pt_mul(a, ref.BASE), t8)), a)]
    pick = rng.integers(0, len(pool), _TREE_LANES)
    pick[:len(pool)] = np.arange(len(pool))
    pub = np.stack([np.frombuffer(pool[k][0], np.uint8) for k in pick]
                   ).astype(np.int32)
    tab, ok = jax.jit(ed25519.prepare_pubkey_tables)(pub)
    assert np.asarray(ok).all()
    return Cached(*[np.asarray(c) for c in tab]), [pool[k][1] for k in pick]


def _digits(rng, width, nw):
    """Random digits, every fourth lane all zero (a z = 0 lane)."""
    d = rng.integers(0, 16, (width, nw)).astype(np.int32)
    d[::4] = 0
    return d


@pytest.mark.parametrize("width", [1, 7, 256, 1024])
def test_rlc_tree_form_sums_match_the_add_cc_tree(real_tables, width):
    """On real tables the tree-form lane tree gives, window by window,
    the point the ``add_cc`` tree gives, identity padding and z = 0
    lanes included; packed with the Σzs that balances them, both fold
    to True natively and in Python, and to False with Σzs off by one.
    The A sheet's 64 windows only (the R sums are the identity): the
    tree does not depend on the window count, and each tree costs a
    compile."""
    full_tab, scal = real_tables
    tab = Cached(*[c[:, :, :width] for c in full_tab])
    dig = _digits(np.random.default_rng(width), width, 64)

    def new(tab, dig):
        tt = rlc._table_to_tree(tab)
        return rlc._tree_reduce_lanes(*rlc._window_sheet(tt, dig))

    def old(tab, dig):
        return _cc_tree(*rlc._window_sheet(tab, dig))

    got = [np.asarray(c) for c in jax.jit(new)(tab, dig)]
    want = [np.asarray(c) for c in jax.jit(old)(tab, dig)]
    for c in got:
        assert c.shape == (fe.NLIMBS, 64)
        assert 0 <= c.min() and c.max() <= fe.LIMB_MAX
    for col in range(64):
        assert _same_point(tuple(_val(c[:, col]) for c in got),
                           tuple(_val(c[:, col]) for c in want)), col

    ident = _gl.cache(_gl.identity((32,)))
    zs = sum(a * sum(int(v) << (4 * k) for k, v in enumerate(row))
             for row, a in zip(dig, scal))
    for sums in (got, want):
        for off, expect in ((0, True), (1, False)):
            packed = np.asarray(rlc._pack_sums(
                Cached(*sums), ident,
                jnp.asarray(fe.limbs_from_int((zs + off) % L)),
                jnp.asarray(True)))
            assert bool(rlc_finish.fold_python(packed)) == expect
            if native.available():
                assert native.rlc_fold(packed) is expect
            assert bool(rlc_finish.finish(packed)[0]) == expect

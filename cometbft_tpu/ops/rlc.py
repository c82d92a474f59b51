"""Random-linear-combination (RLC) batch verification on device.

The round-4 profile put 76% of device time in the per-lane Straus ladder
(~256 doublings + 128 table additions per signature); this kernel is the
structural answer (docs/explanation/tpu-kernel.md "what's next"): verify
the WHOLE batch with one cofactored random-linear-combination equation

    [8]( [Σᵢ zᵢsᵢ]B  -  Σᵢ [zᵢhᵢ](Aᵢ)  -  Σᵢ [zᵢ](Rᵢ) )  ==  identity

with independent 128-bit coefficients zᵢ — exactly what the native CPU
path (``native/ed25519.cpp``) and the reference's curve25519-voi batch
verifier do on host (``crypto/ed25519/ed25519.go:188-221``), redesigned
for the TPU's vector units:

- The doublings are paid ONCE for the whole batch (64 windows x 4),
  not once per lane: the MSB-first ladder walks 4-bit windows of all
  scalars simultaneously.
- Per window, each lane contributes one selected table entry
  ([digit](-Aᵢ) from the cached per-validator tables, [digit](-Rᵢ)
  from per-batch tables), and the lane contributions collapse through a
  **binary tree of cached-coordinate additions** (``group.add_cc``):
  log2(B) levels of halving-width vector adds — total group-op work
  ~B per window instead of ~6B for the per-lane ladder, and every
  level is a dense vector op over the limb-major lane axis.
- The B term needs no tree: Σzᵢsᵢ mod L is a cheap mod-L sum, one
  scalar for the whole batch.
- zᵢ is 128 bits, so the R tree only runs for the lower 32 windows.
- The programs END at the per-window sums: what is left, the MSB-first
  fold ``Σ_w 16^w·S_w`` with its 252 doublings one after another, is one
  lane wide whatever the batch (62 ms a dispatch on a v5e, at 256 lanes
  or at 4,096), so the host does it in ~0.1 ms with its 64-bit field
  arithmetic (``crypto/rlc_finish.py``).  The chip returns ONE int32
  array: the window sums, Σzᵢsᵢ and the lane-ok bit.

Soundness: per-lane defects Dᵢ = sᵢB - hᵢAᵢ - Rᵢ of VALID signatures
are torsion (killed by the cofactor), so any zᵢ accept; a batch with a
non-torsion defect survives only if Σzᵢ·Dᵢ lands in torsion, which the
independent 128-bit zᵢ bound to probability ~2⁻¹²⁸.  Scalars need only
be correct mod L and < 2^256 (the cofactored-equation trick of
``ops/scalar.py``): [kL]P is torsion for every curve point P.  The RLC
verdict is all-or-nothing; on reject the dispatcher falls back to the
per-lane kernel (``ops/ed25519.py``) to localize failures, mirroring
the native CPU path's fallback contract.  Padding lanes carry zᵢ = 0
and contribute the identity to every sum.

Layout follows the promoted limb-major convention: byte matrices stay
batch-major at the interface, curve arithmetic runs over (20, B).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..crypto import rlc_finish
from . import fe, scalar, sha512
from .ed25519 import _build_neg_a_table, _g
from .group import Cached

__all__ = ["verify_batch_rlc", "verify_batch_rlc_gather",
           "host_rlc_coeffs"]

_RADIX, _MASK = fe.RADIX, fe.MASK


def host_rlc_coeffs(n: int, active_mask=None, rng_bytes=None) -> np.ndarray:
    """(n, 10) int32 13-bit limbs of independent 128-bit coefficients.

    Inactive (padding) lanes get z = 0 so they drop out of every sum;
    active all-zero rows (probability 2⁻¹²⁸, but a z=0 lane would verify
    unchecked) are bumped to 1.  ``rng_bytes`` injects determinism for
    tests; production uses the OS CSPRNG — the coefficients must be
    unpredictable to an adversary who chose the signatures."""
    if rng_bytes is None:
        import secrets

        rng_bytes = secrets.token_bytes(16 * n)
    raw = np.frombuffer(rng_bytes, np.uint8).reshape(n, 16)
    limbs = np.zeros((n, scalar.Z_NLIMBS), np.int64)
    for i in range(scalar.Z_NLIMBS):
        bit0 = _RADIX * i
        acc = np.zeros((n,), np.int64)
        for j in range(bit0 // 8, min((bit0 + _RADIX + 7) // 8, 16)):
            shift = 8 * j - bit0
            b = raw[:, j].astype(np.int64)
            acc += (b << shift) if shift >= 0 else (b >> -shift)
        limbs[:, i] = acc & _MASK
    if active_mask is not None:
        limbs[~np.asarray(active_mask, bool)] = 0
        zero = (limbs.sum(axis=1) == 0) & np.asarray(active_mask, bool)
    else:
        zero = limbs.sum(axis=1) == 0
    limbs[zero, 0] = 1
    return limbs.astype(np.int32)


@jax.named_scope("rlc_sheet")
def _window_sheet(tab: Cached, digits) -> tuple:
    """Per-lane table (16, 20, B) + per-window digits (B, NW) ->
    cached entries (20, B*NW), LANE-MAJOR: column b*NW + w holds lane
    b's table row for window w, so the whole (window x lane) sheet is
    one flat 2-D axis whose tree halving pairs lane b with lane b + B/2
    for every window simultaneously.

    A lane's entry is one of its 16 rows, so it is SELECTED, not
    gathered: Σ_j [digit == j]·tab[j] over all windows at once, one
    elementwise-and-reduce that XLA fuses into a single pass over the
    sheet (no gather, no transposed table, no 16-fold intermediate in
    HBM).  On a v5e a gather of B·NW 80-byte slices costs over ten times
    this pass (``PERF.md`` §5)."""
    nw = digits.shape[1]
    hit = digits.reshape(-1) == jnp.arange(16, dtype=digits.dtype)[:, None]

    def one(c):
        rows = jnp.repeat(c, nw, axis=2)         # (16, 20, B*NW), fused
        return jnp.sum(jnp.where(hit[:, None], rows, 0), axis=0)

    return Cached(*[one(c) for c in tab]), nw


@jax.named_scope("rlc_tree")
def _tree_reduce_lanes(ents: Cached, nw: int) -> Cached:
    """Binary tree of cached-coordinate additions over the lane-major
    (20, W*NW) sheet -> per-window sums (20, NW).

    All windows reduce simultaneously: the tree compiles ONCE for the
    whole verdict (log2(W) add_cc levels) instead of once per window
    body, and every level is a (20, (W/2)*NW)-wide vector op — the
    narrow tail of a per-window tree gets NW-fold occupancy here.
    Lanes pad to a power of two with identity entries (z = 0 padding
    lanes are already identity contributors, but arbitrary batch sizes
    appear in tests)."""
    w = ents.ypx.shape[1] // nw
    p2 = 1 << (w - 1).bit_length()
    if p2 != w:
        idc = _g.cache(_g.identity(((p2 - w) * nw,)))
        ents = Cached(*[jnp.concatenate([c, i_c], axis=1)
                        for c, i_c in zip(ents, idc)])
        w = p2
    while w > 1:
        h = (w // 2) * nw
        left = Cached(*[c[:, :h] for c in ents])
        right = Cached(*[c[:, h:] for c in ents])
        ents = _g.add_cc(left, right)
        w //= 2
    return ents                                   # (20, NW)


@jax.named_scope("rlc_sums")
def _rlc_sums(neg_a_tab, ok_a, rb, sb, blocks, active, z10):
    """Per-window lane sums + the B-term scalar sum + the lane-ok
    verdict, for one (shard of a) batch.  Everything here is local to
    the lanes it sees — the sharded dispatch runs this per device and
    combines the outputs, the single-device path packs them for the
    host's fold."""
    r_pt, ok_r = _g.decompress_zip215(jnp.transpose(rb))
    neg_r_tab = _build_neg_a_table(_g.neg_ext(r_pt))

    s20 = scalar.bytes32_to_limbs(sb)
    ok_s = scalar.lt_l(s20)
    h20 = scalar.reduce512(sha512.sha512_blocks(blocks, active))

    zh = scalar.mul_mod_l(h20, z10)              # (B, 20)
    zs_sum = scalar.sum_mod_l(scalar.mul_mod_l(s20, z10), axis=0)  # (20,)

    zh_dig = scalar.nibbles(zh)                  # (B, 64)
    z_dig = scalar.nibbles_k(z10, scalar.Z_NLIMBS, 32)   # (B, 32)

    # all 64 (resp. 32) per-window lane sums at once: one selection +
    # one shared tree — per-window sums (20, NW)
    sum_a = _tree_reduce_lanes(*_window_sheet(neg_a_tab, zh_dig))
    sum_r = _tree_reduce_lanes(*_window_sheet(neg_r_tab, z_dig))

    # ok bits only bind on ACTIVE lanes (z != 0): padding lanes repeat
    # lane 0's bytes on some callers but carry arbitrary garbage on
    # others, and a garbage padding lane must never veto the batch (its
    # z = 0 already removes it from every sum).  Active all-zero z rows
    # are bumped to 1 host-side, so z != 0 is exactly the active mask.
    active_lane = jnp.any(z10 != 0, axis=1)
    lanes_ok = jnp.all((ok_a & ok_r & ok_s) | ~active_lane)
    return sum_a, sum_r, zs_sum, lanes_ok


def _pack_sums(sum_a, sum_r, zs_sum, lanes_ok):
    """One int32 (20, 386) array, ``crypto/rlc_finish.py``'s layout: the
    cached coordinates of the window sums side by side, then ``zs_sum``
    and ``lanes_ok`` as a column each.  The limbs stay in ``fe_lm``'s
    loose form: the host's fold takes any non-negative limbs, so the
    chip spends nothing on a freeze."""
    ok_col = jnp.broadcast_to(lanes_ok.astype(jnp.int32), (fe.NLIMBS, 1))
    out = jnp.concatenate(
        [*sum_a, *sum_r, zs_sum.astype(jnp.int32)[:, None], ok_col], axis=1)
    assert out.shape == rlc_finish.SHAPE        # one layout, stated there
    return out


def _rlc_core(neg_a_tab, ok_a, rb, sb, blocks, active, z10):
    """Shared RLC sums over per-lane [j](-A) cached tables, packed."""
    return _pack_sums(*_rlc_sums(neg_a_tab, ok_a, rb, sb, blocks, active,
                                 z10))


def verify_batch_rlc(pub, rb, sb, blocks, active, z10):
    """The RLC sums of a padded batch, for the host to finish.

    pub/rb/sb (B, 32) int32 bytes; blocks/active as
    ``ed25519.verify_padded``; z10 (B, 10) int32 coefficient limbs
    (``host_rlc_coeffs`` — 0 on padding lanes).  Returns the packed
    (20, 386) int32 array of :func:`_pack_sums`;
    ``crypto.rlc_finish.finish`` turns it into the verdict: True iff
    every active lane verifies (up to the 2⁻¹²⁸ RLC bound).
    """
    from .ed25519 import prepare_pubkey_tables

    neg_a_tab, ok_a = prepare_pubkey_tables(pub)
    return _rlc_core(neg_a_tab, ok_a, rb, sb, blocks, active, z10)


def verify_batch_rlc_gather(tab, ok_a, idx, rb, sb, blocks, active, z10):
    """RLC sums through a CACHED whole-validator-set table
    (``ed25519.prepare_pubkey_tables`` output): the steady-state commit
    path — A decompression and table building amortize across commits,
    the doublings amortize across lanes and leave the chip, so
    per-commit device work is the gathers and two trees."""
    lane_tab = Cached(*[jnp.take(c, idx, axis=2) for c in tab])
    lane_ok = jnp.take(ok_a, idx, axis=0)
    return _rlc_core(lane_tab, lane_ok, rb, sb, blocks, active, z10)


def make_verify_batch_rlc_sharded(mesh, gather: bool = False):
    """RLC sums sharded over the lane axis of ``mesh``.

    The tree reduce is group addition, not an elementwise sum, so the
    lane tree cannot simply ``psum``: instead each device runs
    :func:`_rlc_sums` on its own lane shard (decompression, hashing,
    gathers and the local reduction tree all stay collective-free), and
    only the per-device PARTIAL per-window sums — cached coordinates,
    (20, 96) per device — cross the interconnect, where a replicated
    chain of ``add_cc`` folds them into the one packed array the host
    finishes.
    Cross-chip traffic is therefore O(windows) points per verdict,
    independent of batch size — the reduction the single-device gate at
    ``crypto/batch.py`` used to forbid.

    ``gather=True`` builds the cached-valset-table variant (table and ok
    mask replicated, per-lane args sharded).  Returns an UNJITTED
    callable with the same signature as the corresponding single-device
    entry; callers jit it once per mesh.
    """
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    lane = P(axis)
    ndev = int(np.asarray(mesh.devices).size)

    def _local_sums(tab_or_pub, ok_or_none, *args):
        if gather:
            idx, rb, sb, blocks, active, z10 = args
            lane_tab = Cached(*[jnp.take(c, idx, axis=2)
                                for c in tab_or_pub])
            lane_ok = jnp.take(ok_or_none, idx, axis=0)
        else:
            from .ed25519 import prepare_pubkey_tables

            rb, sb, blocks, active, z10 = args
            lane_tab, lane_ok = prepare_pubkey_tables(tab_or_pub)
        sum_a, sum_r, zs, ok = _rlc_sums(lane_tab, lane_ok, rb, sb,
                                         blocks, active, z10)
        return (tuple(c[None] for c in sum_a),
                tuple(c[None] for c in sum_r), zs[None], ok[None])

    dev3 = P(axis, None, None)
    out_specs = ((dev3,) * len(Cached._fields),
                 (dev3,) * len(Cached._fields), P(axis, None),
                 P(axis))
    if gather:
        in_specs = ((P(),) * len(Cached._fields), P(),
                    lane, lane, lane, lane, lane, lane)
    else:
        in_specs = (lane, lane, lane, lane, lane, lane)
        # signature folds (pub, rb, ...) into (tab_or_pub, *args): drop
        # the unused ok slot by wrapping below
    # check_vma off: the kernels' scans start from constant carries
    # (SHA IVs, identity points) that the per-shard body makes varying,
    # which jax >= 0.7's varying-axes typing refuses; every output is
    # declared device-stacked in out_specs, so nothing needs inferring
    smapped = jax.shard_map(
        (lambda tab, ok, *a: _local_sums(tab, ok, *a)) if gather
        else (lambda pub, *a: _local_sums(pub, None, *a)),
        mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)

    def _combine(sa_stk, sr_stk, zs_stk, ok_stk):
        sum_a = Cached(*[c[0] for c in sa_stk])
        sum_r = Cached(*[c[0] for c in sr_stk])
        for d in range(1, ndev):
            sum_a = _g.add_cc(sum_a, Cached(*[c[d] for c in sa_stk]))
            sum_r = _g.add_cc(sum_r, Cached(*[c[d] for c in sr_stk]))
        zs_sum = scalar.sum_mod_l(zs_stk, axis=0)
        return _pack_sums(sum_a, sum_r, zs_sum, jnp.all(ok_stk))

    if gather:
        def fn(tab, ok_a, idx, rb, sb, blocks, active, z10):
            return _combine(*smapped(tuple(tab), ok_a, idx, rb, sb,
                                     blocks, active, z10))
    else:
        def fn(pub, rb, sb, blocks, active, z10):
            return _combine(*smapped(pub, rb, sb, blocks, active, z10))
    return fn

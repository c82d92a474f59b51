"""RFC-6962-style merkle trees and proofs.

Reference: ``crypto/merkle/`` — leaf/inner domain separation (0x00/0x01
prefixes), split at the largest power of two strictly less than n, empty
tree hashes to SHA-256 of the empty string.  Used for block-part sets, tx
hashes, header field hashing, validator-set hashing and evidence.

Construction is LEVEL-ORDER for anything beyond tiny trees: pair adjacent
nodes left to right, promote an odd tail node unchanged — provably the
same tree as the recursive largest-power-of-two split (pinned by golden
tests), but buildable one whole level at a time.  That shape admits three
interchangeable level engines behind a size-based dispatch:

- hashlib loop           — tiny trees, and the no-dependency fallback;
- native C++ (ctypes)    — ``kv_merkle_levels``/``kv_merkle_root`` in
  ``native/kvstore.cpp``: the host fast path (one C call for the whole
  tree);
- batched JAX kernel     — ``ops/sha256.py``: one jitted dispatch hashes
  an entire level, engaged for large trees when an accelerator is live
  (measured ~7x SLOWER than the hashlib loop on host CPU, so a
  ``JAX_PLATFORMS=cpu`` box falls back to the native/hashlib engines).

Every engine retains the per-level node cache, so
:func:`proofs_from_byte_slices` assembles ALL aunt paths by indexing into
the cached levels — zero re-hashing, and the gather is vectorized
(numpy sibling indices + one ``itemgetter`` sweep per level) instead of
the old recursive per-node dict merging.
"""

from __future__ import annotations

import hashlib
import os
from dataclasses import dataclass
from itertools import count, repeat
from operator import itemgetter
from typing import NamedTuple

LEAF_PREFIX = b"\x00"
INNER_PREFIX = b"\x01"


def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def leaf_hash(leaf: bytes) -> bytes:
    return _sha(LEAF_PREFIX + leaf)


def inner_hash(left: bytes, right: bytes) -> bytes:
    return _sha(INNER_PREFIX + left + right)


def _split_point(n: int) -> int:
    """Largest power of two strictly less than n (n >= 2)."""
    k = 1
    while k * 2 < n:
        k *= 2
    return k


def hash_from_byte_slices(items: list[bytes]) -> bytes:
    n = len(items)
    if n == 0:
        return _sha(b"")
    if n == 1:
        return leaf_hash(items[0])
    k = _split_point(n)
    return inner_hash(hash_from_byte_slices(items[:k]),
                      hash_from_byte_slices(items[k:]))


_NATIVE_ROOT = None


def _native_root_fn():
    """ctypes binding for the C++ RFC-6962 tree (native/kvstore.cpp), or
    None when the native build is unavailable.  Binds both the root-only
    entry and the level-cache builder the proof path uses."""
    global _NATIVE_ROOT
    if _NATIVE_ROOT is None:
        import ctypes

        try:
            from ..native import lib_path

            lib = ctypes.CDLL(lib_path("kvstore"))
            lib.kv_merkle_root.restype = None
            lib.kv_merkle_root.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_uint64, ctypes.c_char_p]
            lib.kv_merkle_levels.restype = ctypes.c_uint64
            lib.kv_merkle_levels.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_uint64, ctypes.c_char_p]
            _NATIVE_ROOT = (lib,)
        except Exception:
            _NATIVE_ROOT = ()
    return _NATIVE_ROOT[0] if _NATIVE_ROOT else None


def _native_args(items: list[bytes]):
    """(buf, offs) for the native tree calls: leaves concatenated plus
    numpy prefix offsets (a Python accumulation loop here was ~5x the
    native tree's own cost at 20k leaves)."""
    import numpy as np

    buf = b"".join(items)
    offs = np.zeros(len(items) + 1, np.uint64)
    np.cumsum(np.fromiter(map(len, items), np.uint64, len(items)),
              out=offs[1:])
    return buf, offs


def hash_from_byte_slices_fast(items: list[bytes]) -> bytes:
    """Root-only merkle hash through the fastest available engine —
    identical output to :func:`hash_from_byte_slices` (pinned by tests).

    Dispatch: tiny trees stay on hashlib (callers must not pay the
    one-time native build/load), large trees ride the batched device
    kernel when an accelerator is live, everything else goes through the
    native C++ tree (~30x the recursion on big leaf sets — the builtin
    kvstore's per-block app hash was the hottest function in the e2e
    throughput profile)."""
    n = len(items)
    if n < 64:                 # BEFORE lib resolution
        return hash_from_byte_slices(items)
    if _kernel_wanted(n):
        root = _root_kernel(items)
        if root is not None:
            return root
    lib = _native_root_fn()
    if lib is None:
        return _levels_hashlib(items)[-1][0]
    import ctypes

    buf, offs = _native_args(items)
    out = ctypes.create_string_buffer(32)
    lib.kv_merkle_root(buf,
                       offs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                       n, out)
    return out.raw


# ------------------------------------------------------- level-order core
# Pair adjacent nodes left to right; an odd tail node is promoted
# unchanged.  The ancestor of leaf i at level l is node i >> l in every
# level (promotion preserves floor-halving indices), so aunt paths are
# pure index arithmetic over the cached levels: sibling (i >> l) ^ 1,
# absent exactly when it falls off the level's width.

_KERNEL_MIN_LEAVES = 2048   # leaves before the device kernel is considered
_PROOF_LEVEL_MIN = 64       # below: the tiny recursive reference path
# padded kernel dispatch widths are owned by the declarative device
# plan (crypto/plan.py merkle_buckets) since r13; _bucket_width reads
# the ACTIVE plan so the AOT compile bundle and this dispatch agree
_LEAF_KERNEL_MAX_LEN = 118  # 0x00 + item + 9B padding fits two SHA-256 blocks


def set_merkle_kernel_min(n: int) -> None:
    """Config hook: minimum leaf count before the batched device kernel
    is considered for tree hashing (accelerator-gated either way)."""
    global _KERNEL_MIN_LEAVES
    _KERNEL_MIN_LEAVES = max(2, int(n))


def _level_widths(n: int) -> list[int]:
    widths = [n]
    while n > 1:
        n = (n + 1) // 2
        widths.append(n)
    return widths


def _levels_hashlib(items: list[bytes]) -> list[list[bytes]]:
    """Pure-Python level cache: every tree level, leaves first."""
    lv = [_sha(LEAF_PREFIX + it) for it in items]
    levels = [lv]
    while len(lv) > 1:
        m = len(lv) // 2
        nxt = [_sha(INNER_PREFIX + lv[2 * i] + lv[2 * i + 1])
               for i in range(m)]
        if len(lv) & 1:
            nxt.append(lv[-1])
        levels.append(nxt)
        lv = nxt
    return levels


def _levels_native(items: list[bytes]) -> list[list[bytes]] | None:
    """Whole level cache in one native call, or None without the lib."""
    lib = _native_root_fn()
    if lib is None:
        return None
    import ctypes

    import numpy as np

    n = len(items)
    widths = _level_widths(n)
    buf, offs = _native_args(items)
    out = ctypes.create_string_buffer(32 * sum(widths))
    wrote = lib.kv_merkle_levels(
        buf, offs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), n, out)
    if wrote != sum(widths):
        return None
    raw = out.raw
    levels, pos = [], 0
    for w in widths:
        end = pos + 32 * w
        levels.append([raw[i:i + 32] for i in range(pos, end, 32)])
        pos = end
    return levels


_ACCEL_LIVE: bool | None = None      # cached accelerator verdict


def _kernel_wanted(n: int) -> bool:
    """Should this tree try the batched device kernel?  Accelerator-gated:
    on host CPU the per-level kernel measured ~7x slower than the hashlib
    loop, so a ``JAX_PLATFORMS=cpu`` box must keep the native/hashlib
    engines.  ``TPU_BFT_MERKLE_KERNEL=1/0`` forces/disables (tests and
    bench exercise the kernel path on the CPU backend with =1).  The
    probe verdict is cached: re-resolving it (and retrying a failing
    crypto-backend import) per tree was ~30% of a 10k root+proofs
    build."""
    global _ACCEL_LIVE
    if n < _KERNEL_MIN_LEAVES:
        return False
    force = os.environ.get("TPU_BFT_MERKLE_KERNEL", "").strip()
    if force == "0":
        return False
    if force == "1":
        return True
    if _ACCEL_LIVE is None:
        try:
            from .batch import _accelerator_device

            _ACCEL_LIVE = _accelerator_device() is not None
        except Exception:
            _ACCEL_LIVE = False
    return _ACCEL_LIVE


def _kernel_jits():
    """(jit(merkle_inner_level), jit(sha256_blocks)) with the persistent
    compile cache on, or None when jax cannot be imported.  Import stays
    lazy: merkle is on many non-JAX paths."""
    global _KERNEL_JITS
    if _KERNEL_JITS is None:
        try:
            import jax
        except ImportError:
            _KERNEL_JITS = ()
        else:
            from ..jaxenv import enable_compile_cache
            from ..ops import sha256 as _s

            enable_compile_cache()
            _KERNEL_JITS = (jax.jit(_s.merkle_inner_level),
                            jax.jit(_s.sha256_blocks), _s)
    return _KERNEL_JITS if _KERNEL_JITS else None


_KERNEL_JITS = None


def _bucket_width(n: int) -> int:
    from . import plan as _plan

    buckets = _plan.active().merkle_buckets
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _kernel_leaf_words(items: list[bytes], jits):
    """Leaf hashes as (n, 8) uint32 digest words.  Small items batch
    through the generic block kernel; big items (e.g. 64 kB block parts)
    hash through hashlib — leaf hashing there is data-bound, where C
    wins, while the kernel's edge is the per-node dispatch overhead."""
    import numpy as np

    jit_level, jit_blocks, _s = jits
    n = len(items)
    maxlen = max(map(len, items), default=0)
    if maxlen > _LEAF_KERNEL_MAX_LEN:
        leaves = b"".join(_sha(LEAF_PREFIX + it) for it in items)
        return _s.bytes_to_words(
            np.frombuffer(leaves, np.uint8).reshape(n, 32))
    nb = _s.max_blocks_for_len(maxlen + 1)
    lens = np.fromiter(map(len, items), np.int64, n) + 1
    msgs = np.zeros((n, maxlen + 1), np.uint8)
    for i, it in enumerate(items):       # rows start with the 0x00 prefix
        msgs[i, 1:1 + len(it)] = np.frombuffer(it, np.uint8)
    out = np.empty((n, 32), np.uint8)
    cap = _bucket_width(1 << 30)           # plan's largest level width
    for start in range(0, n, cap):
        end = min(start + cap, n)
        c = end - start
        bb = _bucket_width(c)
        mp = np.zeros((bb, maxlen + 1), np.uint8)
        mp[:c] = msgs[start:end]
        lp = np.ones((bb,), np.int64)
        lp[:c] = lens[start:end]
        blocks, active = _s.host_pad(mp, lp, nb)
        out[start:end] = np.asarray(
            jit_blocks(blocks, active), np.uint8)[:c]
    return _s.bytes_to_words(out)


def _kernel_levels_from_words(words, jits, keep_levels: bool):
    """Run the level kernel to the root.  Returns the level list (word
    arrays, leaves first) when ``keep_levels``, else just the root row."""
    import numpy as np

    from . import aotbundle as _aot

    jit_level, _, _s = jits
    cap = _bucket_width(1 << 30)           # plan's largest level width
    lv = words
    levels = [lv]
    while len(lv) > 1:
        m = len(lv) // 2
        left, right = lv[0:2 * m:2], lv[1:2 * m:2]
        out = np.empty((m, 8), np.uint32)
        for start in range(0, m, cap):
            end = min(start + cap, m)
            c = end - start
            bb = _bucket_width(c)
            lpad = np.zeros((bb, 8), np.uint32)
            rpad = np.zeros((bb, 8), np.uint32)
            lpad[:c], rpad[:c] = left[start:end], right[start:end]
            # AOT compile-bundle consult: a bundled level width skips
            # tracing/compiling on the first dispatch (warm boot)
            fn = _aot.lookup(f"merkle_level:{bb}") or jit_level
            out[start:end] = np.asarray(fn(lpad, rpad))[:c]
        if len(lv) & 1:
            out = np.concatenate([out, lv[-1:]])
        lv = out
        levels.append(lv)
    if not keep_levels:
        return lv
    _sdw = jits[2].words_to_bytes
    return [[row.tobytes() for row in _sdw(l_)] for l_ in levels]


def _root_kernel(items: list[bytes]) -> bytes | None:
    jits = _kernel_jits()
    if jits is None:
        return None
    words = _kernel_leaf_words(items, jits)
    root = _kernel_levels_from_words(words, jits, keep_levels=False)
    return jits[2].words_to_bytes(root)[0].tobytes()


def _levels_kernel(items: list[bytes]) -> list[list[bytes]] | None:
    jits = _kernel_jits()
    if jits is None:
        return None
    words = _kernel_leaf_words(items, jits)
    return _kernel_levels_from_words(words, jits, keep_levels=True)


def _build_levels(items: list[bytes]) -> list[list[bytes]]:
    """The dispatch ladder shared by the proof builders."""
    if _kernel_wanted(len(items)):
        levels = _levels_kernel(items)
        if levels is not None:
            return levels
    return _levels_native(items) or _levels_hashlib(items)


class Proof(NamedTuple):
    """Merkle inclusion proof (crypto/merkle/proof.go semantics).

    A NamedTuple rather than a dataclass: proofs are built in bulk (one
    per part / per tx) and never mutated, and tuple construction is
    C-speed — the dataclass ``__init__`` was ~40% of a 10k-leaf
    root+proofs build."""

    total: int
    index: int
    leaf_hash: bytes
    aunts: tuple[bytes, ...] = ()

    def compute_root(self) -> bytes:
        return _compute_from_aunts(self.index, self.total, self.leaf_hash,
                                   self.aunts)

    def verify(self, root: bytes, leaf: bytes) -> bool:
        if self.total < 0 or self.index < 0 or self.index >= self.total:
            return False
        if leaf_hash(leaf) != self.leaf_hash:
            return False
        computed = self.compute_root()
        return computed is not None and computed == root


def _compute_from_aunts(index: int, total: int, leaf: bytes,
                        aunts: list[bytes]) -> bytes | None:
    if total == 0 or index >= total:
        return None
    if total == 1:
        return leaf if not aunts else None
    if not aunts:
        return None
    k = _split_point(total)
    if index < k:
        left = _compute_from_aunts(index, k, leaf, aunts[:-1])
        return None if left is None else inner_hash(left, aunts[-1])
    right = _compute_from_aunts(index - k, total - k, leaf, aunts[:-1])
    return None if right is None else inner_hash(aunts[-1], right)


def proofs_from_byte_slices_reference(items: list[bytes]
                                      ) -> tuple[bytes, list[Proof]]:
    """Recursive reference builder (crypto/merkle/proof.go shape): root
    hash + one inclusion proof per item.  Kept as the oracle the batched
    level-order path is pinned against, and as the tiny-tree fast path —
    a handful of leaves don't amortize the vectorized assembly."""
    total = len(items)
    leaves = [leaf_hash(it) for it in items]

    def build(lo: int, hi: int) -> tuple[bytes, dict[int, list[bytes]]]:
        n = hi - lo
        if n == 0:
            return _sha(b""), {}
        if n == 1:
            return leaves[lo], {lo: []}
        k = _split_point(n)
        lroot, lpaths = build(lo, lo + k)
        rroot, rpaths = build(lo + k, hi)
        paths = {}
        for i, p in lpaths.items():
            paths[i] = p + [rroot]
        for i, p in rpaths.items():
            paths[i] = p + [lroot]
        return inner_hash(lroot, rroot), paths

    root, paths = build(0, total)
    # paths accumulate bottom-up (deepest sibling first), which is exactly
    # the order _compute_from_aunts consumes (aunts[-1] = topmost).  Aunts
    # are tuples on EVERY construction path (here, the level-order
    # builder, and the wire decoders) so Proof equality is reliable.
    proofs = [Proof(total=total, index=i, leaf_hash=leaves[i],
                    aunts=tuple(paths[i])) for i in range(total)]
    return root, proofs


def _proofs_from_levels(levels: list[list[bytes]], total: int
                        ) -> tuple[bytes, list[Proof]]:
    """All aunt paths from the cached levels with zero re-hashing.

    Per level one vectorized sibling-index computation plus one
    ``itemgetter`` gather (both C-speed over all leaves at once);
    the per-leaf Python work is a single zip/list pass.  Aunts come out
    bottom-up (deepest first), matching ``_compute_from_aunts``."""
    import numpy as np

    root = levels[-1][0]
    if total == 1:
        return root, [Proof(1, 0, levels[0][0], ())]
    idx = np.arange(total)
    cols = []           # per level: sequence of that level's aunt per leaf
    starts = []         # per level: first leaf whose sibling is promoted
    for lvl_i in range(len(levels) - 1):
        nodes = levels[lvl_i]
        w = len(nodes)
        run = 1 << lvl_i
        # the only possible invalid sibling is the promoted odd tail:
        # ancestor w-1 with (w-1)^1 == w — a contiguous tail of leaves
        start = ((w - 1) << lvl_i) if ((w - 1) ^ 1) >= w else total
        if run >= 32:
            # deep levels: the aunt is constant over runs of 2^l leaves,
            # so sequence-multiply beats a per-leaf gather (None fills
            # the promoted tail; `start` keeps it out of every proof)
            col = []
            for j in range(w):
                sib = j ^ 1
                col.extend((nodes[sib] if sib < w else None,) * run)
            cols.append(col[:total])
        else:
            sib = (idx >> lvl_i) ^ 1
            np.minimum(sib, w - 1, out=sib)
            cols.append(itemgetter(*sib.tolist())(nodes))
        starts.append(start)
    min_start = min(starts, default=total)
    leaves = levels[0]
    nlv = len(cols)
    # bulk assembly, C-speed end to end: one zip builds each proof's
    # field tuple, Proof._make (tuple.__new__) materializes it.  Aunt
    # paths are tuples here — never mutated, and list() per proof would
    # be ~15% of the whole build.
    proofs = list(map(Proof._make,
                      zip(repeat(total, min_start), count(), leaves,
                          zip(*cols))))
    for i in range(min_start, total):    # promoted-tail leaves: filter
        aunts = tuple(cols[k][i] for k in range(nlv) if i < starts[k])
        proofs.append(Proof(total, i, leaves[i], aunts))
    return root, proofs


def proofs_from_byte_slices(items: list[bytes]) -> tuple[bytes, list[Proof]]:
    """Root hash + one inclusion proof per item, through the size-based
    engine dispatch (see the module docstring).  Bit-identical to
    :func:`proofs_from_byte_slices_reference` on every path."""
    total = len(items)
    if total < _PROOF_LEVEL_MIN:
        return proofs_from_byte_slices_reference(items)
    return _proofs_from_levels(_build_levels(items), total)


class TreeCache:
    """Retained per-level node cache of one tree: build the levels ONCE
    (through the same engine dispatch as :func:`proofs_from_byte_slices`),
    then emit inclusion proofs for ARBITRARY leaf indexes by pure index
    arithmetic over the cached levels — zero re-hashing per proof.

    This is the light-serving seam: a block's tx/validator tree is built
    on the first proof request and every later request (any subset of
    indexes, any order, any number of clients) is a gather.  Unlike
    :func:`proofs_from_byte_slices` it does not materialize all N proofs
    up front, so a 10k-leaf block whose clients only ever ask for a few
    hundred leaves never pays the full assembly.

    Proofs are bit-identical to the reference builder (aunts bottom-up,
    promoted odd-tail nodes skipped), pinned by tests."""

    __slots__ = ("levels", "total")

    def __init__(self, levels: list[list[bytes]], total: int):
        self.levels = levels
        self.total = total

    @classmethod
    def build(cls, items: list[bytes]) -> "TreeCache":
        n = len(items)
        if n == 0:
            return cls([[_sha(b"")]], 0)
        if n < _PROOF_LEVEL_MIN:
            return cls(_levels_hashlib(items), n)
        return cls(_build_levels(items), n)

    @property
    def root(self) -> bytes:
        return self.levels[-1][0]

    def nbytes(self) -> int:
        """Approximate retained size (cache accounting): 32 bytes per
        node across every level."""
        return 32 * sum(len(lv) for lv in self.levels)

    def proof(self, index: int) -> Proof:
        """Inclusion proof for leaf ``index`` (raises IndexError when out
        of range).  The ancestor of leaf i at level l is node i >> l, its
        sibling (i >> l) ^ 1 — absent exactly when the sibling index
        falls off the level's width (promoted odd tail)."""
        total = self.total
        if not 0 <= index < total:
            raise IndexError(f"leaf {index} out of range (total {total})")
        if total == 1:
            return Proof(1, 0, self.levels[0][0], ())
        aunts = []
        for lvl_i in range(len(self.levels) - 1):
            nodes = self.levels[lvl_i]
            sib = (index >> lvl_i) ^ 1
            if sib < len(nodes):
                aunts.append(nodes[sib])
        return Proof(total, index, self.levels[0][index], tuple(aunts))

    def proofs(self, indexes) -> list[Proof]:
        return [self.proof(i) for i in indexes]


# ------------------------------------------------------------- proof ops
# (crypto/merkle/proof_op.go + proof_value.go: composable proof chains for
# multi-store queries — ProofOperators.Verify walks ops leaf-to-root,
# each op transforming its input into the next layer's expected value)

@dataclass
class ProofOp:
    """Serialized proof step (type tag + key + opaque payload)."""

    type: str
    key: bytes
    data: bytes


class ProofOpError(Exception):
    pass


def kv_leaf(key: bytes, value: bytes) -> bytes:
    """Leaf encoding for provable KV stores: the KEY is bound into the
    leaf alongside the value hash (proof_value.go does the same via
    proto KVPair) — otherwise a prover could relabel any proven value
    under any key."""
    return (len(key).to_bytes(4, "big") + key
            + hashlib.sha256(value).digest())


class ValueOp:
    """Proves (key, value) -> store root: leaf = hash(kv_leaf(key,
    sha256(value))), then the merkle path in ``proof``
    (crypto/merkle/proof_value.go)."""

    TYPE = "simple:v"

    def __init__(self, key: bytes, proof: Proof):
        self.key = key
        self.proof = proof

    def run(self, args: list[bytes]) -> list[bytes]:
        if len(args) != 1:
            raise ProofOpError(f"ValueOp wants 1 arg, got {len(args)}")
        if leaf_hash(kv_leaf(self.key, args[0])) != self.proof.leaf_hash:
            raise ProofOpError("key/value does not match proof leaf")
        root = self.proof.compute_root()
        if root is None:
            raise ProofOpError("invalid merkle path")
        return [root]

    def proof_op(self) -> ProofOp:
        import msgpack

        return ProofOp(self.TYPE, self.key, msgpack.packb(
            {"t": self.proof.total, "i": self.proof.index,
             "l": self.proof.leaf_hash, "a": self.proof.aunts},
            use_bin_type=True))

    @classmethod
    def decode(cls, op: ProofOp) -> "ValueOp":
        import msgpack

        d = msgpack.unpackb(op.data, raw=False)
        return cls(op.key, Proof(d["t"], d["i"], d["l"], tuple(d["a"])))


_OP_DECODERS = {ValueOp.TYPE: ValueOp.decode}


def register_proof_op(type_: str, decoder) -> None:
    """proof_op.go ProofRuntime.RegisterOpDecoder."""
    _OP_DECODERS[type_] = decoder


class ProofOperators:
    """Ordered op chain: Verify(root, keypath, value) runs each op over
    the previous op's output, consuming keypath segments right-to-left
    (proof_op.go ProofOperators.Verify)."""

    def __init__(self, ops: list):
        self.ops = ops

    @classmethod
    def decode(cls, ops: list[ProofOp]) -> "ProofOperators":
        decoded = []
        for op in ops:
            dec = _OP_DECODERS.get(op.type)
            if dec is None:
                raise ProofOpError(f"unregistered proof op {op.type!r}")
            decoded.append(dec(op))
        return cls(decoded)

    def verify(self, root: bytes, keypath: list[bytes],
               value: bytes) -> None:
        """Raises ProofOpError unless the chain proves value@keypath
        under root."""
        if not self.ops:
            raise ProofOpError("empty proof op chain")
        args = [value]
        keys = list(keypath)
        for op in self.ops:
            if getattr(op, "key", b""):
                if not keys:
                    raise ProofOpError("keypath exhausted")
                if keys[-1] != op.key:
                    raise ProofOpError(
                        f"key mismatch: {keys[-1]!r} != {op.key!r}")
                keys.pop()
            args = op.run(args)
        if keys:
            raise ProofOpError(f"keypath not fully consumed: {keys!r}")
        if args != [root]:
            raise ProofOpError("computed root does not match")

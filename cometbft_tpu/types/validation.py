"""Commit verification: the exact seam where the TPU backend enters.

Mirrors ``types/validation.go:13-360``:

- ``VerifyCommit``            — checks every signature (commit AND nil votes),
                                tallies only for-block power, needs > 2/3.
- ``VerifyCommitLight``       — verifies commit-flag sigs only, stops once
                                > 2/3 is tallied (blocksync/light hot path).
- ``VerifyCommitLightTrusting`` — validators looked up BY ADDRESS in a
                                (possibly different) trusted set, threshold =
                                trust-level fraction of the trusted total.
- ``...AllSignatures`` variants (evidence verification) — no early exit.

All paths route signatures through ``crypto.batch.BatchVerifier``; the
backend ("auto"/"tpu"/"cpu") comes from ``set_default_backend`` — the
reference's config.Config-driven selection point.  Where the reference
falls back to one-by-one verification for mixed key types
(``shouldBatchVerify``), our device verifier routes non-ed25519 lanes to
CPU inside the batch instead.

Commits carrying a BLS aggregate (``types/commit.py``) verify the whole
folded cohort up front — two pairings via ``crypto/blsagg``, regardless
of cohort size — and the per-lane machinery then only sees the Ed25519
cohort plus any individual BLS lanes (NIL votes sign a different
message and never fold).
"""

from __future__ import annotations

from fractions import Fraction

from ..crypto import batch as cryptobatch
from ..libs import tracing
from .commit import Commit
from .validator_set import ValidatorSet

_DEFAULT_BACKEND = "auto"


def set_default_backend(backend: str) -> None:
    """Select the signature backend ("auto" | "tpu" | "jax" | "cpu")."""
    global _DEFAULT_BACKEND
    _DEFAULT_BACKEND = backend


def get_default_backend() -> str:
    return _DEFAULT_BACKEND


def _verify_span(entry: str, items):
    """The flight recorder's span of one entry call over ``items``
    (``(block_id, height, commit)`` each): the commits and signatures
    presented, stamped with the height (or the window's ``h_lo`` /
    ``h_hi``) that ``/dump_trace?height=`` selects by; ``ok`` turns
    False if the entry raises."""
    if not tracing.is_enabled():
        return tracing.span("types.validation", "verify")
    heights = [h for _, h, _ in items]
    where = {"height": heights[0]} if len(items) == 1 else \
        {"h_lo": min(heights, default=0), "h_hi": max(heights, default=0)}
    return tracing.span("types.validation", "verify", entry=entry,
                        commits=len(items),
                        lanes=sum(c.size() for _, _, c in items), ok=True,
                        **where)


class CommitVerificationError(Exception):
    pass


class ErrInvalidCommit(CommitVerificationError):
    pass


class ErrNotEnoughVotingPower(CommitVerificationError):
    pass


class ErrInvalidSignature(CommitVerificationError):
    def __init__(self, idx: int, msg: str = ""):
        self.idx = idx
        super().__init__(msg or f"wrong signature (#{idx})")


def _check_commit_basics(vals: ValidatorSet, commit: Commit, height: int,
                         block_id) -> None:
    if vals.size() != commit.size():
        raise ErrInvalidCommit(
            f"invalid commit: {commit.size()} sigs for {vals.size()} vals")
    if height != commit.height:
        raise ErrInvalidCommit(
            f"invalid commit height {commit.height}, want {height}")
    if block_id != commit.block_id:
        raise ErrInvalidCommit("invalid commit: wrong block ID")


def _verify_aggregate(chain_id: str, vals: ValidatorSet, commit: Commit,
                      *, lookup_by_address: bool) -> tuple[frozenset, int]:
    """Verify the commit's BLS aggregate lane block up front; the main
    loop then TALLIES the proven lanes without re-verifying them.

    Returns ``(proven aggregate lane indices, pre-tallied power)``.  The
    lane set is empty when the commit carries no aggregate, or (trusting
    path only) when the signer cohort could not be resolved in the
    trusted set, in which case the aggregate lanes simply contribute no
    power.  The power is the proven lanes' summed voting power on the
    index path — where lanes align 1:1 with the valset, so no duplicate
    bookkeeping is possible and the caller's loop can skip AGGREGATE
    lanes entirely — and 0 on the trusting path, whose loop still owns
    the by-address tally and duplicate detection.

    Index path (``lookup_by_address=False``): lanes align with the
    valset, so the structure is fully checkable — any malformation
    raises ErrInvalidCommit, a failing aggregate signature raises
    ErrInvalidSignature on the first aggregate lane.  The structural
    checks (every lane a cohort member, addresses matching the valset)
    run vectorized over numpy columns cached per commit (``_agg_np``)
    and per valset (``blsagg.valset_table``) — at 10k validators the
    per-lane object loop was costing more than the pairings.

    Trusting path: signers resolve BY ADDRESS into a possibly different
    trusted set, all-or-nothing.  If every signer resolves to a BLS
    validator there, the aggregate is verified against those pubkeys
    (a bad signature then raises — a commit carrying a provably false
    aggregate is invalid, not merely unproven).  If ANY signer is
    unknown, the cohort's power cannot be attributed and the whole
    aggregate is skipped — exactly how the trusting loop skips
    individual lanes from unknown validators.
    """
    if not commit.has_aggregate():
        return frozenset(), 0
    err = commit._validate_aggregate()
    if err:
        raise ErrInvalidCommit(f"invalid commit: {err}")
    from ..crypto import blsagg as _blsagg

    lanes = commit.aggregate_lanes()
    power = 0
    if not lookup_by_address:
        import numpy as np

        try:
            tbl = _blsagg.valset_table(vals)
        except ValueError:
            raise ErrInvalidSignature(
                lanes[0], "invalid BLS cohort pubkey in valset")
        n = len(commit.signatures)
        if tbl.cohort_mask.shape[0] != n:
            raise ErrInvalidCommit(
                f"invalid commit: {n} sigs for {vals.size()} vals")
        cached = commit.__dict__.get("_agg_np")
        if cached is None:
            mask = np.zeros((n,), np.bool_)
            lane_addrs = np.zeros((len(lanes), 20), np.uint8)
            for r, idx in enumerate(lanes):
                mask[idx] = True
                addr = commit.signatures[idx].validator_address
                if len(addr) == 20:
                    lane_addrs[r] = np.frombuffer(addr, np.uint8)
            cached = (mask, lane_addrs)
            commit.__dict__["_agg_np"] = cached
        mask, lane_addrs = cached
        stray = mask & ~tbl.cohort_mask
        if bool(stray.any()):
            raise ErrInvalidCommit(
                f"aggregate lane {int(np.nonzero(stray)[0][0])} "
                "is not a BLS validator")
        addr_bad = (tbl.addr_mat[mask] != lane_addrs).any(axis=1)
        if bool(addr_bad.any()):
            raise ErrInvalidCommit(
                f"aggregate lane {lanes[int(np.nonzero(addr_bad)[0][0])]} "
                "address does not match valset")
        power = int(tbl.powers[mask].sum())
        signers = mask
    else:
        signers = []
        for idx in lanes:
            vi, val = vals.get_by_address(
                commit.signatures[idx].validator_address)
            if vi < 0 or val.pub_key.type() != "bls12_381":
                return frozenset(), 0       # unattributable: contributes 0
            signers.append(vi)
    sp = tracing.begin("crypto.agg", "verify", height=commit.height,
                       lanes=len(lanes)) if tracing.is_enabled() else None
    ok = _blsagg.verify_commit_aggregate(
        vals, signers, commit.aggregate_sign_bytes(chain_id),
        commit.agg_signature)
    tracing.finish(sp, ok=ok)
    if not ok:
        raise ErrInvalidSignature(
            lanes[0], f"wrong aggregate signature (lanes {lanes})")
    return frozenset(lanes), power


def _verify(chain_id: str, vals: ValidatorSet, commit: Commit,
            voting_power_needed: int, *, count_all: bool,
            verify_nil_sigs: bool, lookup_by_address: bool,
            backend: str | None, use_cache: bool = True) -> None:
    """Shared tally+verify core (types/validation.go verifyCommitBatch).

    count_all=False allows early exit once the tally clears the threshold
    (remaining signatures are NOT verified — VerifyCommitLight semantics).

    use_cache consults (and seeds) the verified-signature cache
    (``crypto/scheduler``): a commit signature already verified as a
    gossiped vote costs a dict hit instead of a scalar multiplication.
    The evidence-path ``*AllSignatures`` variants pass False — evidence
    verification never trusts the cache.
    """
    from ..crypto import scheduler as _vsched

    # BLS aggregate lanes verify up front (one pairing check covers the
    # whole cohort); the loop below only tallies the proven lanes.  The
    # dense paths never see aggregates: any valset with a BLS member has
    # vals.dense() None, and each dense core also guards explicitly.
    agg_proven, agg_power = _verify_aggregate(
        chain_id, vals, commit, lookup_by_address=lookup_by_address)
    if (agg_power > voting_power_needed and not count_all
            and not verify_nil_sigs):
        # VerifyCommitLight semantics: the proven aggregate alone clears
        # the threshold, remaining lanes need not be verified — the
        # O(1)-pairing fast path never enters the per-lane loop at all
        return

    if not lookup_by_address:
        if _dense_verify(chain_id, vals, commit, voting_power_needed,
                         count_all=count_all,
                         verify_nil_sigs=verify_nil_sigs,
                         backend=backend or _DEFAULT_BACKEND,
                         use_cache=use_cache):
            return
    elif not verify_nil_sigs:
        if _dense_verify_trusting(chain_id, vals, commit,
                                  voting_power_needed,
                                  count_all=count_all,
                                  backend=backend or _DEFAULT_BACKEND,
                                  use_cache=use_cache):
            return
    bv = cryptobatch.create_batch_verifier(backend or _DEFAULT_BACKEND)
    lanes: list[int] = []          # commit-sig indices added to the batch
    seeds: list[tuple] = []        # lanes to seed into the cache on success
    cache_on = use_cache and _vsched.cache_active()
    tally = agg_power
    seen: set[bytes] = set()

    for idx, cs in enumerate(commit.signatures):
        if cs.is_absent():
            continue
        if not cs.is_commit() and not verify_nil_sigs:
            # ignoreSig runs BEFORE lookup/dup bookkeeping
            # (validation.go:243-266): a NIL sig then a COMMIT sig from
            # the same address is legal on the trusting path
            continue
        if cs.is_aggregate():
            if not lookup_by_address:
                # index path: pre-tallied into agg_power (every lane is
                # proven — _verify_aggregate raises otherwise)
                if not count_all and tally > voting_power_needed:
                    break
                continue
            if idx not in agg_proven:
                continue   # trusting path, unresolved cohort: no power
        if lookup_by_address:
            vi, val = vals.get_by_address(cs.validator_address)
            if vi < 0:
                continue
            if cs.validator_address in seen:
                raise ErrInvalidCommit(
                    f"duplicate validator {cs.validator_address.hex()} in commit")
            seen.add(cs.validator_address)
        else:
            val = vals.get_by_index(idx)
        if cs.is_aggregate():
            # proven by the up-front aggregate verification: tally only
            tally += val.voting_power
            if not count_all and tally > voting_power_needed:
                break
            continue
        # BLS validators' individual lanes (NIL votes, or a cohort too
        # small to fold) sign the zero-timestamp aggregation domain
        msg = commit.vote_sign_bytes_for(chain_id, idx,
                                         val.pub_key.type())
        if cache_on and _vsched.cache_lookup(val.pub_key.bytes(), msg,
                                             cs.signature):
            pass            # verified before (gossip/scheduler): free lane
        else:
            bv.add(val.pub_key, msg, cs.signature)
            lanes.append(idx)
            if cache_on:
                seeds.append((val.pub_key.bytes(), msg, cs.signature))
        if cs.is_commit():
            tally += val.voting_power
        if not count_all and tally > voting_power_needed:
            break

    if len(bv) > 0:
        ok, oks = bv.verify()
        if not ok:
            first_bad = lanes[oks.index(False)]
            raise ErrInvalidSignature(first_bad)
        for s in seeds:
            _vsched.cache_seed(*s)
    if tally <= voting_power_needed:
        raise ErrNotEnoughVotingPower(
            f"tallied {tally} <= needed {voting_power_needed}")


def _cache_split(pubs_sel, sigs_sel, msgs, lens):
    """Per-lane verified-signature cache consult for dense rows: returns
    ``(hit mask, keys)`` where keys feed :func:`cache_seed` after a
    successful verification.  Key material matches the object path
    exactly — raw 32-byte pubkey, exact sign bytes, 64-byte signature —
    so gossip-time seeds hit commit-time lookups."""
    import numpy as np

    from ..crypto import scheduler as _vsched

    k = pubs_sel.shape[0]
    mask = np.zeros((k,), bool)
    keys: list[tuple] = []
    for i in range(k):
        key = (pubs_sel[i].tobytes(), msgs[i, :int(lens[i])].tobytes(),
               sigs_sel[i].tobytes())
        keys.append(key)
        mask[i] = _vsched.cache_lookup(*key)
    return mask, keys


def _dense_verify(chain_id: str, vals: ValidatorSet, commit: Commit,
                  needed: int, *, count_all: bool, verify_nil_sigs: bool,
                  backend: str, use_cache: bool = True) -> bool:
    """Vectorized VerifyCommit core: columnar valset/commit views + the
    native sign-bytes builder + one dense batch dispatch.  At 10k
    validators this cuts the host side from ~60 ms of per-lane Python to
    ~3 ms (the BASELINE <5 ms p50 headline needs the host share small).

    Returns True when it fully handled verification (raising on bad sigs
    or insufficient power), False when not applicable — mixed key types,
    odd signature sizes, or no native encoder — and the caller runs the
    per-lane loop.  Semantics mirror the loop exactly, including Light's
    early exit after the lane that clears the threshold."""
    import numpy as np

    from ..crypto import _native_ed25519 as nat

    if not count_all and verify_nil_sigs:
        # no caller uses this combination; the early-exit cumsum below
        # would count nil-vote power toward the threshold (the loop only
        # tallies commit lanes) — refuse rather than miscount
        return False
    if commit.has_aggregate():
        # aggregate lanes tally through the loop path (any valset with a
        # BLS member has dense() None anyway; this guards the malformed
        # all-Ed25519-commit-with-aggregate case into the strict loop)
        return False
    with tracing.span("types.validation", "rows", commits=1) as sp:
        dense = vals.dense()
        cols = commit.dense_columns()
        if dense is None or cols is None or not nat.available():
            return False
        pubs, powers = dense
        flags, ts, sigmat = cols
        if len(flags) != len(powers):
            return False               # size mismatch: let the loop raise
        from .commit import BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT

        commit_mask = flags == BLOCK_ID_FLAG_COMMIT
        if count_all:
            if verify_nil_sigs:
                scope = np.nonzero(flags != BLOCK_ID_FLAG_ABSENT)[0]
            else:
                scope = np.nonzero(commit_mask)[0]
            tally = int(powers[scope][commit_mask[scope]].sum()) \
                if scope.size else 0
        else:
            scope, tally = _dense_light_scope(powers, flags, needed)
        if scope.size:
            built = _dense_build_rows(chain_id, commit, ts, flags, scope)
            if built is None:
                return False
            msgs, lens = built
            pubs_sel = np.ascontiguousarray(pubs[scope])
            sigs_sel = np.ascontiguousarray(sigmat[scope])
        if sp is not None:
            sp.attrs["lanes"] = int(scope.size)
    if scope.size:
        from ..crypto import scheduler as _vsched

        if use_cache and _vsched.dense_cache_active():
            mask, keys = _cache_split(pubs_sel, sigs_sel, msgs, lens)
            live = np.nonzero(~mask)[0]
        else:
            keys = None
            live = np.arange(scope.size)
        if live.size:
            res = cryptobatch.verify_dense(
                backend, np.ascontiguousarray(pubs_sel[live]),
                np.ascontiguousarray(sigs_sel[live]),
                np.ascontiguousarray(msgs[live]), lens[live],
                valset_pubs=pubs, scope=scope[live])
            if res is None:
                return False
            ok, oks = res
            if not ok:
                raise ErrInvalidSignature(
                    int(scope[live[np.nonzero(~oks)[0][0]]]))
            if keys is not None:
                for j in live:
                    _vsched.cache_seed(*keys[j])
    if tally <= needed:
        raise ErrNotEnoughVotingPower(
            f"tallied {tally} <= needed {needed}")
    return True


def _dense_verify_trusting(chain_id: str, vals: ValidatorSet,
                           commit: Commit, needed: int, *,
                           count_all: bool, backend: str,
                           use_cache: bool = True) -> bool:
    """Dense core of VerifyCommitLightTrusting: commit sigs resolve BY
    ADDRESS into a (possibly different) trusted set.  Lane selection
    stays a (cheap) Python loop — dict lookups, duplicate detection and
    the early exit are inherently sequential — but sign-bytes building
    and signature verification go through the same native dense
    machinery as the index-aligned paths.  Returns True when fully
    handled; False -> caller runs the object loop."""
    import numpy as np

    from ..crypto import _native_ed25519 as nat
    from .commit import BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT

    if commit.has_aggregate():
        return False                   # aggregate lanes: loop path only
    with tracing.span("types.validation", "rows", commits=1) as sp:
        dense = vals.dense()
        cols = commit.dense_columns()
        if dense is None or cols is None or not nat.available():
            return False
        pubs, powers = dense
        flags, ts, sigmat = cols
        addrs = commit.dense_addresses()
        aidx = vals.address_index()
        seen: set[bytes] = set()
        scope: list[int] = []            # commit-sig lanes to verify
        rows: list[int] = []             # their rows in the trusted set
        tally = 0
        for i, addr in enumerate(addrs):
            fl = int(flags[i])
            # non-commit sigs are ignored BEFORE the lookup/dup
            # bookkeeping, matching the reference's ignoreSig ordering in
            # verifyCommitBatch (validation.go:243-266) — a NIL sig
            # followed by a COMMIT sig from the same address is legal
            if fl != BLOCK_ID_FLAG_COMMIT:
                continue
            row = aidx.get(addr)
            if row is None:
                continue
            if addr in seen:
                raise ErrInvalidCommit(
                    f"duplicate validator {addr.hex()} in commit")
            seen.add(addr)
            scope.append(i)
            rows.append(row)
            tally += int(powers[row])
            if not count_all and tally > needed:
                break
        if scope:
            scope_arr = np.asarray(scope)
            built = _dense_build_rows(chain_id, commit, ts, flags,
                                      scope_arr)
            if built is None:
                return False
            msgs, lens = built
            rows_arr = np.asarray(rows)
            pubs_sel = np.ascontiguousarray(pubs[rows_arr])
            sigs_sel = np.ascontiguousarray(sigmat[scope_arr])
        if sp is not None:
            sp.attrs["lanes"] = len(scope)
    if scope:
        from ..crypto import scheduler as _vsched

        if use_cache and _vsched.dense_cache_active():
            mask, keys = _cache_split(pubs_sel, sigs_sel, msgs, lens)
            live = np.nonzero(~mask)[0]
        else:
            keys = None
            live = np.arange(scope_arr.size)
        if live.size:
            res = cryptobatch.verify_dense(
                backend, np.ascontiguousarray(pubs_sel[live]),
                np.ascontiguousarray(sigs_sel[live]),
                np.ascontiguousarray(msgs[live]), lens[live],
                valset_pubs=pubs, scope=rows_arr[live])
            if res is None:
                return False
            ok, oks = res
            if not ok:
                raise ErrInvalidSignature(
                    scope[int(live[np.nonzero(~oks)[0][0]])])
            if keys is not None:
                for j in live:
                    _vsched.cache_seed(*keys[j])
    if tally <= needed:
        raise ErrNotEnoughVotingPower(
            f"tallied {tally} <= needed {needed}")
    return True


def _dense_light_scope(powers, flags, needed):
    """VerifyCommitLight lane selection, shared by the single-commit and
    cross-block dense paths so the consensus-critical early-exit math
    lives in exactly one place: commit-flag lanes up to AND including the
    lane whose power pushes the tally past ``needed`` (the loop breaks
    after adding that lane).  Returns ``(scope indices, tally)``."""
    import numpy as np

    from .commit import BLOCK_ID_FLAG_COMMIT

    scope = np.nonzero(flags == BLOCK_ID_FLAG_COMMIT)[0]
    cum = np.cumsum(powers[scope]) if scope.size else np.zeros(0)
    over = np.nonzero(cum > needed)[0]
    if over.size:
        return scope[:int(over[0]) + 1], int(cum[int(over[0])])
    return scope, int(cum[-1]) if cum.size else 0


def _dense_build_rows(chain_id: str, commit: Commit, ts, flags, scope):
    """Native sign-bytes rows for the selected lanes of one commit, or
    None when the native builder is unavailable."""
    from ..crypto import _native_ed25519 as nat

    pre_c, pre_n, post = commit.sign_bytes_templates(chain_id)
    return nat.build_vote_sign_bytes(pre_c, pre_n, post, ts[scope],
                                     flags[scope])


def VerifyCommit(chain_id: str, vals: ValidatorSet, block_id, height: int,
                 commit: Commit, backend: str | None = None) -> None:
    """All signatures verified; > 2/3 of total power must be for block_id
    (types/validation.go:28)."""
    with _verify_span("VerifyCommit", ((block_id, height, commit),)):
        _check_commit_basics(vals, commit, height, block_id)
        needed = vals.total_voting_power() * 2 // 3
        _verify(chain_id, vals, commit, needed, count_all=True,
                verify_nil_sigs=True, lookup_by_address=False,
                backend=backend)


def VerifyCommitLight(chain_id: str, vals: ValidatorSet, block_id,
                      height: int, commit: Commit,
                      backend: str | None = None,
                      use_cache: bool = True) -> None:
    """Commit-flag signatures only, early exit at > 2/3
    (types/validation.go:63 — blocksync/light-client hot path).

    Callers verifying commits that were never gossiped to this node
    (light-client backfill, blocksync fallbacks) pass use_cache=False:
    with zero possible hits, the per-lane cache consult is pure
    overhead."""
    with _verify_span("VerifyCommitLight", ((block_id, height, commit),)):
        _check_commit_basics(vals, commit, height, block_id)
        needed = vals.total_voting_power() * 2 // 3
        _verify(chain_id, vals, commit, needed, count_all=False,
                verify_nil_sigs=False, lookup_by_address=False,
                backend=backend, use_cache=use_cache)


def VerifyCommitLightAllSignatures(chain_id: str, vals: ValidatorSet,
                                   block_id, height: int, commit: Commit,
                                   backend: str | None = None) -> None:
    """types/validation.go:96 (evidence path: no early exit, and no
    verified-signature cache — evidence rests on fresh verification)."""
    with _verify_span("VerifyCommitLightAllSignatures",
                      ((block_id, height, commit),)):
        _check_commit_basics(vals, commit, height, block_id)
        needed = vals.total_voting_power() * 2 // 3
        _verify(chain_id, vals, commit, needed, count_all=True,
                verify_nil_sigs=False, lookup_by_address=False,
                backend=backend, use_cache=False)


def VerifyCommitLightTrusting(chain_id: str, vals: ValidatorSet,
                              commit: Commit,
                              trust_level: Fraction = Fraction(1, 3),
                              backend: str | None = None,
                              count_all: bool = False,
                              use_cache: bool = True) -> None:
    """Trust-level verification against a possibly different validator set,
    lookup by address (types/validation.go:127 — light-client skipping
    verification)."""
    if trust_level <= 0 or trust_level > 1:
        raise ValueError("trust level must be in (0, 1]")
    needed = (vals.total_voting_power() * trust_level.numerator
              // trust_level.denominator)
    with _verify_span("VerifyCommitLightTrusting",
                      ((None, commit.height, commit),)):
        _verify(chain_id, vals, commit, needed, count_all=count_all,
                verify_nil_sigs=False, lookup_by_address=True,
                backend=backend, use_cache=use_cache)


class ErrBatchItemInvalid(CommitVerificationError):
    """A commit inside a multi-commit batch failed; ``item`` indexes the
    offending entry so blocksync can redo exactly that height."""

    def __init__(self, item: int, height: int, cause: Exception):
        self.item = item
        self.height = height
        self.cause = cause
        super().__init__(f"commit #{item} (height {height}): {cause}")


def verify_commits_light_batched(chain_id: str, vals: ValidatorSet,
                                 items: list, backend: str | None = None,
                                 patient: bool = False,
                                 use_cache: bool = False) -> int:
    """VerifyCommitLight over MANY commits sharing one validator set in a
    single device batch — the blocksync cross-block batching seam
    (reference verifies one commit per block sequentially at
    ``internal/blocksync/reactor.go:495``; here K blocks' commits fill one
    TPU dispatch, BASELINE configs[4]).

    ``items`` is a list of ``(block_id, height, commit)``.  Returns the
    number of signatures proven (dispatched + cache-proven).  Raises
    ErrBatchItemInvalid naming the first offending item.  ``patient`` is
    the blocksync accumulator's staging mode: the device dispatch queues
    behind an in-flight window instead of host-falling-back
    (``crypto/batch._device_call``).

    ``use_cache`` consults and seeds the verified-signature dedup cache
    (``crypto/scheduler``) per lane: a commit re-verified for the second
    client (the light-serving tier's hot-anchor workload) costs dict
    hits instead of scalar multiplications.  Default False — blocksync
    and light-client callers verify commits never gossiped here, and
    evidence-grade callers must never trust a cache.

    Demux contract for callers applying per item: when the raised
    error's ``cause`` is :class:`ErrInvalidSignature`, every item BEFORE
    ``err.item`` had all its selected lanes proven valid (lane order is
    item order; the dispatch computes every verdict before raising on
    the first bad lane, and cache-proven lanes hold positive verdicts by
    construction).  Any other cause is a pre-dispatch basics/tally
    failure — earlier items were NOT signature-checked and need their
    own verification pass before being trusted.
    """
    with _verify_span("verify_commits_light_batched", items):
        return _verify_commits_light_batched(
            chain_id, vals, items, backend or _DEFAULT_BACKEND, patient,
            use_cache)


def _verify_commits_light_batched(chain_id: str, vals: ValidatorSet,
                                  items: list, backend: str, patient: bool,
                                  use_cache: bool) -> int:
    """:func:`verify_commits_light_batched` proper: the dense core, else
    the per-lane loop."""
    from ..crypto import scheduler as _vsched

    n = _dense_verify_commits_batched(chain_id, vals, items, backend,
                                      patient=patient, use_cache=use_cache)
    if n is not None:
        return n
    bv = cryptobatch.create_batch_verifier(backend)
    lanes: list[tuple[int, int]] = []      # (item idx, commit-sig idx)
    seeds: list[tuple] = []
    cache_on = use_cache and _vsched.cache_active()
    n_hits = 0
    needed = vals.total_voting_power() * 2 // 3
    for k, (block_id, height, commit) in enumerate(items):
        try:
            _check_commit_basics(vals, commit, height, block_id)
            # index path: raises on any aggregate problem, so every
            # AGGREGATE lane is proven — its power is pre-tallied
            _, agg_power = _verify_aggregate(chain_id, vals, commit,
                                             lookup_by_address=False)
        except CommitVerificationError as e:
            raise ErrBatchItemInvalid(k, height, e) from e
        tally = agg_power
        if tally > needed:
            continue       # aggregate alone clears the threshold
        for idx, cs in enumerate(commit.signatures):
            if not cs.is_commit():
                continue
            if cs.is_aggregate():
                continue   # pre-tallied above
            val = vals.get_by_index(idx)
            msg = commit.vote_sign_bytes_for(chain_id, idx,
                                             val.pub_key.type())
            if cache_on and _vsched.cache_lookup(val.pub_key.bytes(), msg,
                                                 cs.signature):
                n_hits += 1            # proven before: free lane
            else:
                bv.add(val.pub_key, msg, cs.signature)
                lanes.append((k, idx))
                if cache_on:
                    seeds.append((val.pub_key.bytes(), msg, cs.signature))
            tally += val.voting_power
            if tally > needed:
                break
        if tally <= needed:
            raise ErrBatchItemInvalid(
                k, height,
                ErrNotEnoughVotingPower(f"tallied {tally} <= {needed}"))
    if len(bv) > 0:
        ok, oks = bv.verify()
        if not ok:
            k, idx = lanes[oks.index(False)]
            raise ErrBatchItemInvalid(k, items[k][1],
                                      ErrInvalidSignature(idx))
        for s in seeds:
            _vsched.cache_seed(*s)
    return len(lanes) + n_hits


def _dense_verify_commits_batched(chain_id: str, vals: ValidatorSet,
                                  items: list, backend: str,
                                  patient: bool = False,
                                  use_cache: bool = False) -> int | None:
    """Vectorized core of :func:`verify_commits_light_batched`: per-commit
    basics/tally checks in item order (matching the loop's raise order),
    then ONE dense verification over every selected lane of every commit
    (minus verified-sig-cache hits when ``use_cache``).  Returns the lane
    count, or None when not applicable (caller loops)."""
    import numpy as np

    from ..crypto import _native_ed25519 as nat
    from ..crypto import scheduler as _vsched

    dense = vals.dense()
    if dense is None or not nat.available():
        return None
    if any(item[2].has_aggregate() for item in items):
        return None                    # aggregate lanes: loop path only
    pubs, powers = dense
    needed = vals.total_voting_power() * 2 // 3
    with tracing.span("types.validation", "rows",
                      commits=len(items)) as sp:
        sel_pubs, sel_sigs, sel_msgs, sel_lens = [], [], [], []
        sel_scope = []
        lanes: list[tuple[int, int]] = []
        stride = 0
        for k, (block_id, height, commit) in enumerate(items):
            try:
                _check_commit_basics(vals, commit, height, block_id)
            except CommitVerificationError as e:
                raise ErrBatchItemInvalid(k, height, e) from e
            cols = commit.dense_columns()
            if cols is None:
                return None
            flags, ts, sigmat = cols
            scope, tally = _dense_light_scope(powers, flags, needed)
            if tally <= needed:
                raise ErrBatchItemInvalid(
                    k, height,
                    ErrNotEnoughVotingPower(f"tallied {tally} <= {needed}"))
            built = _dense_build_rows(chain_id, commit, ts, flags, scope)
            if built is None:
                return None
            msgs, lens = built
            sel_pubs.append(pubs[scope])
            sel_sigs.append(sigmat[scope])
            sel_msgs.append(msgs)
            sel_lens.append(lens)
            sel_scope.append(scope)
            stride = max(stride, msgs.shape[1])
            lanes.extend((k, int(i)) for i in scope)
        if sp is not None:
            sp.attrs["lanes"] = len(lanes)
        if not lanes:
            return 0
        # strides are equal in practice (same chain_id; fixed-width height);
        # pad defensively if a template ever differs
        sel_msgs = [m if m.shape[1] == stride else np.pad(
            m, ((0, 0), (0, stride - m.shape[1]))) for m in sel_msgs]
        pubs_all = np.ascontiguousarray(np.concatenate(sel_pubs))
        sigs_all = np.ascontiguousarray(np.concatenate(sel_sigs))
        msgs_all = np.ascontiguousarray(np.concatenate(sel_msgs))
        lens_all = np.concatenate(sel_lens)
        scope_all = np.concatenate(sel_scope)
    keys = None
    if use_cache and _vsched.cache_active():
        # per-lane dedup-cache consult (same key material as the single-
        # commit dense paths): hit lanes hold positive verdicts and drop
        # out of the dispatch — a hot anchor commit re-verified for the
        # k-th light client costs k-1 dict sweeps, not k dispatches.
        # Gated on cache_active (not dense_cache_active): opt-in callers
        # are the serving tier, whose FIRST verification must seed.
        mask, keys = _cache_split(pubs_all, sigs_all, msgs_all, lens_all)
        live = np.nonzero(~mask)[0]
    else:
        live = np.arange(len(lanes))
    if live.size:
        res = cryptobatch.verify_dense(
            backend, np.ascontiguousarray(pubs_all[live]),
            np.ascontiguousarray(sigs_all[live]),
            np.ascontiguousarray(msgs_all[live]), lens_all[live],
            valset_pubs=pubs, scope=scope_all[live],
            patient=patient)
        if res is None:
            return None
        ok, oks = res
        if not ok:
            k, idx = lanes[int(live[np.nonzero(~oks)[0][0]])]
            raise ErrBatchItemInvalid(k, items[k][1],
                                      ErrInvalidSignature(idx))
        if keys is not None:
            for j in live:
                _vsched.cache_seed(*keys[int(j)])
    return len(lanes)


def VerifyCommitLightTrustingAllSignatures(chain_id: str, vals: ValidatorSet,
                                           commit: Commit,
                                           trust_level: Fraction = Fraction(1, 3),
                                           backend: str | None = None) -> None:
    """types/validation.go:182 (evidence path: no cache, see above)."""
    VerifyCommitLightTrusting(chain_id, vals, commit, trust_level,
                              backend=backend, count_all=True,
                              use_cache=False)


# ------------------------------------------------- extended commits (ABCI 2.0)


class ErrInvalidExtensionSignature(ErrInvalidSignature):
    """Validator ``idx``'s vote signature holds and its vote-extension
    signature does not (``types/vote.go`` VerifyExtension)."""

    def __init__(self, idx: int, msg: str = ""):
        super().__init__(idx, msg or f"wrong extension signature (#{idx})")


_EXTENDED_METRICS = None


def _extended_metrics():
    """``types_extended_commit_*``, registered on first use."""
    global _EXTENDED_METRICS
    if _EXTENDED_METRICS is None:
        from ..libs import metrics as m

        _EXTENDED_METRICS = (
            m.counter("types_extended_commit_lanes_total",
                      "signatures VerifyExtendedCommit submitted, by kind "
                      "(vote / extension)"),
            m.counter("types_extended_commit_verify_total",
                      "VerifyExtendedCommit calls by result (ok / "
                      "bad_vote_sig / bad_ext_sig / refused)"))
    return _EXTENDED_METRICS


def VerifyExtendedCommit(chain_id: str, vals: ValidatorSet, block_id,
                         height: int, ext_commit, *,
                         backend: str | None = None,
                         patient: bool = False) -> None:
    """An ``ExtendedCommit`` verified whole, as one batch: upstream's
    ``ExtendedCommit.ToExtendedVoteSet`` (``types/block.go``), which adds
    every vote through ``VerifyVoteAndExtension`` (``types/vote.go``),
    with :func:`VerifyCommit`'s basics and tally.

    Size, height and block id must match; extensions must be where
    vote extensions put them (an extension signature on every for-block
    lane, nothing on a nil or absent one: ``ErrInvalidCommit``
    otherwise); EVERY non-absent lane's vote signature and every
    for-block lane's extension signature is verified, with no early exit
    at +2/3; the for-block power must exceed two thirds
    (``ErrNotEnoughVotingPower``).  A bad signature raises
    ``ErrInvalidSignature(idx)`` (the vote's) or its subclass
    :class:`ErrInvalidExtensionSignature` (the extension's), ``idx`` the
    FIRST validator in index order with a bad signature, its vote
    before its extension: what upstream's in-order ``AddVote`` reports.

    Both kinds of rows go to ONE ``verify_dense`` call over the
    validator table (every index twice: vote lanes, then extension lanes,
    each in validator order).  ``patient`` queues the dispatch behind one
    in flight instead of failing over to the host: the callers are a node
    starting or leaving blocksync, not the consensus loop
    (``crypto/batch._device_call``).  Mixed key types, aggregate lanes
    and odd signature sizes take the per-lane ``BatchVerifier``, which
    sends its Ed25519 lanes to the device and the rest to the host."""
    results = _extended_metrics()[1]
    with tracing.span("types.validation", "verify", entry="extended",
                      height=height, commits=1, lanes=ext_commit.size(),
                      ext_lanes=0, ok=True) as sp:
        try:
            _check_commit_basics(vals, ext_commit, height, block_id)
            needed = vals.total_voting_power() * 2 // 3
            if not _dense_verify_extended(
                    chain_id, vals, ext_commit, needed,
                    backend or _DEFAULT_BACKEND, patient, sp):
                _verify_extended_loop(chain_id, vals, ext_commit, needed,
                                      backend or _DEFAULT_BACKEND, sp)
        except ErrInvalidExtensionSignature:
            results.inc(result="bad_ext_sig")
            raise
        except ErrInvalidSignature:
            results.inc(result="bad_vote_sig")
            raise
        except CommitVerificationError:
            results.inc(result="refused")
            raise
        results.inc(result="ok")


_EXTENSIONS_MISPLACED = ("invalid commit: vote extensions are not where "
                         "extensions-enabled puts them (a signature on "
                         "every for-block lane, nothing on another)")


def _first_bad_extended(vote_scope, ext_scope, oks) -> Exception:
    """The error of the first validator in index order with a refuted
    lane, its vote lane before its extension lane; ``oks`` holds the
    vote lanes' verdicts, then the extension lanes'."""
    nv = len(vote_scope)
    idx, is_ext = min((int(vote_scope[j]), False) if j < nv
                      else (int(ext_scope[j - nv]), True)
                      for j, ok in enumerate(oks) if not ok)
    return (ErrInvalidExtensionSignature if is_ext
            else ErrInvalidSignature)(idx)


def _dense_verify_extended(chain_id: str, vals: ValidatorSet, ec,
                           needed: int, backend: str, patient: bool,
                           entry_span) -> bool:
    """Vectorized core of :func:`VerifyExtendedCommit`: the vote rows by
    the native builder (``rows`` span), the extension rows in numpy
    (``ext_rows`` span), then ONE dense verification over both.  Returns
    True when it handled the commit (raising as the entry documents),
    False when not applicable (a key that is not Ed25519, an aggregate,
    a signature that is not 64 bytes, no native library): the caller
    loops.  ``entry_span`` (the entry's open span, or None) learns the
    number of extension lanes."""
    import numpy as np

    from ..crypto import _native_ed25519 as nat
    from .commit import (BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_AGGREGATE,
                         BLOCK_ID_FLAG_COMMIT)

    dense = vals.dense()
    if dense is None or ec.agg_signature or ec.agg_signers \
            or not nat.available():
        return False
    pubs, powers = dense
    with tracing.span("types.validation", "rows", commits=1) as sp:
        commit = ec.stripped()
        cols = commit.dense_columns()
        if cols is None:
            return False
        flags, ts, sigmat = cols
        if len(flags) != len(powers) or \
                (flags == BLOCK_ID_FLAG_AGGREGATE).any():
            return False
        vote_scope = np.nonzero(flags != BLOCK_ID_FLAG_ABSENT)[0]
        pre_c, pre_n, post = commit.sign_bytes_templates(chain_id)
        vote_rows = nat.build_vote_sign_bytes(
            pre_c, pre_n, post, ts[vote_scope], flags[vote_scope])
        if vote_rows is None:
            return False
        if sp is not None:
            sp.attrs["lanes"] = int(vote_scope.size)
    with tracing.span("types.validation", "ext_rows", lanes=0) as sp:
        cols = ec.dense_columns()
        if cols is None:
            return False
        ext_lens, extmat, ext_sig_lens, ext_sigmat = cols[3:]
        commit_mask = flags == BLOCK_ID_FLAG_COMMIT
        ext_scope = np.nonzero(commit_mask)[0]
        if (ext_sig_lens[ext_scope] == 0).any() or \
                (ext_lens + ext_sig_lens)[~commit_mask].any():
            raise ErrInvalidCommit(_EXTENSIONS_MISPLACED)
        if (ext_sig_lens[ext_scope] != 64).any():
            return False
        tally = int(powers[ext_scope].sum())
        msgs, lens = _dense_extended_rows(
            vote_rows, _dense_build_extension_rows(
                ec.extension_sign_bytes_suffix(chain_id),
                extmat[ext_scope], ext_lens[ext_scope]))
        scope = np.concatenate([vote_scope, ext_scope])
        sigs = np.concatenate([sigmat[vote_scope], ext_sigmat[ext_scope]])
        pubs_sel = np.ascontiguousarray(pubs[scope])
        if sp is not None:
            sp.attrs["lanes"] = int(ext_scope.size)
        if entry_span is not None:
            entry_span.attrs["ext_lanes"] = int(ext_scope.size)
    if scope.size:
        res = cryptobatch.verify_dense(
            backend, pubs_sel, sigs, msgs, lens, valset_pubs=pubs,
            scope=scope, patient=patient)
        if res is None:
            return False
        lanes = _extended_metrics()[0]
        lanes.inc(int(vote_scope.size), kind="vote")
        lanes.inc(int(ext_scope.size), kind="extension")
        if not res[0]:
            raise _first_bad_extended(vote_scope, ext_scope, res[1])
    if tally <= needed:
        raise ErrNotEnoughVotingPower(
            f"tallied {tally} <= needed {needed}")
    return True


def _dense_build_extension_rows(suffix: bytes, extmat, ext_lens):
    """CanonicalVoteExtension sign-bytes rows (``canonical.
    canonical_vote_extension_sign_bytes``) for the given lanes'
    extensions: ``(msgs (k, stride) uint8 zero-padded, lens (k,))``.  A
    lane is its body's length, field 1 (omitted when empty, as proto3
    does), then ``suffix`` (height, round, chain id), so lanes of one
    extension length share everything but the extension itself and are
    written as one block."""
    import numpy as np

    from . import wire

    k = len(ext_lens)
    widest = int(ext_lens.max()) if k else 0
    stride = 5 + 6 + widest + len(suffix)
    msgs = np.zeros((k, stride), np.uint8)
    lens = np.zeros((k,), np.int64)
    tail = np.frombuffer(suffix, np.uint8)
    for n in np.unique(ext_lens):
        n = int(n)
        rows = np.nonzero(ext_lens == n)[0]
        field = wire.tag(1, wire.WIRE_BYTES) + wire.varint(n) if n else b""
        head = wire.varint(len(field) + n + len(suffix)) + field
        at = len(head)
        msgs[rows, :at] = np.frombuffer(head, np.uint8)
        msgs[rows, at:at + n] = extmat[rows, :n]
        msgs[rows, at + n:at + n + len(tail)] = tail
        lens[rows] = at + n + len(tail)
    return msgs, lens


def _dense_extended_rows(vote_rows, ext_rows):
    """Vote rows, then extension rows, as one zero-padded matrix."""
    import numpy as np

    (vm, vl), (em, el) = vote_rows, ext_rows
    stride = max(vm.shape[1], em.shape[1])
    msgs = np.zeros((len(vl) + len(el), stride), np.uint8)
    msgs[:len(vl), :vm.shape[1]] = vm
    msgs[len(vl):, :em.shape[1]] = em
    return msgs, np.concatenate([vl, el])


def _verify_extended_loop(chain_id: str, vals: ValidatorSet, ec,
                          needed: int, backend: str, entry_span) -> None:
    """Per-lane core of :func:`VerifyExtendedCommit` for what the dense
    rows do not cover (a key that is not Ed25519, a BLS aggregate, an odd
    signature size, no native builder): the same lanes in the same
    order through a ``BatchVerifier``, which resolves each lane's key
    type as the other entries' loops do."""
    commit = ec.stripped()
    # raises on any aggregate problem, so every AGGREGATE lane's vote is
    # proven by the aggregate; its extension signature is its own lane
    _verify_aggregate(chain_id, vals, commit, lookup_by_address=False)
    bv = cryptobatch.create_batch_verifier(backend)
    vote_scope, ext_scope, ext_items = [], [], []
    tally = 0
    for idx, e in enumerate(ec.extended_signatures):
        cs = e.commit_sig
        if (e.extension or e.extension_signature) if not cs.is_commit() \
                else not e.extension_signature:
            raise ErrInvalidCommit(_EXTENSIONS_MISPLACED)
        if cs.is_absent():
            continue
        val = vals.get_by_index(idx)
        if not cs.is_aggregate():
            bv.add(val.pub_key, commit.vote_sign_bytes_for(
                chain_id, idx, val.pub_key.type()), cs.signature)
            vote_scope.append(idx)
        if cs.is_commit():
            ext_items.append((val.pub_key,
                              ec.extension_sign_bytes(chain_id, idx),
                              e.extension_signature))
            ext_scope.append(idx)
            tally += val.voting_power
    for item in ext_items:
        bv.add(*item)
    if entry_span is not None:
        entry_span.attrs["ext_lanes"] = len(ext_scope)
    lanes = _extended_metrics()[0]
    lanes.inc(len(vote_scope), kind="vote")
    lanes.inc(len(ext_scope), kind="extension")
    if len(bv) > 0:
        ok, oks = bv.verify()
        if not ok:
            raise _first_bad_extended(vote_scope, ext_scope, oks)
    if tally <= needed:
        raise ErrNotEnoughVotingPower(
            f"tallied {tally} <= needed {needed}")

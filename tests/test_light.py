"""Light client: adjacent/non-adjacent verification, batched sequential
sync (BASELINE configs[3]: 1000 headers), bisection, detector
(reference: ``light/verifier_test.go``, ``light/client_test.go``,
``light/detector_test.go``)."""

import asyncio

import pytest

from cometbft_tpu.light import (Client, DivergenceError,
                                ErrInvalidHeader, ErrNewValSetCantBeTrusted,
                                LightBlock, LightClientError, Provider,
                                SEQUENTIAL, TrustOptions, TrustedStore,
                                verify_adjacent, verify_non_adjacent,
                                verify_sequential_batched)
from cometbft_tpu.light.provider import ErrLightBlockNotFound
from cometbft_tpu.testing import make_light_chain
from cometbft_tpu.types.validation import ErrBatchItemInvalid

pytestmark = pytest.mark.timeout(120)

CHAIN = "light-chain"
PERIOD = 3600 * 1_000_000_000       # 1 h trusting period


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _now(chain):
    return chain[-1].header.time_ns + 60 * 1_000_000_000


class ChainProvider(Provider):
    """Serves a pre-generated chain; counts fetches, records reported
    evidence (the detector's two-sided dispatch)."""

    def __init__(self, chain, name="prov"):
        self.by_height = {lb.height: lb for lb in chain}
        self.tip = max(self.by_height)
        self.name = name
        self.fetches = 0
        self.reported = []

    def id(self):
        return self.name

    async def light_block(self, height):
        self.fetches += 1
        if height == 0:
            height = self.tip
        lb = self.by_height.get(height)
        if lb is None:
            raise ErrLightBlockNotFound(f"{self.name}: {height}")
        return lb

    async def report_evidence(self, evidence):
        self.reported.append(evidence)


def test_verify_adjacent_ok_and_bad_linkage():
    chain = make_light_chain(3)
    verify_adjacent(CHAIN, chain[0], chain[1], PERIOD, _now(chain),
                    backend="cpu")
    # non-consecutive heights refuse the adjacent path
    with pytest.raises(ErrInvalidHeader):
        verify_adjacent(CHAIN, chain[0], chain[2], PERIOD, _now(chain),
                        backend="cpu")


def test_verify_adjacent_rejects_forged_valset():
    chain = make_light_chain(3)
    forged = make_light_chain(3, seed=b"other")
    # same heights, different keys: next_validators_hash cannot match
    with pytest.raises(ErrInvalidHeader):
        verify_adjacent(CHAIN, chain[0], forged[1], PERIOD, _now(chain),
                        backend="cpu")


def test_verify_non_adjacent_skip_ok():
    chain = make_light_chain(50)
    verify_non_adjacent(CHAIN, chain[0], chain[49], PERIOD, _now(chain),
                        backend="cpu")


def test_verify_non_adjacent_rotated_set_cant_be_trusted():
    # one validator of 4 swapped every block: after 3 rotations the
    # original set retains < 1/3 overlap power... rotate 3 of 4 by height 4
    chain = make_light_chain(20, n_vals=4, rotate_every=1)
    with pytest.raises(ErrNewValSetCantBeTrusted):
        verify_non_adjacent(CHAIN, chain[0], chain[15], PERIOD,
                            _now(chain), backend="cpu")


def test_expired_trusting_period_rejected():
    chain = make_light_chain(5)
    late = chain[0].header.time_ns + PERIOD + 1
    with pytest.raises(LightClientError):
        verify_adjacent(CHAIN, chain[0], chain[1], PERIOD, late,
                        backend="cpu")


def test_sequential_batched_1000_headers():
    """BASELINE configs[3]: 1000-header sequential sync on the batched
    path — correctness here, device timing in the bench."""
    chain = make_light_chain(1000, n_vals=8)
    verify_sequential_batched(CHAIN, chain[0], chain[1:], PERIOD,
                              _now(chain), backend="cpu")


def test_sequential_batched_flags_corrupt_header():
    chain = make_light_chain(40, n_vals=4)
    bad = chain[25]
    sig = bytearray(bad.commit.signatures[0].signature)
    sig[10] ^= 1
    bad.commit.signatures[0].signature = bytes(sig)
    with pytest.raises(ErrBatchItemInvalid) as exc:
        verify_sequential_batched(CHAIN, chain[0], chain[1:], PERIOD,
                                  _now(chain), backend="cpu")
    assert exc.value.height == bad.height


def test_client_skipping_sync_is_sublinear():
    chain = make_light_chain(200, n_vals=4, rotate_every=10)
    primary = ChainProvider(chain)
    client = Client(CHAIN, TrustOptions(PERIOD, 1, chain[0].header.hash()),
                    primary, backend="cpu",
                    now_ns=lambda: _now(chain))

    async def main():
        lb = await client.verify_light_block_at_height(200)
        assert lb.header.hash() == chain[199].header.hash()
        return True

    assert run(main())
    # bisection fetches far fewer than one header per height
    assert primary.fetches < 60, primary.fetches


def test_client_sequential_mode():
    chain = make_light_chain(60, n_vals=4)
    primary = ChainProvider(chain)
    client = Client(CHAIN, TrustOptions(PERIOD, 1, chain[0].header.hash()),
                    primary, mode=SEQUENTIAL, backend="cpu",
                    now_ns=lambda: _now(chain))

    async def main():
        lb = await client.verify_light_block_at_height(60)
        assert lb.header.hash() == chain[59].header.hash()
        # every intermediate header is now trusted
        assert client.store.get(30) is not None
        return True

    assert run(main())


def test_client_detects_forked_witness():
    chain = make_light_chain(30, n_vals=4)
    fork = make_light_chain(30, n_vals=4, seed=b"fork")
    primary = ChainProvider(chain, "primary")
    witness = ChainProvider(chain[:20] + fork[20:], "witness")
    client = Client(CHAIN, TrustOptions(PERIOD, 1, chain[0].header.hash()),
                    primary, witnesses=[witness], backend="cpu",
                    now_ns=lambda: _now(chain))

    async def main():
        with pytest.raises(DivergenceError) as exc:
            await client.verify_light_block_at_height(25)
        assert exc.value.witness_id == "witness"
        assert exc.value.evidence is not None
        return True

    assert run(main())


def test_detector_trace_walk_two_sided_evidence():
    """A fork at height H with divergence point H-k
    must yield evidence whose common_height is the TRUE fork height
    (trace examination, detector.go:285), two-sided evidence, and
    delivery to both honest parties — the witness receives the case
    against the primary, the primary the case against the witness."""
    H, F = 30, 22                       # tip and fork heights
    chain = make_light_chain(H, n_vals=4)
    forked = make_light_chain(H, n_vals=4, fork_at=F, fork_skew_ns=777)
    # sanity: shared validly-signed prefix, divergent suffix
    assert chain[F - 1].header.hash() == forked[F - 1].header.hash()
    assert chain[F].header.hash() != forked[F].header.hash()

    primary = ChainProvider(chain, "primary")
    witness = ChainProvider(forked, "witness")
    client = Client(CHAIN, TrustOptions(PERIOD, 1, chain[0].header.hash()),
                    primary, witnesses=[witness], mode=SEQUENTIAL,
                    backend="cpu", now_ns=lambda: _now(chain))

    async def main():
        with pytest.raises(DivergenceError) as exc:
            await client.verify_light_block_at_height(H)
        e = exc.value
        assert e.common_height == F
        # primary's side of the fork at the first divergent height
        assert e.evidence_against_primary.common_height == F
        assert e.evidence_against_primary.conflicting_height == F + 1
        assert e.evidence_against_primary.conflicting_header_hash == \
            chain[F].header.hash()
        # witness's side
        assert e.evidence_against_witness.common_height == F
        assert e.evidence_against_witness.conflicting_height == F + 1
        assert e.evidence_against_witness.conflicting_header_hash == \
            forked[F].header.hash()
        # each honest party received the case against the other side
        assert [ev.conflicting_header_hash for ev in witness.reported] == \
            [chain[F].header.hash()]
        assert [ev.conflicting_header_hash for ev in primary.reported] == \
            [forked[F].header.hash()]
        # nothing divergent was persisted as trusted
        assert client.store.get(H) is None
        return True

    assert run(main())


def test_detector_drops_persistently_lagging_witness():
    """A witness that can never serve the height is
    struck out after MAX_WITNESS_LAG_STRIKES consecutive misses instead
    of being retried forever; an agreeing witness survives."""
    from cometbft_tpu.light.detector import (MAX_WITNESS_LAG_STRIKES,
                                             detect_divergence)

    chain = make_light_chain(10, n_vals=4)
    primary = ChainProvider(chain, "primary")
    laggard = ChainProvider(chain[:2], "laggard")     # tip stuck at 2
    healthy = ChainProvider(chain, "healthy")
    client = Client(CHAIN, TrustOptions(PERIOD, 1, chain[0].header.hash()),
                    primary, witnesses=[laggard, healthy], backend="cpu",
                    now_ns=lambda: _now(chain))

    async def main():
        client.store.save(chain[0])
        for i in range(MAX_WITNESS_LAG_STRIKES):
            assert laggard in client.witnesses, f"dropped too early ({i})"
            await detect_divergence(client, chain[7], _now(chain))
        assert laggard not in client.witnesses
        assert healthy in client.witnesses
        return True

    assert run(main())


def test_client_backwards_verification():
    chain = make_light_chain(40, n_vals=4)
    primary = ChainProvider(chain)
    client = Client(CHAIN, TrustOptions(PERIOD, 30,
                                        chain[29].header.hash()),
                    primary, backend="cpu", now_ns=lambda: _now(chain))

    async def main():
        await client.initialize()
        lb = await client.verify_light_block_at_height(10)
        assert lb.header.hash() == chain[9].header.hash()
        return True

    assert run(main())


def test_client_prunes_store_to_pruning_size():
    """light/client.go:26 defaultPruningSize: the trusted store keeps a
    bounded number of light blocks as sync advances."""
    chain = make_light_chain(20)
    primary = ChainProvider(chain, "primary")

    async def main():
        client = Client(CHAIN,
                        TrustOptions(PERIOD, 1, chain[0].header.hash()),
                        primary, mode=SEQUENTIAL, backend="cpu",
                        pruning_size=5, now_ns=lambda: _now(chain))
        await client.initialize()
        await client.verify_light_block_at_height(20)
        stored = [h for h in range(1, 21)
                  if client.store.get(h) is not None]
        assert len(stored) <= 5, stored
        assert client.latest_trusted().height == 20
        return True

    assert run(main())

"""Mempool admission concurrency: check_tx no longer
serializes on one lock across the app round-trip — one slow CheckTx must
not stall other admissions — while the executor's update/flush critical
section stays exclusive against in-flight admissions."""

import asyncio
import time

import pytest

from cometbft_tpu.mempool.clist_mempool import CListMempool, TxRejectedError

pytestmark = pytest.mark.timeout(60)


class SlowCheckApp:
    """CheckTx sleeps per-tx as directed; records concurrency level."""

    def __init__(self):
        self.inflight = 0
        self.max_inflight = 0
        self.checked: list[bytes] = []

    async def check_tx(self, tx: bytes, recheck: bool = False):
        from cometbft_tpu.abci.types import CheckTxResponse

        self.inflight += 1
        self.max_inflight = max(self.max_inflight, self.inflight)
        delay = 0.3 if tx.startswith(b"slow") else 0.01
        await asyncio.sleep(delay)
        self.inflight -= 1
        self.checked.append(tx)
        return CheckTxResponse(code=0, gas_wanted=1)


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def test_slow_checktx_does_not_stall_admission():
    """10 fast admissions complete while one slow CheckTx is in flight:
    total wall-clock ~= the slow call, not the sum."""

    async def main():
        app = SlowCheckApp()
        mp = CListMempool(app)
        t0 = time.perf_counter()
        txs = [b"slow-0"] + [b"fast-%d" % i for i in range(10)]
        await asyncio.gather(*(mp.check_tx(tx) for tx in txs))
        dt = time.perf_counter() - t0
        assert mp.size() == 11
        assert app.max_inflight > 1, "admissions were serialized"
        # serialized would be ~0.3 + 10*0.01 = 0.4s minimum; pipelined
        # is ~0.3s.  Assert well under the serial bound.
        assert dt < 0.38, dt
        return True

    assert run(main())


def test_update_excludes_inflight_admissions():
    """The executor's lock() (writer) waits for in-flight admissions and
    blocks new ones, so update/recheck sees a quiescent mempool."""

    async def main():
        app = SlowCheckApp()
        mp = CListMempool(app)
        await mp.check_tx(b"fast-pre")

        adm = asyncio.ensure_future(mp.check_tx(b"slow-1"))
        await asyncio.sleep(0.05)          # slow admission now in flight
        t0 = time.perf_counter()
        async with mp.lock():
            # writer acquired only after the in-flight admission finished
            waited = time.perf_counter() - t0
            assert waited > 0.15, waited
            late = asyncio.ensure_future(mp.check_tx(b"fast-late"))
            await asyncio.sleep(0.05)
            assert not late.done(), "admission ran during the critical section"
            await mp.update(2, [b"fast-pre"], [])
        await asyncio.gather(adm, late)
        assert mp.size() == 2              # slow-1 + fast-late survive
        assert mp.height == 2
        return True

    assert run(main())


def test_full_mempool_rechecked_after_app_roundtrip():
    """The capacity check re-runs after the await: concurrent admissions
    racing past the pre-check can't overfill the pool."""

    async def main():
        app = SlowCheckApp()
        mp = CListMempool(app, max_txs=3)
        results = await asyncio.gather(
            *(mp.check_tx(b"tx-%d" % i) for i in range(6)),
            return_exceptions=True)
        rejected = [r for r in results if isinstance(r, TxRejectedError)]
        assert mp.size() == 3
        assert len(rejected) == 3
        assert all("full" in str(r) for r in rejected)
        return True

    assert run(main())


def test_arrival_fifo_preserved_under_out_of_order_completion():
    """Reap/gossip order follows ARRIVAL order even when the app answers
    CheckTx out of order (the slow tx arrives first, completes last)."""

    async def main():
        app = SlowCheckApp()
        mp = CListMempool(app)
        txs = [b"slow-first"] + [b"fast-%d" % i for i in range(5)]
        await asyncio.gather(*(mp.check_tx(tx) for tx in txs))
        # dict insertion order is completion order (slow-first is LAST)…
        assert app.checked[-1] == b"slow-first"
        # …but reaping restores arrival order
        assert mp.reap_max_txs(10) == txs
        assert mp.contents() == txs
        assert mp.reap_max_bytes_max_gas(-1, -1) == txs
        return True

    assert run(main())

"""BLS native-backend robustness fuzz: malformed/garbage/mutated inputs
must never verify and never crash; every native accept must be a
Python-oracle accept (sampled)."""
import os, sys, random, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, __import__("os").path.join(__import__("os").path.dirname(__file__), ".."))
from cometbft_tpu.crypto import _bls12381_py as B
from cometbft_tpu.crypto import bls12381 as keys

n = keys._NativeBackend()
rng = random.Random(20260731)
sk = rng.randrange(1, B.R)
pk = B.sk_to_pk(sk)
msg = b"fuzz-msg"
sig = B.sign(sk, msg)
assert n.verify(pk, msg, sig)

t0 = time.time()
trials = accepts = 0
checked_cross = 0
N = int(sys.argv[1]) if len(sys.argv) > 1 else 20000
for i in range(N):
    mode = rng.randrange(6)
    p, m, s = pk, msg, sig
    if mode == 0:        # random garbage sig
        s = rng.randbytes(96)
    elif mode == 1:      # random garbage pk
        p = rng.randbytes(48)
    elif mode == 2:      # bitflip sig
        b_ = bytearray(sig); b_[rng.randrange(96)] ^= 1 << rng.randrange(8)
        s = bytes(b_)
    elif mode == 3:      # bitflip pk
        b_ = bytearray(pk); b_[rng.randrange(48)] ^= 1 << rng.randrange(8)
        p = bytes(b_)
    elif mode == 4:      # msg mutation
        m = msg + bytes([rng.randrange(256)])
    else:                # flag-byte adversarial: force comp/inf/sign bits
        b_ = bytearray(sig); b_[0] = rng.randrange(256)
        s = bytes(b_)
    ok = n.verify(p, m, s)
    trials += 1
    if ok:
        accepts += 1
        # any accept of a mutated input must agree with the oracle
        assert B.verify(p, m, s), (i, mode)
        checked_cross += 1
        # the only legitimate accepts are identity mutations
        assert (p, m, s) == (pk, msg, sig), ("non-identity accept!", i, mode)

# ---- aggregate path: mutated aggregates must never fast-verify ----
# a 4-signer cohort on one shared message (the commit-aggregation shape)
sks = [rng.randrange(1, B.R) for _ in range(4)]
pks = [B.sk_to_pk(k) for k in sks]
amsg = b"agg-fuzz-msg"
asigs = [B.sign(k, amsg) for k in sks]
agg_sig = keys.aggregate_signatures(asigs, check=False)
agg_pk = keys.aggregate_pubkeys(pks)
assert keys.fast_aggregate_verify(pks, amsg, agg_sig)
assert n.verify(agg_pk, amsg, agg_sig)

agg_trials = agg_accepts = 0
AN = max(N // 4, 1000)
for i in range(AN):
    mode = rng.randrange(6)
    ps, m, s = list(pks), amsg, agg_sig
    if mode == 0:        # bitflip aggregate sig
        b_ = bytearray(s); b_[rng.randrange(96)] ^= 1 << rng.randrange(8)
        s = bytes(b_)
    elif mode == 1:      # drop a signer from the claimed cohort
        ps.pop(rng.randrange(len(ps)))
    elif mode == 2:      # duplicate a signer (bitmap can't, the API must)
        ps.append(ps[rng.randrange(len(ps))])
    elif mode == 3:      # swap in a fresh non-signer key
        ps[rng.randrange(len(ps))] = B.sk_to_pk(rng.randrange(1, B.R))
    elif mode == 4:      # msg mutation under the real aggregate
        m = amsg + bytes([rng.randrange(256)])
    else:                # substitute one individual sig for the aggregate
        s = asigs[rng.randrange(len(asigs))]
    ok = keys.fast_aggregate_verify(ps, m, s)   # documented never-raises
    agg_trials += 1
    if ok:
        agg_accepts += 1
        assert (ps, m, s) == (pks, amsg, agg_sig), \
            ("non-identity aggregate accept!", i, mode)

print(f"{trials} mutated-input trials: {accepts} accepts "
      f"(all identity + oracle-confirmed), "
      f"{agg_trials} mutated-aggregate trials: {agg_accepts} accepts, "
      f"0 crashes, {time.time()-t0:.0f}s")

import os, sys, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import numpy as np
import jax, jax.numpy as jnp
from cometbft_tpu.ops import fe

print("device:", jax.devices()[0])
if os.environ.get("KERNLAYOUT_REQUIRE_TPU"):
    # a tpu-tagged artifact must never hold silent-CPU-fallback numbers
    assert jax.devices()[0].platform != "cpu", \
        "KERNLAYOUT_REQUIRE_TPU set but jax fell back to CPU"
B = 10240
rng = np.random.default_rng(7)
an = rng.integers(0, 8191, (B, 20), dtype=np.int32)
bn = rng.integers(0, 8191, (B, 20), dtype=np.int32)
a = jnp.asarray(an); b = jnp.asarray(bn)
aT = jnp.asarray(an.T.copy()); bT = jnp.asarray(bn.T.copy())

def bench(name, f, *args, n=5):
    out = f(*args); jax.block_until_ready(out)
    ts = []
    for _ in range(n):
        t0 = time.perf_counter(); jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t0)
    print(f"{name:44s} {min(ts)*1e3:9.3f} ms", flush=True)

MASK = fe.MASK; RADIX = fe.RADIX; FOLD = fe.FOLD; NL = fe.NLIMBS; NC = fe.NCOLS

# --- reference: raw elementwise throughput, full-lane shape
c128 = jnp.asarray(rng.integers(0, 2**30, (B, 128), dtype=np.int32))
@jax.jit
def raw100(x):
    return jax.lax.fori_loop(0, 100, lambda _, v: (v * 3 + 7) & 0x7fffffff, x)
bench("100 mul-add elementwise (B,128)", raw100, c128)

# --- 20 chained muls, current einsum layout (B,20)
@jax.jit
def mul20_cur(a, b):
    return jax.lax.fori_loop(0, 20, lambda _, x: fe.mul(x, b), a)
bench("20 fe.mul einsum (B,20)", mul20_cur, a, b)

# --- shifted-accumulation mul, batch-major (B,20)
def mul_shift(a, b):
    out = jnp.zeros(a.shape[:-1] + (NC,), jnp.int32)
    for i in range(NL):
        out = out.at[..., i:i + NL].add(a[..., i:i + 1] * b)
    return fe._reduce_columns(out)
@jax.jit
def mul20_shift(a, b):
    return jax.lax.fori_loop(0, 20, lambda _, x: mul_shift(x, b), a)
bench("20 fe.mul shifted-acc (B,20)", mul20_shift, a, b)

# --- limb-major (20,B): shifted accumulation + carry
def wrap_carry_T(x, passes):
    for _ in range(passes):
        lo = x & MASK
        hi = x >> RADIX
        wrapped = jnp.concatenate([hi[-1:] * FOLD, hi[:-1]], axis=0)
        x = lo + wrapped
    return x

def reduce_cols_T(cols):          # (39,B) -> (20,B)
    lo = cols & MASK
    hi = cols >> RADIX
    limbs40 = jnp.concatenate([lo, jnp.zeros_like(lo[:1])], axis=0
                              ).at[1:].add(hi)
    folded = limbs40[:NL] + FOLD * limbs40[NL:]
    return wrap_carry_T(folded, 3)

def mul_T(a, b):                  # (20,B)x(20,B) -> (20,B)
    out = jnp.zeros((NC,) + a.shape[1:], jnp.int32)
    for i in range(NL):
        out = out.at[i:i + NL].add(a[i:i + 1] * b)
    return reduce_cols_T(out)

@jax.jit
def mul20_T(a, b):
    return jax.lax.fori_loop(0, 20, lambda _, x: mul_T(x, b), a)
out = bench("20 fe.mul shifted-acc (20,B)", mul20_T, aT, bT)

# check correctness of limb-major chain vs batch-major einsum chain
r1 = np.asarray(jax.jit(mul20_cur)(a, b))
r2 = np.asarray(jax.jit(mul20_T)(aT, bT)).T
v1 = [fe.int_from_limbs(r1[i]) % fe.P_INT for i in range(3)]
v2 = [fe.int_from_limbs(r2[i]) % fe.P_INT for i in range(3)]
assert v1 == v2, "limb-major mul diverges!"
print("limb-major chain correct")

# --- einsum formulation in limb-major: cols[k,b] = sum_i a[i,b] * bT_toeplitz
IDX = np.asarray(fe._MUL_IDX); MSK = np.asarray(fe._MUL_MSK)
@jax.jit
def mul20_T_einsum(a, b):
    def one(x, b):
        bmat = b[jnp.asarray(IDX)] * jnp.asarray(MSK)[..., None]   # (20,39,B)
        cols = jnp.einsum("ib,ikb->kb", x, bmat,
                          preferred_element_type=jnp.int32)
        return reduce_cols_T(cols)
    return jax.lax.fori_loop(0, 20, lambda _, x: one(x, b), a)
bench("20 fe.mul einsum (20,B)", mul20_T_einsum, aT, bT)

# --- add / carry costs in both layouts
@jax.jit
def add100(a, b):
    return jax.lax.fori_loop(0, 100, lambda _, x: fe.add(x, b), a)
bench("100 fe.add (B,20)", add100, a, b)
@jax.jit
def add100T(a, b):
    return jax.lax.fori_loop(0, 100, lambda _, x: wrap_carry_T(x + b, 1), a)
bench("100 add+carry (20,B)", add100T, aT, bT)


# ---- full-pipeline timing: production (limb-major) per-lane kernel ----
# (the batch-major full pipeline was deleted when the limb-major layout
# was promoted in round 5)
from cometbft_tpu.ops import ed25519 as _prod_kernel
from cometbft_tpu.testing import dense_signature_batch as _dsb

for B2 in (1024, 4096):
    args, _ = _dsb(B2, msg_len=120, seed=2024)
    args = jax.device_put(args)
    f_prod = jax.jit(_prod_kernel.verify_padded)
    o1 = np.asarray(f_prod(*args))
    assert o1.all(), "production kernel rejected valid batch!"
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(f_prod(*args))
        ts.append(time.perf_counter() - t0)
    print(f"verify_padded straus       B={B2:5d} {min(ts)*1e3:9.2f} ms "
          f"({B2/min(ts):8.0f} sigs/s)", flush=True)

# ---- RLC batch kernel (round-5 structural rework), if present ---------
try:
    from cometbft_tpu.ops import rlc as _rlc
except ImportError:
    _rlc = None
if _rlc is not None:
    for B2 in (1024, 4096):
        args, _ = _dsb(B2, msg_len=120, seed=2024)
        z = _rlc.host_rlc_coeffs(B2, np.ones(B2, bool))
        rargs = jax.device_put(args + (z,))
        f_rlc = jax.jit(_rlc.verify_batch_rlc)
        from cometbft_tpu.crypto import rlc_finish
        ok, _ = rlc_finish.finish(f_rlc(*rargs))    # the host folds the sums
        assert ok, "RLC kernel rejected valid batch!"
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f_rlc(*rargs))
            ts.append(time.perf_counter() - t0)
        print(f"verify_batch rlc           B={B2:5d} {min(ts)*1e3:9.2f} ms "
              f"({B2/min(ts):8.0f} sigs/s)", flush=True)

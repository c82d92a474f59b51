"""Child process for test_multihost.py: one host of a 2-process
jax.distributed CPU cluster.  Each host contributes 2 virtual devices;
the global mesh spans 4.  Runs one lane-sharded verification step
through the production ``parallel/mesh.py`` path and prints MULTIHOST_OK
on success."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

port, proc_id = sys.argv[1], int(sys.argv[2])

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2").strip()

from cometbft_tpu.jaxenv import enable_compile_cache

enable_compile_cache()

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from cometbft_tpu.parallel.mesh import init_multihost, sharded_verify_fn
from cometbft_tpu.testing import dense_signature_batch

mesh = init_multihost(coordinator=f"127.0.0.1:{port}",
                      num_processes=2, process_id=proc_id)
n_global = mesh.devices.size
assert n_global == 4, f"expected 4 global devices, got {n_global}"
assert jax.process_count() == 2

# identical batch on both hosts; each host materializes only its
# addressable shards of the global arrays
args, _ = dense_signature_batch(8, msg_len=80, seed=5)


def to_global(a):
    a = np.asarray(a)
    spec = P(*(("batch",) + (None,) * (a.ndim - 1)))
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(a.shape, sharding,
                                        lambda idx: a[idx])


out = sharded_verify_fn(mesh)(*[to_global(a) for a in args])
local = np.concatenate(
    [np.asarray(s.data).ravel() for s in out.addressable_shards])
assert local.all(), "sharded verify rejected valid signatures"
print(f"MULTIHOST_OK {proc_id}", flush=True)

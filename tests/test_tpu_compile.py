"""The main-path kernels compile for a TPU v5e at real widths.

Nothing runs: the chip is described, not attached
(``jax.experimental.topologies``), and XLA:TPU compiles each program for
it.  What the chip's compiler refuses — a program that does not fit the
device, a sharding it cannot partition — fails here, at no chip time.
Shapes come from ``aotbundle.sample_args`` (the dispatch's own host
packers), so this rehearsal and the live dispatch cannot drift.

This is the ONE file that describes a TPU topology, and it does so inside
a fixture: only the worker that runs it loads the TPU library.
"""

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

from cometbft_tpu.crypto import aotbundle
from cometbft_tpu.crypto import plan as deviceplan
from cometbft_tpu.crypto.plan import CompileBucket
from cometbft_tpu.parallel import mesh as M

COMPILE_TIMEOUT_S = 420         # ~1 min per shape; conftest default is 180


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def compile_bucket(topo, bucket: CompileBucket, mesh_devices: int = 0):
    """XLA:TPU-compile one plan bucket for the described chip (or for a
    1-D mesh of its first ``mesh_devices`` chips, through the same
    ``sharded_kernel`` authority the live dispatch uses)."""
    args = aotbundle.sample_args(bucket)
    if mesh_devices:
        jfn = M.sharded_kernel(bucket.kind,
                               list(topo.devices[:mesh_devices]))
        ins, _, _ = deviceplan.kernel_shardings(
            bucket.kind, M.batch_mesh(topo.devices[:mesh_devices]))
    else:
        jfn = jax.jit(aotbundle._kernel_fn(bucket.kind))
        ins = (SingleDeviceSharding(topo.devices[0]),) * len(args)
    shapes = tuple(
        jax.tree_util.tree_map(
            lambda a, s=s: jax.ShapeDtypeStruct(
                np.shape(a), np.asarray(a).dtype, sharding=s), arg)
        for arg, s in zip(args, ins))
    return jfn.lower(*shapes).compile()


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes + mem.generated_code_size_in_bytes)
    assert total < 16 * 2**30, mem      # one v5e chip: 16 GB of HBM


@pytest.mark.timeout(COMPILE_TIMEOUT_S)
@pytest.mark.parametrize("bucket", [
    CompileBucket("verify", 4096, 2),
    # the route every real commit takes: 10k validators -> 16384-row
    # table, 4096-lane chunks
    CompileBucket("gather", 4096, 2, table_rows=16384),
    CompileBucket("merkle_level", 4096),
], ids=lambda b: b.key)
def test_one_chip_bucket_compiles_for_v5e(topo, bucket):
    _assert_fits(compile_bucket(topo, bucket))


@pytest.mark.timeout(COMPILE_TIMEOUT_S)
def test_sharded_rlc_compiles_for_four_v5e_chips(topo):
    """The lane-sharded RLC sums over a 4-chip mesh: the one main-path
    program whose partition needs a collective (the per-device partial
    window sums cross the interconnect)."""
    compiled = compile_bucket(topo, CompileBucket("rlc", 4096, 2),
                              mesh_devices=4)
    _assert_fits(compiled)
    hlo = compiled.as_text()
    assert "all-gather" in hlo or "all-reduce" in hlo \
        or "collective-permute" in hlo or "all-to-all" in hlo
    # the program ends at the window sums: one replicated int32 array
    # for the host to fold (crypto/rlc_finish.py), no one-lane ladder
    from cometbft_tpu.crypto import rlc_finish
    from cometbft_tpu.ops import rlc

    (out,) = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert out.is_fully_replicated
    assert "s32[20,386]" in hlo and rlc_finish.SHAPE == (20, 386)
    assert "rlc_ladder" not in hlo and "rlc_sums" in hlo
    assert not hasattr(rlc, "_rlc_ladder")
    # each lane's window entry is selected, not gathered: the sheets'
    # construction holds neither a gather nor a transposed table
    sheet = [ln for ln in hlo.splitlines() if "/rlc_sheet/" in ln]
    assert sheet
    assert not [ln for ln in sheet if " gather(" in ln or " transpose(" in ln]

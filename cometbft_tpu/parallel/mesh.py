"""Mesh construction and the sharded kernel authority.

Scaling model (BASELINE.json: "sharded over chips with pjit"): one mesh
axis (``DevicePlan.mesh_axis``, default ``batch``) over the plan's
devices; every per-lane input array shards on its leading axis, cached
valset tables replicate, and outputs shard (per-lane verdicts) or
replicate (RLC scalars).  XLA inserts no collectives for the per-lane
kernels — lanes are independent — and the RLC reduction folds
per-device partial sums with one tiny combine, so the step scales
linearly over ICI-connected chips and the driver's virtual CPU mesh
alike.

:func:`sharded_kernel` is the single authority every multi-device
compile goes through: ``crypto/batch.py``'s ``_compiled_*_sharded``
factories and ``crypto/aotbundle.py``'s sharded bundle build both call
it, with in/out shardings and donated argnums realized from the
``DevicePlan``'s :data:`~..crypto.plan.KERNEL_SHARDINGS` labels — so
the live dispatch and the serialized executable can never disagree
about argument layout.
"""

from __future__ import annotations

import warnings

import jax
import numpy as np
from jax.sharding import Mesh


def batch_mesh(devices=None) -> Mesh:
    """1-D mesh over the given (default: all) devices, named by the
    active plan's mesh axis."""
    from ..crypto import plan as deviceplan

    devs = np.array(devices if devices is not None else jax.devices())
    return Mesh(devs, axis_names=(deviceplan.active().mesh_axis,))


def init_multihost(coordinator: str | None = None,
                   num_processes: int | None = None,
                   process_id: int | None = None) -> Mesh:
    """Multi-host mesh: initialize the jax distributed runtime (every
    host runs this with the same coordinator) and return the global
    batch mesh spanning all hosts' devices.

    The reference scales hosts with its own TCP fabric (p2p) and has no
    device fabric; here host networking is likewise p2p/RPC, while the
    *verification batch* shards over every chip on every host — XLA
    routes any cross-host traffic over ICI/DCN, and since lanes are
    independent the step stays collective-free.  Args default to the
    standard env vars (JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES,
    JAX_PROCESS_ID) so launchers can configure it without code."""
    import os

    coordinator = coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if coordinator is None and (num_processes is not None
                                or process_id is not None):
        raise ValueError("num_processes/process_id given without a "
                         "coordinator address")
    if coordinator:
        if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
            num_processes = int(os.environ["JAX_NUM_PROCESSES"])
        if process_id is None and "JAX_PROCESS_ID" in os.environ:
            process_id = int(os.environ["JAX_PROCESS_ID"])
        if not jax.distributed.is_initialized():
            # None process args let jax auto-detect cluster membership
            # (TPU pods)
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id)
    return batch_mesh()


def _kernel_target(kind: str, mesh: Mesh):
    """The python callable a sharded program of ``kind`` compiles."""
    from ..ops import ed25519 as _ed, rlc as _rlc, sha256 as _sha

    if kind == "verify":
        return _ed.verify_padded
    if kind == "gather":
        return _ed.verify_padded_gather
    if kind == "rlc":
        return _rlc.make_verify_batch_rlc_sharded(mesh)
    if kind == "rlc_gather":
        return _rlc.make_verify_batch_rlc_sharded(mesh, gather=True)
    if kind == "merkle_level":
        return _sha.merkle_inner_level
    raise KeyError(f"no sharded kernel target for {kind!r}")


def sharded_kernel(kind: str, devices=None, mesh: Mesh | None = None):
    """jit of the ``kind`` kernel as ONE sharded program over ``mesh``
    (built from ``devices`` when not given): in/out shardings and
    donated argnums realized from the plan's sharding labels.  The mesh
    size must divide the lane count (each device takes an equal
    contiguous slab).  Donation lets XLA reuse the staged input buffers
    for outputs — dispatch always re-transfers from host numpy, so no
    caller observes the aliasing."""
    from ..crypto import plan as deviceplan

    if mesh is None:
        mesh = batch_mesh(devices)
    ins, out, donate = deviceplan.kernel_shardings(kind, mesh)
    return jax.jit(
        _kernel_target(kind, mesh),
        in_shardings=ins,
        out_shardings=out,
        donate_argnums=donate,
    )


def sharded_verify_fn(mesh: Mesh):
    """jit of the ed25519 verify kernel with every arg sharded on the
    batch axis of ``mesh`` (kept as the historical name for the plain
    verify program; delegates to :func:`sharded_kernel`)."""
    return sharded_kernel("verify", mesh=mesh)


# CPU host-device emulation cannot alias most donated buffers; jax warns
# per-compile.  Donation is correct regardless (inputs are staging
# copies), so the warning is noise on every CI run.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

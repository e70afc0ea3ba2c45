"""Device-mesh helpers: the substrate for every parallelism strategy.

Reference parity: the reference's parallelism is device *enumeration* —
ParallelWrapper spawns one trainer thread per device
(parallelism/ParallelWrapper.java:460-468), Spark enumerates executors, the
Aeron parameter server enumerates endpoints. TPU-native, the analogous
object is a `jax.sharding.Mesh`: a named, possibly multi-host grid of
devices over which shardings are expressed and XLA inserts collectives
(psum over ICI/DCN) automatically.

Axis conventions used throughout this framework:
  * "data"  — data parallelism (batch axis). The reference's ONLY strategy.
  * "model" — tensor parallelism (feature/hidden axis). New scope.
  * "seq"   — sequence/context parallelism for long sequences. New scope.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
STAGE_AXIS = "stage"


def create_mesh(shape: Optional[Sequence[int]] = None,
                axis_names: Sequence[str] = (DATA_AXIS,),
                devices=None) -> Mesh:
    """Build a Mesh over the given (or all) devices.

    `shape=None` puts every device on the first axis (pure DP — the
    reference ParallelWrapper default of "all devices in the box")."""
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = [len(devices)] + [1] * (len(axis_names) - 1)
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(f"Mesh shape {tuple(shape)} needs {n} devices, "
                         f"have {len(devices)}")
    grid = np.array(devices[:n]).reshape(shape)
    return Mesh(grid, tuple(axis_names))


def data_parallel_mesh(num_devices: Optional[int] = None, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"Requested a {num_devices}-device data-parallel mesh but only "
                f"{len(devices)} devices are visible: {devices}")
        devices = devices[:num_devices]
    return create_mesh([len(devices)], (DATA_AXIS,), devices)


def is_multiprocess(mesh: Mesh) -> bool:
    """True when the mesh spans devices owned by more than one process
    (multi-host: the Spark-cluster analog, ICI/DCN instead of shuffle)."""
    return jax.process_count() > 1 and \
        any(d.process_index != jax.process_index() for d in mesh.devices.flat)


def place(arr, sharding: NamedSharding, mesh: Mesh):
    """Place an array under a sharding, multiprocess-safe.

    Single-process: plain device_put. Multi-process: device_put cannot
    address remote devices, so the global array is assembled from each
    process's local portion (for batch-sharded data: this process's
    partition; for replicated: the full host copy) — the TPU-native
    analog of the Spark driver broadcasting NetBroadcastTuple
    (ParameterAveragingTrainingMaster.java:346-357)."""
    if arr is None:
        return None
    if is_multiprocess(mesh):
        return jax.make_array_from_process_local_data(sharding, np.asarray(arr))
    return jax.device_put(arr, sharding)


def place_global(arr, sharding: NamedSharding, mesh: Mesh):
    """Place a host value that is IDENTICAL on every process, sharded
    arbitrarily across the global mesh.

    This is the other multiprocess placement contract from `place`:
    `place` assembles a global array from per-process LOCAL PORTIONS
    (the DP data-feeding convention), while place_global takes the same
    full value everywhere and lets each process slice out its
    addressable shards (make_array_from_callback) — what tensor/
    sequence parallelism need for params after same-seed init or
    restore, and for whole batches fed identically to every process.
    Single-process: plain device_put."""
    if arr is None:
        return None
    if is_multiprocess(mesh):
        a = np.asarray(arr)
        return jax.make_array_from_callback(a.shape, sharding,
                                            lambda idx: a[idx])
    return jax.device_put(arr, sharding)


def gather_replicated(tree, mesh: Mesh):
    """All-gather a (possibly cross-process-sharded) pytree back to
    REPLICATED device arrays — jit identity with replicated output
    shardings, so XLA inserts the all-gathers. COLLECTIVE under a
    multiprocess mesh: every process must call in lockstep. After this,
    np.asarray on any leaf is legal (fully addressable), which is what
    checkpoint serialization needs (ModelSerializer writes host npz)."""
    if tree is None:
        return None
    rep = replicated(mesh)
    with mesh:
        return jax.jit(lambda t: t, out_shardings=rep)(tree)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharded(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Shard the leading (batch) dimension across `axis`."""
    return NamedSharding(mesh, PartitionSpec(axis))


def shard_batch(mesh: Mesh, tree, axis: str = DATA_AXIS):
    """Place a pytree of host arrays on the mesh, batch-dim sharded. In a
    multi-process mesh each process passes its LOCAL partition and the
    global batch is their concatenation in process order."""
    sh = batch_sharded(mesh, axis)
    return jax.tree_util.tree_map(
        lambda x: place(x, sh, mesh), tree, is_leaf=lambda x: x is None)


def replicate(mesh: Mesh, tree):
    """Replicate a pytree of arrays across the whole mesh (every process
    must hold the same values — true after same-seed init or checkpoint
    restore)."""
    sh = replicated(mesh)
    return jax.tree_util.tree_map(lambda x: place(x, sh, mesh), tree)


def pad_batch_to_multiple(arr: np.ndarray, multiple: int) -> Tuple[np.ndarray, int]:
    """Pad batch dim up to a multiple (XLA needs even shards); returns
    (padded, original_n). Padding repeats the last example so batch stats
    stay finite; callers rescale loss/metrics by original_n when needed."""
    n = arr.shape[0]
    rem = n % multiple
    if rem == 0:
        return arr, n
    pad = multiple - rem
    reps = np.repeat(arr[-1:], pad, axis=0)
    return np.concatenate([arr, reps], axis=0), n

"""Device-resident iteration counter shared by MultiLayerNetwork and
ComputationGraph.

The jitted train step takes the iteration (for LR schedules / bias
correction) and returns iteration+1. Re-uploading a fresh host scalar
every step costs a DevicePut + convert_element_type dispatch per step
(~4.5 ms/step of host-side overhead in the profiled ResNet50 loop,
docs/perf_resnet50.md) — so the returned device scalar is cached and fed
straight back in. Assigning `net.iteration = n` (checkpoint restore,
transfer learning) drops the cache; the next step re-uploads once. The
cache is also keyed by the mesh it was produced under so ParallelWrapper's
sharded steps never feed a foreign-sharded scalar into a single-device
program. Under a mesh the fresh scalar is placed replicated over it — the
placement the step's own output has — so step 2 presents the signature step
1 compiled for (a single-device scalar there cost a second full compile of
the train step).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


class DeviceIterationMixin:
    _iteration: int = 0
    _iteration_dev = None
    _iteration_dev_mesh = None

    @property
    def iteration(self) -> int:
        return self._iteration

    @iteration.setter
    def iteration(self, value):
        self._iteration = int(value)
        self._iteration_dev = None
        self._iteration_dev_mesh = None

    def _iteration_device(self, mesh=None):
        if self._iteration_dev is None or self._iteration_dev_mesh is not mesh:
            if mesh is None:
                return jnp.asarray(self._iteration, jnp.int32)
            from ..parallel.mesh import replicate
            return replicate(mesh, np.asarray(self._iteration, np.int32))
        return self._iteration_dev

    def _commit_iteration(self, new_iter, mesh=None):
        self._iteration += 1
        self._iteration_dev = new_iter
        self._iteration_dev_mesh = mesh

"""Data-parallel training — the ParallelWrapper replacement.

Reference analog: org.deeplearning4j.parallelism.ParallelWrapper — N trainer
threads with per-device model replicas, prefetch queues, and either parameter
averaging or Strom-style threshold-encoded gradient sharing
(EncodedGradientsAccumulator, SURVEY.md §3.3). All of that machinery exists
because the reference must coordinate asynchronous device replicas by hand.

TPU-native: the SAME jitted train step, with the batch sharded over the
mesh's "data" axis and params replicated. XLA SPMD inserts one fused
all-reduce (psum over ICI) for the gradients — semantically identical to
synchronous gradient sharing with zero host involvement, no threads, no
queues, no encoding. Multi-host (the Spark/Aeron analog) is the same code
under jax.distributed; DCN collectives replace the parameter server.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from deeplearning4j_tpu.parallel.mesh import DeviceMesh


class ParallelWrapper:
    """Shards a model's training over a DeviceMesh data axis.

    Usage (mirrors the reference's wrapper-around-model pattern):

        wrapper = ParallelWrapper(model, mesh)   # mesh defaults to all devices
        wrapper.fit(iterator, epochs=2)

    The wrapped model's params/opt state are placed replicated on the mesh;
    each fit_batch shards the host batch over "data" and runs the model's own
    jitted train step under the mesh context — XLA partitions it SPMD.
    """

    def __init__(self, model, mesh: Optional[DeviceMesh] = None,
                 prefetch_buffer: int = 2):
        self.model = model
        self.mesh = mesh or DeviceMesh()
        self.prefetch_buffer = prefetch_buffer
        self._placed = False

    def _place(self):
        m = self.model
        m.params = self.mesh.replicate(m.params)
        m.state = self.mesh.replicate(m.state)
        m.opt_state = self.mesh.replicate(m.opt_state)
        self._placed = True

    def fit_batch(self, ds) -> float:
        if not self._placed:
            self._place()
        from deeplearning4j_tpu.nn.multilayer import _unpack

        x, y, mask, label_mask = _unpack(ds)
        # np.shape, not np.asarray(x).shape: a batch the prefetch thread has
        # already sharded onto the mesh must not be gathered back to the
        # host just to read its leading dimension
        n = np.shape(x)[0] if not isinstance(x, (list, tuple, dict)) else None
        dp = self.mesh.shape["data"]
        if n is not None and n % dp:
            raise ValueError(f"batch size {n} not divisible by data-parallel degree {dp}")
        parts = (x, y) if mask is None else (x, y, mask)
        if label_mask is not None:
            parts = (x, y, mask, label_mask)
        batch = self.mesh.shard_batch(parts)
        with self.mesh.mesh:
            loss = self.model.fit_batch(batch)
        if self._lockstep():
            # multi-process CPU (Gloo): fit_batch's float(loss) does NOT
            # wait for the gradient/param psum (loss is computed pre-
            # update), so the all-reduce is still in flight when the host
            # moves on. Any later host-initiated collective (orbax save
            # barriers, broadcast_one_to_all) then interleaves with it on
            # the same Gloo pair and aborts the transport. Blocking on the
            # updated params serializes the rounds; TPU/GPU transports
            # don't need it and skip this branch.
            jax.block_until_ready((self.model.params, self.model.opt_state,
                                   self.model.state))
        return loss

    def _lockstep(self) -> bool:
        if not hasattr(self, "_lockstep_cached"):
            self._lockstep_cached = (jax.process_count() > 1
                                     and jax.default_backend() == "cpu")
        return self._lockstep_cached

    def fit(self, data, epochs: int = 1):
        from deeplearning4j_tpu.datasets.iterators import AsyncPrefetchIterator

        if self.prefetch_buffer and hasattr(data, "reset"):
            # single-process: the prefetch thread shards each batch onto the
            # mesh, overlapping H2D with the previous step's compute
            # (fit_batch's shard_batch then passes it through unchanged).
            # Multi-process stages host-side: make_array_from_callback from
            # a second thread would interleave on the Gloo transport.
            sharder = (self.mesh.shard_batch
                       if jax.process_count() == 1 else None)
            data = AsyncPrefetchIterator(data, queue_size=self.prefetch_buffer,
                                         device_put=False, sharder=sharder)
        # the network's own epoch loop (phases, drain, listener hooks), with
        # the sharding fit_batch above as its step
        return self.model._fit_epochs(data, epochs, self.fit_batch)

    def average_params(self):
        """No-op kept for API parity: synchronous SPMD keeps replicas identical
        by construction (the reference needed explicit averaging because its
        replicas drifted between averaging rounds)."""
        return self.model.params

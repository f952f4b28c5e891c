"""Multi-host (multi-process) distributed runtime.

The reference imports torch.distributed + DDP + DistributedSampler and a
``backend='nccl'`` config field but never initializes any of it
(train.py:7-10, 88; SURVEY.md section 2.3). This module is the working
TPU-native replacement:

  - ``initialize()`` wraps ``jax.distributed.initialize``. On TPU pods
    JAX autodetects coordinator/process topology from the environment; on
    manual clusters pass the coordinator address/count/id explicitly.
    Gradient/parameter collectives then ride ICI within a slice and DCN
    across slices — placement follows the mesh axes (parallel/mesh.py),
    no NCCL-style process-group plumbing.
  - ``global_batch()`` assembles each host's locally drawn windows into
    one global jax.Array laid out per the batch sharding — the working
    replacement for the reference's unused ``DistributedSampler``
    (per-host disjoint draws come free from the epoch permutation:
    each host takes a distinct slice of the same seeded bijection,
    data/native.py).
  - ``is_primary()`` gates logging and checkpoint writes to process 0.

Single-process behavior is identity (no initialization needed), so the
same trainer code runs on a laptop, one chip, or a pod.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Bring up the JAX distributed runtime (DCN coordination layer).

    No-op when running single-process with no explicit arguments — the
    common laptop/single-chip case needs no coordinator. On TPU pods all
    three arguments autodetect from the environment when left None."""
    if jax.distributed.is_initialized():
        return
    if (
        coordinator_address is None
        and num_processes is None
        and process_id is None
        and jax.process_count() == 1
    ):
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def is_primary() -> bool:
    """True on the process that should write logs/checkpoints."""
    return jax.process_index() == 0


def process_count() -> int:
    return jax.process_count()


def local_batch_slice(global_batch_size: int) -> tuple:
    """(start, size) of this host's share of a global batch — each host
    draws only its own windows (the DistributedSampler capability,
    train.py:8-10, done with arithmetic instead of a sampler object)."""
    n = jax.process_count()
    if global_batch_size % n:
        raise ValueError(
            f"global batch {global_batch_size} must divide evenly over "
            f"{n} processes"
        )
    per = global_batch_size // n
    return jax.process_index() * per, per


def gather_to_host(tree):
    """Host (numpy) copy of a state pytree whose leaves may be sharded
    over NON-addressable devices (fsdp/tensor shards living on other
    processes' chips) — the multi-process-safe replacement for
    ``jax.device_get(state)``, which raises on such arrays.

    On multi-process runs this is a COLLECTIVE: every process must call
    it (each contributes its shards to the allgather), even though only
    the primary typically consumes the result. Single-process it
    degrades to a plain ``device_get``. Fully-replicated leaves (step
    counters, schedules) are read from a local replica without any
    cross-process traffic."""
    if jax.process_count() == 1:
        return jax.device_get(tree)
    from jax.experimental import multihost_utils

    def leaf(x):
        if isinstance(x, jax.Array):
            if x.is_fully_replicated:
                return np.asarray(x)
            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
        return x

    return jax.tree_util.tree_map(leaf, tree)


def global_batch(local: dict, mesh: Mesh) -> dict:
    """Assemble per-host ``{"x": (A, B_local, T), "y": ...}`` numpy arrays
    into global jax.Arrays sharded per the training batch spec. Each host
    provides only its local shard; no host ever materializes the global
    batch."""
    spec = P(None, ("data", "fsdp"), "sequence")
    sharding = NamedSharding(mesh, spec)
    return {
        k: jax.make_array_from_process_local_data(sharding, np.asarray(v))
        for k, v in local.items()
    }

"""Multi-host initialization.

The distributed 'backend' here is not hand-written (the reference had none
at all, and NCCL/MPI-style code would be the wrong shape for TPU): XLA
compiles the collectives, ICI/DCN routing included, once every host joins
one `jax.distributed` runtime and sees the global device set. This module
is the join step.

On Cloud TPU pods the coordinator/process count/process id are
auto-detected; elsewhere they come from the standard env vars
(JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES, JAX_PROCESS_ID) or explicit
arguments. Single-process runs are a no-op, so the CLI can call this
unconditionally.
"""
from __future__ import annotations

import os
from typing import Optional


def maybe_initialize_distributed(
        coordinator_address: Optional[str] = None,
        num_processes: Optional[int] = None,
        process_id: Optional[int] = None,
        log=None) -> bool:
    """Join the jax.distributed runtime when multi-host config is present.

    Returns True if initialization happened. After it, ``jax.devices()``
    spans all hosts, the mesh spans the pod, each process's reader strides
    the data file (``PathContextReader(process_index, process_count)``) and
    ``parallel.mesh.shard_batch`` assembles the global batch from the
    process-local shards. In-training per-epoch evaluation runs the same
    fixed-step, counter-merged path as standalone ``Code2VecModel.evaluate``
    (exactness across process counts:
    ``tests/test_distributed.py::test_midtrain_eval_matches_single_process``).
    """
    import jax

    coordinator_address = coordinator_address or os.environ.get(
        'JAX_COORDINATOR_ADDRESS')
    env_processes = os.environ.get('JAX_NUM_PROCESSES')
    num_processes = num_processes if num_processes is not None else (
        int(env_processes) if env_processes else None)
    env_pid = os.environ.get('JAX_PROCESS_ID')
    process_id = process_id if process_id is not None else (
        int(env_pid) if env_pid else None)

    # a pod is MORE THAN ONE worker: a single-host TPU setup (one entry,
    # or none, in TPU_WORKER_HOSTNAMES) must not trigger a coordinator
    # handshake
    worker_hostnames = [h for h in os.environ.get(
        'TPU_WORKER_HOSTNAMES', '').split(',') if h]
    on_tpu_pod = (len(worker_hostnames) > 1
                  or bool(os.environ.get('MEGASCALE_COORDINATOR_ADDRESS')))
    if coordinator_address is None and not on_tpu_pod:
        return False  # single-host run

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes, process_id=process_id)
    if log is not None:
        log('jax.distributed initialized: process %d of %d, %d global '
            'devices' % (jax.process_index(), jax.process_count(),
                         len(jax.devices())))
    return True

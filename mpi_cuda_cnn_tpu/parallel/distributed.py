"""Multi-host runtime initialization.

The reference's process management is `mpirun -np 8` + MPI_Init
(Makefile:44, cnnmpi.c:419). The JAX equivalent for multi-host TPU pods is
`jax.distributed.initialize()`: each host process joins the same runtime,
`jax.devices()` becomes the global device list, and XLA routes collectives
over ICI within a slice and DCN across slices — user training code is
unchanged (SURVEY.md §5.8).

On a single host (one chip or one four-chip host, and the reference's
own test setup) initialization is a no-op.
"""

from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class ProcessInfo:
    process_index: int
    process_count: int
    local_devices: int
    global_devices: int


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> ProcessInfo:
    """Join the multi-host runtime when launched as one process per host.

    With no arguments and no coordinator in the environment this is a
    single-process run and nothing is initialized — the same entry
    point covers a laptop CPU, one TPU host and a pod. Once a
    coordinator IS named (argument or COORDINATOR_ADDRESS /
    JAX_COORDINATOR_ADDRESS), failing to join raises: a process that
    carried on alone would train on 1/N of the data and report it as
    the whole job.
    """
    if coordinator_address is not None or _looks_multiprocess():
        if jax.distributed.is_initialized():
            return process_info()
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    return process_info()


def _looks_multiprocess() -> bool:
    import os

    return any(k in os.environ for k in ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS"))


def process_info() -> ProcessInfo:
    return ProcessInfo(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_devices=jax.local_device_count(),
        global_devices=jax.device_count(),
    )


def barrier(name: str) -> None:
    """Block until every process reaches this point (the multihost
    checkpoint-write ordering fence: process 0 writes, everyone meets
    here, so no process can act on "the checkpoint exists" before it
    does — train/checkpoint.save_checkpoint). Single-process runs
    return immediately; `name` keys the rendezvous so two different
    barrier sites can't accidentally pair up."""
    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)

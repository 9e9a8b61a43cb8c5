"""Multi-host distributed runtime.

TPU-native replacement for the reference's socket/MPI linkers
(src/network/linkers_socket.cpp:188-215, linkers_mpi.cpp): instead of a
hand-rolled TCP ring, multi-host training runs as one JAX process per host
joined through `jax.distributed.initialize`; the device mesh then spans all
hosts and the SAME shard_map collectives that ride ICI within a host ride
DCN across hosts — XLA picks the transport.

`init_distributed` maps the reference's conf surface (num_machines +
machine_list_file + local_listen_port, docs/Features.rst:119-141) onto the
JAX coordinator model: the FIRST machine in the list is the coordinator,
process_id is this host's line index. Standard JAX env vars
(JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID) override.

Array placement and host readback helpers paper over the single- vs multi-
process difference: in one process `jax.device_put` suffices; across
processes globally-sharded arrays are assembled from per-process data via
`jax.make_array_from_callback`, and host syncs read the replicated
addressable shard.
"""
from __future__ import annotations

import os
import socket
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding

from ..utils.log import Log
from ..utils.timer import global_timer

_initialized = False


def _local_addresses() -> set:
    names = {"localhost", "127.0.0.1", socket.gethostname()}
    try:
        names.add(socket.gethostbyname(socket.gethostname()))
    except OSError:
        pass
    return names


def init_distributed(config=None,
                     coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> bool:
    """Join (or skip joining) the multi-host world. Idempotent.

    Resolution order: explicit args > JAX_* env vars > reference-style conf
    (machine_list_file + local_listen_port + num_machines). Returns True
    when a multi-process runtime is active after the call.
    """
    global _initialized
    if _initialized:
        return jax.process_count() > 1
    try:  # user may have initialized jax.distributed themselves
        from jax._src import distributed as _dist_state

        if getattr(_dist_state.global_state, "client", None) is not None:
            _initialized = True
            return jax.process_count() > 1
    except Exception:  # noqa: BLE001 - internal layout changed: fall through
        pass

    env_addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    env_np = os.environ.get("JAX_NUM_PROCESSES")
    env_pid = os.environ.get("JAX_PROCESS_ID")
    if coordinator_address is None and env_addr:
        coordinator_address = env_addr
        num_processes = num_processes or int(env_np) if env_np else num_processes
        process_id = process_id if process_id is not None else (
            int(env_pid) if env_pid else None)

    if coordinator_address is None and config is not None:
        machines = []
        mlist = getattr(config, "machine_list_filename", "") or ""
        if mlist and os.path.isfile(mlist):
            with open(mlist) as f:
                machines = [ln.strip() for ln in f if ln.strip()]
        elif getattr(config, "machines", ""):
            machines = [m.strip() for m in config.machines.split(",")
                        if m.strip()]
        if len(machines) > 1:
            port = int(getattr(config, "local_listen_port", 12400))
            host0 = machines[0].split(":")[0].split(" ")[0]
            coordinator_address = f"{host0}:{port}"
            num_processes = num_processes or len(machines)
            if process_id is None:
                local = _local_addresses()
                for i, m in enumerate(machines):
                    if m.split(":")[0].split(" ")[0] in local:
                        process_id = i
                        break

    if coordinator_address is None:
        # no multi-host config: don't touch JAX at all (process_count would
        # initialize the backend, breaking a later explicit initialize())
        return False
    if num_processes is None or process_id is None:
        Log.fatal("Multi-host init needs num_processes and process_id "
                  "(set JAX_NUM_PROCESSES / JAX_PROCESS_ID or a machine "
                  "list containing this host)")
    Log.info("Joining distributed world: coordinator=%s process %d/%d",
             coordinator_address, process_id, num_processes)
    on_cpu = (os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
              or getattr(config, "device_type", "") == "cpu")
    if int(num_processes) > 1 and on_cpu:
        # the default CPU client has no cross-process collectives ("Multi-
        # process computations aren't implemented on the CPU backend");
        # gloo gives the CPU gang real psums — essential for the chaos
        # harness, harmless for the TPU path (knob only affects CPU)
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    try:
        # the coordinator join can block for the whole cluster spin-up;
        # make that visible in perf reports
        with global_timer.scope("dist_init"):
            jax.distributed.initialize(coordinator_address=coordinator_address,
                                       num_processes=int(num_processes),
                                       process_id=int(process_id))
    except RuntimeError as e:
        # "should only be called once" / "already initialized": fine
        if "once" not in str(e) and "already" not in str(e):
            raise
    _initialized = True
    return jax.process_count() > 1


def put_global(arr, mesh: jax.sharding.Mesh, spec) -> jax.Array:
    """Place a host array onto the mesh with the given PartitionSpec, working
    both single-process (plain device_put) and multi-process (each process
    materializes its addressable shards from the same full host array)."""
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    # graftlint: disable=R1 -- multi-process placement must materialize one host copy: make_array_from_callback's callback slices a host array per addressable shard; the single-process path above stays a pure device_put
    arr = np.asarray(arr)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def put_global_tree(tree, mesh: jax.sharding.Mesh, spec):
    """put_global over every leaf of a pytree (same spec)."""
    return jax.tree_util.tree_map(lambda a: put_global(a, mesh, spec), tree)


def put_replicated(tree, mesh: jax.sharding.Mesh):
    """Replicate a pytree of host/device arrays onto every mesh device."""
    return put_global_tree(tree, mesh, jax.sharding.PartitionSpec())


def host_value(arr) -> np.ndarray:
    """Read a (possibly replicated multi-process) device array on host.
    Replicated out_specs=P() results are not fully addressable across
    processes; their first addressable shard IS the full value."""
    if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
        return np.asarray(arr.addressable_data(0))  # graftlint: disable=R1 -- host_value IS the deliberate commit-point device->host read: every caller sits where the host needs the value (split records, narrow/miss counters), so the sync is the contract, not a hidden stall
    return np.asarray(arr)  # graftlint: disable=R1 -- same contract as the multi-process branch above

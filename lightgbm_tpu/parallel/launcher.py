"""Multi-host launch helper — the Dask-module analog.

Reference: python-package/lightgbm/dask.py:196-215 (_train: machine list
assembly + LGBM_NetworkInit on every worker) and src/network/linkers_socket.cpp.

On TPU there is no socket layer to configure: `jax.distributed.initialize()`
connects the hosts, and the SAME SPMD training program spans all of them —
`tree_learner=data|feature|voting` shard over the global device mesh exactly
as they do over a single host's devices.

Typical multi-host run (one process per host, e.g. under `gcloud compute tpus
tpu-vm ssh --worker=all`):

    import lightgbm_tpu as lgb
    lgb.init_distributed()                      # TPU pod: args auto-detected
    # or, on CPU/GPU clusters:
    # lgb.init_distributed(coordinator_address="host0:1234",
    #                      num_processes=4, process_id=rank)
    bst = lgb.train({"tree_learner": "data", ...}, dset)

Every process must execute the same calls; passing a FILE PATH to Dataset
under multi-process training loads only this rank's row shard (bin mappers
sync automatically — see parallel/dist_data.py), so no host ever holds the
full feature matrix. In-memory arrays must still be identical everywhere.
"""
from __future__ import annotations

import os
from typing import Optional

from ..utils.log import LightGBMError, log_info


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     local_device_ids=None) -> None:
    """Connect this process to the multi-host training job (replaces
    LGBM_NetworkInit / the Dask machines= list).

    On TPU pods all arguments are auto-detected from the environment; on
    other platforms pass them explicitly."""
    import jax

    # NOTE: jax.process_count() would itself initialize the XLA backend,
    # after which distributed.initialize is rejected — probe the
    # distributed client state directly instead
    try:
        from jax._src import distributed as _dist
        already = _dist.global_state.client is not None
    except Exception:  # pragma: no cover - jax internals moved
        already = False
    if already:
        log_info("jax.distributed already initialized "
                 f"({jax.process_count()} processes)")
        return
    # the default CPU client refuses cross-process computations
    # ("Multiprocess computations aren't implemented on the CPU
    # backend"); the gloo collectives implementation is what makes
    # localhost-simulated multi-host runs work (parallel/cluster.py's
    # workers set the same; TPU: irrelevant)
    platforms = str(getattr(jax.config, "jax_platforms", None)
                    or os.environ.get("JAX_PLATFORMS", ""))
    if "cpu" in platforms:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    try:
        jax.distributed.initialize(**kwargs)
    except Exception as e:  # pragma: no cover - depends on cluster env
        if "already initialized" in str(e).lower():
            # belt-and-braces for the private-state probe above: an
            # earlier explicit initialize is fine, keep the old no-op
            log_info("jax.distributed already initialized")
            return
        raise LightGBMError(
            f"jax.distributed.initialize failed: {e}; on non-TPU clusters "
            "pass coordinator_address/num_processes/process_id explicitly")
    log_info(f"distributed init OK: process {jax.process_index()}/"
             f"{jax.process_count()}, {jax.device_count()} global devices")

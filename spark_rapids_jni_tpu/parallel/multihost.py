"""Multi-host runtime: process-group init + DCN/ICI-aware mesh construction.

The reference delegates cross-executor transport to the host plugin's UCX
shuffle and cross-process coordination to Spark itself (SURVEY.md §2.3); the
TPU-native equivalent is the JAX distributed runtime: every host process
calls :func:`initialize` once, after which ``jax.devices()`` spans the whole
pod/slice fleet and the SAME ``shard_map`` programs (shuffles, query steps)
scale across hosts — XLA routes collectives over ICI within a slice and DCN
between slices.

Mesh layout is what decides which links collectives ride: with
:func:`make_pod_mesh`, the ``data`` axis is laid out with slice-locality
outermost (``create_hybrid_device_mesh``), so the frequent exchanges
(all_to_all shuffle within a partition group) stay on ICI and only
psum-style reductions cross DCN.  This is the standing-in for "NCCL/MPI
backend that scales to multi-host": there is no transport code to write —
placement + sharding annotations are the backend.

A cluster runtime that exports a coordinator (``JAX_COORDINATOR_ADDRESS``,
Slurm, MPI) lets ``initialize()`` run with no arguments; explicit arguments
support everything else, tests included.  With neither, the process stays
single-process: JAX's own auto-detection would query the cloud metadata
server, which a single TPU host need not have.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax

from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

__all__ = ["initialize", "is_multihost", "make_pod_mesh", "process_summary"]

_initialized = False

# env markers that indicate a multi-process cluster runtime is present; used
# to decide whether an "initialize too late" condition is fatal or benign
_CLUSTER_ENV_MARKERS = (
    "MEGASCALE_COORDINATOR_ADDRESS",
    "JAX_COORDINATOR_ADDRESS",
    "COORDINATOR_ADDRESS",
    "SLURM_JOB_ID",
    "OMPI_MCA_orte_hnp_uri",
)


def _cluster_env_present() -> bool:
    """True when the environment names a genuinely multi-process cluster.

    TPU_WORKER_HOSTNAMES needs a value check, not a presence check: a
    single-host TPU runtime sets it to the one local host, and a one-entry
    list is exactly the single-process case this module must treat as
    benign.
    """
    if any(m in os.environ for m in _CLUSTER_ENV_MARKERS):
        return True
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hosts.split(",") if h.strip()]) > 1


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join the JAX process group (idempotent).

    With no arguments, joins only when the environment names a cluster
    (:data:`_CLUSTER_ENV_MARKERS`) and is a no-op otherwise, so single-host
    code paths need no changes and never reach for a metadata server.
    Must run before the backend is first touched.
    """
    global _initialized
    if _initialized:
        return
    explicit = any(a is not None for a in
                   (coordinator_address, num_processes, process_id))
    if not explicit and not any(m in os.environ
                                for m in _CLUSTER_ENV_MARKERS):
        return
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except ValueError:
        # jax raises ValueError specifically when no cluster environment
        # could be auto-detected; anything else (e.g. an unreachable
        # coordinator) must propagate rather than silently degrade.
        if explicit:
            raise
        return  # single-process; not latched, a later explicit call works
    except RuntimeError:
        # "must be called before any JAX calls": the backend is already up
        # (module-level device constants initialize it under `python -m`).
        # Benign for plain single-process use; FATAL when a cluster runtime
        # is present — degrading there would compute per-host partial
        # results silently.
        if explicit or _cluster_env_present():
            raise
        return
    _initialized = True


def is_multihost() -> bool:
    return jax.process_count() > 1


def make_pod_mesh(
    mp: int = 1,
    axis_names: Tuple[str, str] = (DATA_AXIS, MODEL_AXIS),
):
    """A (data, model) mesh over ALL processes' devices, DCN-aware.

    The data axis is ordered slice-outermost so a contiguous block of
    partition groups lives on one ICI domain: the shuffle's all_to_all
    between a slice's devices never crosses DCN, and only the final
    psum-style aggregations do.  Falls back to a flat mesh when the
    platform exposes no slice topology (CPU meshes, single slice); real
    layout errors (shape mismatches) propagate.
    """
    devices = jax.devices()
    n = len(devices)
    if mp < 1 or n % mp:
        raise ValueError(f"model parallelism {mp} does not divide {n} devices")
    multi_slice = (getattr(devices[0], "slice_index", None) is not None
                   and len({d.slice_index for d in devices}) > 1)
    if multi_slice:
        try:
            from jax.experimental import mesh_utils
        except ImportError:
            mesh_utils = None
        if mesh_utils is not None:
            arr = mesh_utils.create_hybrid_device_mesh(
                mesh_shape=(n // mp // _num_slices(), mp),
                dcn_mesh_shape=(_num_slices(), 1),
                devices=devices,
            )
            return jax.sharding.Mesh(arr, axis_names)
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh

    return make_mesh((n // mp, mp), devices=devices, axis_names=axis_names)


def _num_slices() -> int:
    return len({d.slice_index for d in jax.devices()})


def process_summary() -> dict:
    """Small diagnostic dict (for logs / the bench header)."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }

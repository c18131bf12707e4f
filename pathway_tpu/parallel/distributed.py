"""Multi-host initialization (the DCN control plane).

The reference clusters over TCP with ``PATHWAY_PROCESSES``/``PROCESS_ID``/
``FIRST_PORT`` (``dataflow/config.rs:70-86``); here the same environment
bootstraps ``jax.distributed`` so a multi-host mesh spans all processes —
collectives then ride ICI within a pod and DCN across pods, with the host
side (connectors, persistence, progress) staying per-process exactly like
the reference workers.
"""

from __future__ import annotations

import os

from ..utils import jaxcfg  # noqa: F401  (configures jax before first use)

import jax

__all__ = ["init_from_env", "global_mesh"]

_initialized = False


def init_from_env(coordinator_host: str = "127.0.0.1") -> None:
    """Initialize jax.distributed from PATHWAY_* env (idempotent; no-op for
    single-process runs). Launch with ``pathway-tpu spawn -n M ...``."""
    global _initialized
    if _initialized:
        return
    from ..internals.config import get_pathway_config

    cfg = get_pathway_config()
    if cfg.processes <= 1:
        _initialized = True
        return
    # default coordinator port offset: first_port itself belongs to the
    # ClusterComm TCP mesh listeners
    coordinator = os.environ.get(
        "PATHWAY_COORDINATOR", f"{coordinator_host}:{cfg.first_port + 1000}"
    )
    # multiprocess computations on the CPU backend ride jaxlib's gloo TCP
    # collectives, the default jax_cpu_collectives_implementation
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=cfg.processes,
        process_id=cfg.process_id,
    )
    _initialized = True


def global_mesh(axes: dict[str, int] | None = None):
    """Mesh over every device of every participating process."""
    from .mesh import make_mesh

    init_from_env()
    return make_mesh(axes)

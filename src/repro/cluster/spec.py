"""ClusterSpec: WHO this process is in the cluster, resolved from env vars.

The launcher (``cluster.launcher``) sets these variables in each worker's
environment; a worker calls :func:`ClusterSpec.from_env` +
:func:`initialize` before touching any jax device state.  On managed
clusters (SLURM/YARN/k8s) the same variables are set by the scheduler's
wrapper script — the spec-from-env seam is exactly the shifu/YARN runner
pattern, so nothing in the training path knows how processes were placed.

``REPRO_COORDINATOR``     host:port of the jax.distributed coordinator
                          (process 0 binds it).
``REPRO_NUM_PROCESSES``   world size.
``REPRO_PROCESS_ID``      this process's rank in [0, num_processes).
``REPRO_LOCAL_DEVICES``   devices this process contributes, realized by
                          forcing ``--xla_force_host_platform_device_count``
                          (the launcher runs its workers on the CPU and
                          exports both BEFORE the worker imports jax).
"""
from __future__ import annotations

import os
from dataclasses import dataclass, replace
from typing import Mapping, Optional

ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"
ENV_LOCAL_DEVICES = "REPRO_LOCAL_DEVICES"

DEFAULT_COORDINATOR = "localhost:29400"


@dataclass(frozen=True)
class ClusterSpec:
    """One process's view of the cluster."""
    coordinator: str = DEFAULT_COORDINATOR
    num_processes: int = 1
    process_id: int = 0
    local_devices: int = 1

    def __post_init__(self):
        if self.num_processes < 1:
            raise ValueError(
                f"num_processes must be >= 1, got {self.num_processes}")
        if not 0 <= self.process_id < self.num_processes:
            raise ValueError(
                f"process_id must be in [0, {self.num_processes}), "
                f"got {self.process_id}")
        if self.local_devices < 1:
            raise ValueError(
                f"local_devices must be >= 1, got {self.local_devices}")
        if ":" not in self.coordinator:
            raise ValueError(
                f"coordinator must be host:port, got {self.coordinator!r}")

    @classmethod
    def from_env(cls, env: Optional[Mapping[str, str]] = None,
                 ) -> "ClusterSpec":
        """Resolve the spec from ``env`` (default ``os.environ``); missing
        variables keep their single-process defaults, so code that calls
        this unconditionally still works outside any launcher."""
        env = os.environ if env is None else env
        return cls(
            coordinator=env.get(ENV_COORDINATOR, DEFAULT_COORDINATOR),
            num_processes=int(env.get(ENV_NUM_PROCESSES, "1")),
            process_id=int(env.get(ENV_PROCESS_ID, "0")),
            local_devices=int(env.get(ENV_LOCAL_DEVICES, "1")))

    def env(self) -> dict:
        """The env-var dict the launcher exports into a worker (inverse of
        ``from_env``)."""
        return {
            ENV_COORDINATOR: self.coordinator,
            ENV_NUM_PROCESSES: str(self.num_processes),
            ENV_PROCESS_ID: str(self.process_id),
            ENV_LOCAL_DEVICES: str(self.local_devices),
        }

    def replace(self, **kw) -> "ClusterSpec":
        return replace(self, **kw)

    @property
    def is_multiprocess(self) -> bool:
        return self.num_processes > 1


def in_worker(env: Optional[Mapping[str, str]] = None) -> bool:
    """True when this process was spawned by the cluster launcher (the
    worker env vars are present)."""
    env = os.environ if env is None else env
    return ENV_PROCESS_ID in env


def initialize(spec: ClusterSpec) -> None:
    """Bring up ``jax.distributed`` for this process.

    Must run before any jax computation (device state is fixed once the
    backend initializes).  CPU processes talk gloo — the runtime's
    cross-host CPU collectives — so the lax backend's collectives cross
    process boundaries transparently.  A ``num_processes == 1`` spec is a
    no-op: a single process needs no coordination service, and skipping it
    keeps the degenerate world-size-1 path (the elastic floor) free of a
    dangling coordinator port.
    """
    if not spec.is_multiprocess:
        return
    import jax
    # CPU cross-process collectives go through gloo; guarded because
    # accelerator builds may not carry the option (they use NCCL/ICI).
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except (AttributeError, ValueError):  # pragma: no cover - non-CPU jaxlib
        pass
    jax.distributed.initialize(
        coordinator_address=spec.coordinator,
        num_processes=spec.num_processes,
        process_id=spec.process_id)

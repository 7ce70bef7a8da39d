"""Device mesh construction.

Axis convention (used by every sharding rule in the framework):

- ``dp`` — data/batch parallel: opponents of a debate round are rows of one
  batch; dp splits rows across mesh slices (the TPU-native replacement for
  the reference's thread-per-opponent fan-out, SURVEY §2.3).
- ``tp`` — tensor parallel: attention heads / FFN columns (Megatron-style,
  collectives inserted by GSPMD over ICI).
- ``sp`` — sequence/context parallel: long-context ring attention
  (parallel/ring.py) shards the sequence axis across ICI neighbors.

Multi-host: ``jax.distributed.initialize`` is invoked when the runtime env
indicates a multi-process job; ``jax.devices()`` then spans all hosts and
the same mesh code covers v5e-1 through multi-host v5p pods (DCN between
slices is handled by XLA's collective lowering, not by this code).
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh

DP, TP, SP = "dp", "tp", "sp"
MeshAxes = (DP, TP, SP)
# Expert parallel: the axis a routed layer's expert stack is split over
# (parallel/sharding.py). No registry mesh spec builds it yet (meshes
# through the batcher: ROADMAP D1); a mesh without it holds every expert
# on every device.
EP = "ep"


def maybe_initialize_distributed() -> None:
    """Bring up the multi-host runtime when launched as one process per
    host. Safe no-op otherwise.

    Launch contract (one process per host):

        JAX_COORDINATOR_ADDRESS=host0:1234   # process 0's address
        JAX_NUM_PROCESSES=N
        JAX_PROCESS_ID=i                     # 0..N-1, unique per process

    ``jax.distributed.initialize()`` only auto-detects managed clusters
    (SLURM, Cloud TPU metadata); for the generic env-var launch above it
    requires explicit arguments, so this passes them through. Exercised
    for real by the two-process CPU smoke test
    (tests/test_multihost.py), so the v5p-16 multi-host config is not
    first debugged on scarce hardware.

    The idempotence check must NOT touch the backend (jax.process_count /
    jax.devices would initialize XLA and make distributed.initialize
    illegal), so it inspects the distributed client state directly.
    """
    coordinator = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if not coordinator:
        if os.environ.get("JAX_NUM_PROCESSES") or os.environ.get(
            "JAX_PROCESS_ID"
        ):
            # Half a launch contract: this host would silently run
            # single-process while its peers block at the coordinator
            # barrier forever. Fail fast with the cause.
            raise RuntimeError(
                "multi-host launch: JAX_NUM_PROCESSES/JAX_PROCESS_ID are "
                "set but JAX_COORDINATOR_ADDRESS is not; set all three"
            )
        return
    if jax.distributed.is_initialized():
        return
    num = os.environ.get("JAX_NUM_PROCESSES")
    pid = os.environ.get("JAX_PROCESS_ID")
    if (num is None) != (pid is None):
        # Fail fast with the actual cause — falling through to cluster
        # auto-detect would hang the other hosts at the coordinator
        # barrier or die with an opaque error.
        missing = "JAX_PROCESS_ID" if pid is None else "JAX_NUM_PROCESSES"
        raise RuntimeError(
            f"multi-host launch: JAX_COORDINATOR_ADDRESS is set but "
            f"{missing} is not; set both JAX_NUM_PROCESSES and "
            f"JAX_PROCESS_ID (or neither, for managed clusters)"
        )
    if num is not None:
        try:
            num_i, pid_i = int(num), int(pid)
        except ValueError:
            raise RuntimeError(
                f"multi-host launch: JAX_NUM_PROCESSES={num!r} / "
                f"JAX_PROCESS_ID={pid!r} must be integers"
            ) from None
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_i,
            process_id=pid_i,
        )
    else:
        # Managed-cluster path: let jax's cluster plugins fill the rest.
        jax.distributed.initialize(coordinator_address=coordinator)


def mesh_shape_from_spec(
    mesh_spec: dict[str, int] | None, n_devices: int | None = None
) -> dict[str, int]:
    """Normalize a registry mesh spec {axis: size} to a full {dp,tp,sp}.

    Unspecified axes default to 1; leftover devices go to dp so a spec like
    {"tp": 2} on 8 devices yields dp=4, tp=2, sp=1. A spec that pins dp
    EXPLICITLY may describe a SUBMESH (dp·tp·sp < device count): the mesh
    is built on the LEADING devices, so a small model can run on one chip
    of a slice. (Placing several submesh entries on DISJOINT chips is not
    implemented — every submesh starts at device 0; pass ``devices`` to
    make_mesh for manual placement.)
    """
    n = n_devices if n_devices is not None else len(jax.devices())
    spec = dict(mesh_spec or {})
    unknown = set(spec) - set(MeshAxes)
    if unknown:
        raise ValueError(f"unknown mesh axes {sorted(unknown)}; use {MeshAxes}")
    tp = int(spec.get(TP, 1))
    sp = int(spec.get(SP, 1))
    if DP not in spec and n % (tp * sp) != 0:
        raise ValueError(
            f"mesh tp={tp} sp={sp} does not divide device count {n}"
        )
    dp = int(spec.get(DP, n // (tp * sp)))
    total = dp * tp * sp
    if total > n or (DP not in spec and total != n):
        raise ValueError(
            f"mesh dp*tp*sp = {total} != device count {n}"
        )
    return {DP: dp, TP: tp, SP: sp}


def make_mesh(
    mesh_spec: dict[str, int] | None = None,
    devices: list | None = None,
) -> Mesh:
    """Create the {dp, tp, sp} mesh over the available devices.

    TP is placed on the fastest-varying axis of the device array so
    tensor-parallel collectives ride adjacent ICI links.
    """
    devs = devices if devices is not None else jax.devices()
    shape = mesh_shape_from_spec(mesh_spec, n_devices=len(devs))
    total = shape[DP] * shape[SP] * shape[TP]
    arr = np.asarray(devs[:total]).reshape(shape[DP], shape[SP], shape[TP])
    return Mesh(arr, (DP, SP, TP))

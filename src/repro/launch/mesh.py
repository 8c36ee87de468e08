"""Production mesh construction (task-brief interface, verbatim semantics).

Every builder is a FUNCTION (not a module-level constant) so importing this
module never touches jax device state.  Axis semantics (DESIGN.md §2.2/§5):

  * ``pod``   — data-parallel replication for LM cells; the constraint-grid
    partition of the pod-sharded sweep for CGP cells (DESIGN.md §6 — pods
    run disjoint chunk slices, no cross-pod collectives).
  * ``data``  — batch parallelism (LM) / evolution islands (CGP).
  * ``model`` — tensor parallelism (LM) / input-cube sharding of the jnp
    island path (CGP: metric partials psum across it).

The logical names model code uses resolve against these physical axes in
``parallel.ctx.LOGICAL``.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """The task-brief production topology.

    Single pod: ``(16, 16)`` = 256 chips (data, model).  Multi-pod:
    ``(2, 16, 16)`` = 512 chips (pod, data, model) — the pod axis carries
    data-parallel replication across pods for LM cells and the
    constraint-configuration sweep partition for CGP cells.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, pods: int = 0):
    """Small fixed-shape mesh for tests.

    Needs ``n_data * n_model`` (× ``pods`` if nonzero) devices — tests get
    them by forcing ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    in a subprocess (see ``tests/conftest.run_subprocess``).  ``pods=0``
    omits the pod axis entirely (the single-pod production shape in
    miniature); ``pods>=1`` prepends it.
    """
    if pods:
        return jax.make_mesh((pods, n_data, n_model),
                             ("pod", "data", "model"))
    return jax.make_mesh((n_data, n_model), ("data", "model"))


def make_host_mesh():
    """Whatever devices exist, as a 1×N (data, model) mesh (examples/CI)."""
    n = jax.device_count()
    return jax.make_mesh((1, n), ("data", "model"))


def make_sweep_mesh(pods: int = 1):
    """All local devices as a (pod, data, model) mesh for the pod-sharded
    sweep (DESIGN.md §6): ``pods`` slices of the constraint grid, the rest
    of the devices on the ``model`` axis, a singleton ``data`` axis.

    Host-local stand-in for the multi-pod production mesh: with a forced
    device count this is what the multi-pod parity tests drive
    (``parallel.ctx.pod_count()`` picks up ``pods``).  Device count must be
    divisible by ``pods``.
    """
    n = jax.device_count()
    if pods < 1 or n % pods:
        raise ValueError(f"{n} devices not divisible into {pods} pods")
    return jax.make_mesh((pods, 1, n // pods), ("pod", "data", "model"))

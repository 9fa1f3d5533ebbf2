"""ArrayMesh: N logical FEATHER+ arrays as a first-class axis.

The MINISA results are per-array; production serving runs many arrays.
An :class:`ArrayMesh` names that scale-out dimension for everything a
``Program`` flows through:

  * ``core/program.shard_program`` splits a lowered Program's tile space
    into one sub-Program per array (axis policy from ``dist/sharding``);
  * ``backends`` execute the shards -- the interpreter drives one
    functional machine per array, the Pallas backend wraps its
    ``pallas_call`` in a ``shard_map`` over :meth:`jax_mesh` when enough
    JAX devices back the logical arrays;
  * the runtime (``ProgramCache`` keys, ``ModelExecutable``,
    ``Scheduler``) carries the mesh shape so per-array traffic, stall and
    load-imbalance numbers are reported everywhere.

Logical vs physical: an ArrayMesh is meaningful without JAX devices --
per-array accounting and the interpreter's per-shard execution only need
the *logical* count.  :meth:`jax_mesh` returns a real device mesh when
one is available and ``None`` otherwise, and callers degrade to
sequential per-shard execution (identical numerics).  For CPU CI, export

    XLA_FLAGS=--xla_force_host_platform_device_count=8

*before* the first JAX import to back an 8-array mesh with fake host
devices (see :func:`host_device_flag`).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ArrayMesh:
    """N logical FEATHER+ arrays, optionally backed by JAX devices."""

    n_arrays: int = 1
    axis_name: str = "array"

    def __post_init__(self):
        if self.n_arrays < 1:
            raise ValueError(f"n_arrays must be >= 1, got {self.n_arrays}")

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.n_arrays,)

    def jax_mesh(self):
        """A 1-D ``jax.sharding.Mesh`` over ``n_arrays`` devices, or
        ``None`` when this host has fewer devices than logical arrays
        (callers fall back to sequential per-shard execution)."""
        if self.n_arrays < 2:
            return None
        import jax

        if len(jax.devices()) < self.n_arrays:
            return None
        from jax.sharding import AxisType
        return jax.make_mesh((self.n_arrays,), (self.axis_name,),
                             axis_types=(AxisType.Auto,))

    def degraded(self, n_down: int = 1) -> "ArrayMesh":
        """The mesh that survives ``n_down`` arrays going unhealthy --
        the failover target the scheduler re-lowers onto (never below
        one array: a fully-degraded mesh serves unsharded)."""
        return ArrayMesh(n_arrays=max(1, self.n_arrays - max(0, n_down)),
                         axis_name=self.axis_name)

    @classmethod
    def host(cls) -> "ArrayMesh":
        """One logical array per visible JAX device."""
        import jax

        return cls(n_arrays=len(jax.devices()))

    def __repr__(self) -> str:
        return f"ArrayMesh(n_arrays={self.n_arrays})"


def host_device_flag(n: int) -> str:
    """The ``XLA_FLAGS`` fragment that fakes ``n`` host CPU devices.

    Must be in the environment before the first JAX import; returned as a
    string (not applied) because setting it after ``jax`` initialises is a
    silent no-op."""
    return f"--xla_force_host_platform_device_count={n}"

"""Island-engine device mesh — the paper's distributed message-passing layer
as a 1-D JAX mesh (DESIGN.md §8).

popt4jlib scales past one machine by running island populations in separate
processes that exchange migrants over sockets. The reproduction's analogue is
a :class:`MeshConfig`: islands are laid out over a one-axis device mesh and
the whole round scan runs under ``shard_map``, so each device owns
``n_islands / devices`` islands and migration crosses shard boundaries as a
``lax.ppermute`` ring exchange (``core.migration``) — the socket hop, compiled
to a collective.

The config is deliberately tiny (device count + axis name): it reuses the
serving side's conventions (``launch/mesh.py`` builds meshes in functions so
importing never touches jax device state; ``parallel/sharding.py`` names axes
once and threads ``PartitionSpec``s everywhere) without depending on either.

Off-accelerator the same layout runs on host-platform devices::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python ...

which is how CI exercises the 8-shard ring on CPU (``tests/test_distributed``,
``benchmarks/distributed.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec

ISLAND_AXIS = "islands"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Layout of the island axis over devices: how many devices the leading
    (island) axis of every engine-state leaf is sharded across, and the mesh
    axis name the engine's collectives (``ppermute`` ring, ``all_gather``
    starvation/incumbent paths) refer to. ``devices=1`` is a valid degenerate
    mesh — the determinism contract (DESIGN.md §8) requires its trajectories
    to be bit-identical to the unsharded engine."""

    devices: int = 1          # devices the island axis shards over
    axis: str = ISLAND_AXIS   # mesh axis name used by the engine collectives

    def build(self) -> Mesh:
        """Materialize the 1-D mesh over the first ``devices`` local devices.

        Raises ``ValueError`` when the host exposes fewer devices — on CPU,
        raise the count with ``--xla_force_host_platform_device_count``.
        """
        if self.devices < 1:
            raise ValueError(f"devices must be >= 1, got {self.devices}")
        avail = jax.devices()
        if self.devices > len(avail):
            raise ValueError(
                f"MeshConfig wants {self.devices} devices but only "
                f"{len(avail)} are visible; on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={self.devices}")
        return Mesh(np.asarray(avail[: self.devices]), (self.axis,))

    def local_islands(self, n_islands: int) -> int:
        """Islands each shard owns; validates the axis divides evenly."""
        if n_islands < 1 or n_islands % self.devices:
            raise ValueError(
                f"n_islands={n_islands} must be a positive multiple of "
                f"devices={self.devices} (equal-size shards)")
        return n_islands // self.devices


def island_specs(axis: str, n_replicated: int = 1) -> tuple[tuple, tuple]:
    """``(in_specs, out_specs)`` for the engine's round scan under
    ``shard_map``: the island-stacked state pytree (first argument) shards
    its leading axis over ``axis``; the ``n_replicated`` trailing scan inputs
    are replicated to every shard. The barrier engine replicates one input
    (the round-key table); the async engine (``IslandConfig.sync_policy ==
    "async"``, DESIGN.md §13) replicates three — round keys plus the
    step/deliver schedule masks — and every shard slices its local island
    rows out of them itself, mirroring the key-table discipline."""
    specs = PartitionSpec(axis)
    return ((specs, *([PartitionSpec()] * n_replicated)),
            (specs, PartitionSpec()))


def ring_perm(n_shards: int) -> list[tuple[int, int]]:
    """``ppermute`` permutation for the migration ring: shard d sends to
    d+1 (mod n) — island ``i``'s migrants reach island ``i+1`` when the
    boundary island crosses shards."""
    return [(d, (d + 1) % n_shards) for d in range(n_shards)]


def shard_map(f: Callable, mesh: Mesh, in_specs, out_specs) -> Callable:
    """``jax.shard_map`` with replication checking off (``check_vma=False``),
    the one setting every engine/executor shard_map shares."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def host_device_count() -> int:
    """Visible device count — the ceiling for ``MeshConfig.devices``."""
    return len(jax.devices())

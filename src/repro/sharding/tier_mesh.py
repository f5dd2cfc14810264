"""Per-tier mesh slices: the multi-host rung of tier placement.

``sharding.placement`` pinned each cascade tier to a single local
``jax.Device`` — enough to overlap tier workers, not enough to serve a
tier whose params do not fit one chip. This module extends that plan so
each tier gets a contiguous **mesh slice**: a sub-``Mesh`` over >= 1
devices, sized greedily by the same traffic signal ``plan_placement``
uses (``ServeResult.tier_counts`` online, the offline replay's pending
fractions in the builder). The busiest tiers get the widest slices;
every tier always gets at least one device.

Each slice is a standard 2-D mesh with axes ``("data", "model")``:

  * "data"  — batch / FSDP axis. Batch-dim sharding splits independent
    rows across devices, and FSDP param sharding all-gathers exact
    weight values before use, so **data-only slices are bit-identical**
    to the unsharded computation on CPU devices (pinned by
    tests/test_placement.py's sharded legs). Not on a TPU: there the
    compiler tiles a contraction by the rows each chip holds, and the
    partitioner may all-reduce partial sums, so a greedy token can flip
    at a near-tie (``chip_smoke.py --chips 4`` bounds it).
  * "model" — tensor-parallel axis (``sharding.rules`` head/FFN/vocab
    rules). Width defaults to 1 because model-axis matmul reductions
    change float summation order — opt in via ``mesh_shape=(R, C)``
    with C > 1 when capacity matters more than bit-identicality.

Params are sharded by the same ``sharding.rules`` used for training
(FSDP on the scanned ``params["period"]`` stack), and
``init_params_sharded`` initialises them *sharded from birth*: the init
is jitted with the target shardings as ``out_shardings``, so each
device materialises only its own shard — a 70B-class tier never exists
unsharded on one host. jax's threefry PRNG is counter-based and
elementwise, so the values are identical regardless of mesh shape
(pinned by the determinism test).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer as T
from repro.sharding import rules


def _mesh_device_ids(mesh) -> tuple:
    return tuple(int(d.id) for d in mesh.devices.flat)


def mesh_desc(mesh) -> str:
    """'2x1@cpu:0,1' — rows x cols @ platform : device ids."""
    r, c = mesh.devices.shape
    plat = mesh.devices.flat[0].platform
    ids = ",".join(str(i) for i in _mesh_device_ids(mesh))
    return f"{r}x{c}@{plat}:{ids}"


@dataclasses.dataclass(frozen=True)
class TierMeshPlan:
    """A mesh-slice assignment for one cascade: ``slices[j]`` hosts tier j."""

    slices: tuple                  # one jax.sharding.Mesh per cascade tier
    shares: tuple | None = None    # traffic share the sizing used
    grid: tuple = (1, 1)           # (rows, cols) of the device grid planned

    def for_tier(self, j: int):
        return self.slices[j]

    @property
    def devices_per_tier(self) -> tuple:
        return tuple(m.devices.size for m in self.slices)

    @property
    def n_distinct(self) -> int:
        """Distinct device *sets* (slices may share rows when the grid
        has fewer rows than the cascade has tiers)."""
        return len({_mesh_device_ids(m) for m in self.slices})

    def describe(self, names: Sequence[str] | None = None) -> str:
        parts = []
        for j, m in enumerate(self.slices):
            nm = names[j] if names else f"tier{j}"
            share = (f" ({self.shares[j]:.2f})" if self.shares is not None
                     else "")
            parts.append(f"{nm}{share} -> {mesh_desc(m)}")
        return ", ".join(parts)


def plan_tier_meshes(n_tiers: int, mesh_shape: tuple | None = None,
                     devices: Sequence | None = None,
                     tier_counts: Sequence[float] | None = None
                     ) -> TierMeshPlan:
    """Assign each of ``n_tiers`` cascade tiers a contiguous mesh slice.

    The available devices form an ``R x C`` grid (``mesh_shape``; default
    ``(len(devices), 1)`` — data-parallel only). Rows are the unit of
    allocation: every tier gets >= 1 whole row (C devices wide on the
    "model" axis), and the remaining rows go to tiers greedily by
    traffic share (highest share-per-row first — D'Hondt apportionment,
    so a tier carrying 90% of the traffic ends up with ~90% of the spare
    rows). Slices are contiguous row ranges in tier order. With fewer
    rows than tiers, tiers wrap round-robin onto shared rows (degenerate
    single-row grid == today's shared-device behaviour). Deterministic:
    ties break on ascending tier index.
    """
    if n_tiers < 1:
        raise ValueError(f"n_tiers must be >= 1, got {n_tiers}")
    if tier_counts is not None and len(tier_counts) != n_tiers:
        raise ValueError(f"tier_counts must have {n_tiers} entries, "
                         f"got {len(tier_counts)}")
    devs = list(devices) if devices is not None else list(jax.local_devices())
    if not devs:
        raise ValueError("no devices to slice tiers over")
    if mesh_shape is None:
        rows_n, cols = len(devs), 1
    else:
        rows_n, cols = int(mesh_shape[0]), int(mesh_shape[1])
    if rows_n < 1 or cols < 1:
        raise ValueError(f"mesh_shape must be positive, got {mesh_shape}")
    if rows_n * cols > len(devs):
        raise ValueError(f"mesh_shape {rows_n}x{cols} needs "
                         f"{rows_n * cols} devices, have {len(devs)}")
    grid = np.array(devs[:rows_n * cols], dtype=object).reshape(rows_n, cols)

    def slice_mesh(r0: int, r1: int) -> Mesh:
        return Mesh(grid[r0:r1], ("data", "model"))

    shares = None
    if tier_counts is not None and sum(tier_counts) > 0:
        total = float(sum(tier_counts))
        shares = tuple(float(c) / total for c in tier_counts)

    if rows_n < n_tiers:
        # fewer rows than tiers: share rows round-robin (contiguous
        # single-row slices), like plan_placement's fallback
        slices = tuple(slice_mesh(j % rows_n, j % rows_n + 1)
                       for j in range(n_tiers))
        return TierMeshPlan(slices, shares, (rows_n, cols))

    counts = [1] * n_tiers                 # every tier gets >= 1 row
    spare = rows_n - n_tiers
    eff = shares if shares is not None else tuple([1.0] * n_tiers)
    for _ in range(spare):
        # D'Hondt: next row to the tier with the highest share per row
        j = max(range(n_tiers), key=lambda j: (eff[j] / counts[j], -j))
        counts[j] += 1
    starts = np.concatenate([[0], np.cumsum(counts)])
    slices = tuple(slice_mesh(int(starts[j]), int(starts[j + 1]))
                   for j in range(n_tiers))
    return TierMeshPlan(slices, shares, (rows_n, cols))


# ---------------------------------------------------------------------------
# Sharding a tier over its slice
# ---------------------------------------------------------------------------


def batch_sharding(mesh, n_rows: int) -> NamedSharding:
    """Batch-dim sharding for a (n_rows, ...) array on a slice —
    replicated when the row count does not divide the data axis (the
    engine's pow2 batch buckets normally do)."""
    d = mesh.shape["data"]
    return NamedSharding(mesh, P("data") if d > 1 and n_rows % d == 0
                         else P())


def tier_param_shardings(params, mesh):
    """NamedShardings for a tier's params on its slice: tensor axes over
    "model" per sharding.rules, FSDP over "data" (exact — FSDP
    all-gathers full values before use)."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)
        if not hasattr(x, "shape") else x, params)
    return rules.params_shardings(shapes, mesh, fsdp=True)


def shard_params(params, mesh):
    """device_put a tier's params onto its slice per the rules shardings
    (the across-slice-boundary transfer when a tier moves slices)."""
    return jax.device_put(params, tier_param_shardings(params, mesh))


def init_params_sharded(key, cfg, mesh, *, fold: bool = True):
    """Initialise a tier's params *sharded from birth* on its slice.

    The init function is jitted with the target shardings as
    ``out_shardings``, so XLA materialises each param directly in its
    sharded layout — no host-side full copy ever exists. The
    partitionable threefry lowering is forced on for the init call:
    it generates bits as a pure elementwise function of the counter,
    so the same (key, cfg) gives bit-identical params on a 1x1 and an
    8x1 slice (tests/test_tier_mesh.py pins this). The legacy lowering
    (jax_threefry_partitionable=False, the 0.4.x default) is NOT
    sharding-invariant — XLA partitions its batched hash loop and each
    shard draws different bits. ``fold=True`` folds homogeneous
    prefix/suffix into the scanned stack first, so the whole depth is
    one FSDP-shardable stacked leaf per weight.
    """
    if fold:
        cfg = T.fold_config(cfg)

    def init(k):
        return T.init_params(k, cfg)

    shapes = jax.eval_shape(init, key)
    shardings = rules.params_shardings(shapes, mesh, fsdp=True)
    prev = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        params = jax.jit(init, out_shardings=shardings)(key)
    finally:
        jax.config.update("jax_threefry_partitionable", prev)
    return cfg, params

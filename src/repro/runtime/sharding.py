"""Where the graph fleet lives on the mesh.

``matrix_batch_sharding`` spreads a fit's (B, ...) batch over the
data-parallel axes; ``fleet_placement`` gives each ragged-router bucket
its own device subset, so serving runs without collectives (DESIGN.md
§14).  The LM stack's logical-axis rules are in runtime/lm_sharding.py.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dp_axes(mesh: Mesh):
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def matrix_batch_sharding(mesh: Mesh, ndim: int,
                          batch: Optional[int] = None) -> NamedSharding:
    """Sharding for a leading matrix-batch axis (the batched eigenspace
    engine, DESIGN.md §7): axis 0 (the B independent matrices /
    factorizations / signal blocks) spreads over the data-parallel mesh
    axes, everything else is replicated.  Used by
    core/eigenbasis.py::ApproxEigenbasis for (B, n, n) inputs, (B, S, P)
    staged tables and (B, R, n) signal batches.

    ``batch``: the leading-dim size; the largest (order-preserving) subset
    of data-parallel axes whose product divides it is used, so an awkward
    B degrades to partial sharding or replication instead of raising a
    divisibility error (e.g. (pod=4, data=2) with B=6 shards over "data"
    alone rather than replicating)."""
    dp = dp_axes(mesh)
    if batch is not None:
        best, best_p = (), 1
        for r in range(len(dp), 0, -1):
            for combo in itertools.combinations(dp, r):
                p = int(np.prod([mesh.shape[a] for a in combo]))
                if p > best_p and batch % p == 0:
                    best, best_p = combo, p
        dp = best
    return NamedSharding(mesh, P(dp or None, *(None,) * (ndim - 1)))


# ---------------------------------------------------------------------------
# Fleet placement: whole ragged-router buckets -> disjoint device subsets.
#
# Serving wants the opposite of a fit's "spread one batch over everything":
# each bucket (and each graph within it) must live end-to-end on ONE device
# so the steady-state step program contains zero cross-device collectives
# (verifiable via runtime/hlo_analysis.py::collective_bytes).  A
# BucketPlacement is a frozen, hashable record of which global device ids
# own a bucket — hashable so it can ride inside an ApplyPlan as part of the
# compiled-program cache key (kernels/plan.py).  Graphs partition along the
# batch axis over the bucket's own single-axis sub-mesh; batches that don't
# divide the device count are padded with structural no-op rows
# (repro/tables.py::pad_batch) rather than resharded.
# ---------------------------------------------------------------------------


def assign_buckets(num_devices: int, bucket_sizes: Mapping[Any, int],
                   weights: Optional[Mapping[Any, float]] = None,
                   ) -> Dict[Any, Tuple[int, ...]]:
    """Pure assignment logic: bucket key -> device *indices* 0..D-1.

    Deterministic greedy proportional allocation (largest
    weight-per-allocated-device next), contiguous disjoint ranges, each
    bucket at least one device, never more devices than the bucket has
    graphs (extra devices would only serve padding).  With more buckets
    than devices, buckets share devices round-robin.  ``weights`` defaults
    to the bucket batch sizes; the ragged router passes batch x width so
    wide buckets get proportionally more devices."""
    if num_devices <= 0:
        raise ValueError(f"assign_buckets: num_devices={num_devices} "
                         "must be positive")
    keys = sorted(bucket_sizes)
    if not keys:
        return {}
    if any(bucket_sizes[k] <= 0 for k in keys):
        bad = {k: bucket_sizes[k] for k in keys if bucket_sizes[k] <= 0}
        raise ValueError(f"assign_buckets: empty buckets {bad}")
    if len(keys) > num_devices:
        return {k: (i % num_devices,) for i, k in enumerate(keys)}
    w = np.array([float((weights or bucket_sizes)[k]) for k in keys])
    w = np.maximum(w, 1e-9)
    cap = np.array([int(bucket_sizes[k]) for k in keys])
    alloc = np.ones(len(keys), dtype=int)
    for _ in range(num_devices - len(keys)):
        score = w / alloc
        score[alloc >= cap] = -1.0
        i = int(np.argmax(score))
        if score[i] < 0:
            break  # every bucket saturated: surplus devices stay idle
        alloc[i] += 1
    out: Dict[Any, Tuple[int, ...]] = {}
    nxt = 0
    for k, a in zip(keys, alloc):
        out[k] = tuple(range(nxt, nxt + int(a)))
        nxt += int(a)
    return out


def data_devices(mesh: Mesh):
    """The mesh's data-parallel device list (non-DP axes indexed at 0):
    the pool fleet_placement carves bucket subsets out of."""
    idx = tuple(slice(None) if a in ("pod", "data") else 0
                for a in mesh.axis_names)
    return list(np.asarray(mesh.devices[idx]).ravel())


@lru_cache(maxsize=None)
def _submesh(device_ids: Tuple[int, ...]) -> Mesh:
    by_id = {d.id: d for d in jax.devices()}
    missing = [i for i in device_ids if i not in by_id]
    if missing:
        raise ValueError(
            f"placement names device ids {missing} but this process has "
            f"{len(by_id)} device(s) (ids {sorted(by_id)}); re-place with "
            "fleet_placement on the current mesh")
    return Mesh(np.array([by_id[i] for i in device_ids]), ("data",))


@dataclass(frozen=True)
class BucketPlacement:
    """Which global device ids own one bucket, and its true batch size.

    Frozen + tuple-valued -> hashable, so plans carrying a placement stay
    valid lru_cache keys.  ``batch_padded`` is the serving-time leading dim:
    the smallest multiple of the device count >= batch (pad rows are
    structural no-ops, see tables.pad_batch)."""
    device_ids: Tuple[int, ...]
    batch: int

    def __post_init__(self):
        if not self.device_ids:
            raise ValueError("BucketPlacement needs at least one device")
        if self.batch <= 0:
            raise ValueError(f"BucketPlacement: batch={self.batch}")

    @property
    def num_devices(self) -> int:
        return len(self.device_ids)

    @property
    def batch_padded(self) -> int:
        d = self.num_devices
        return -(-self.batch // d) * d

    def mesh(self) -> Mesh:
        return _submesh(self.device_ids)

    def sharding(self, ndim: int) -> NamedSharding:
        """Leading (batch) axis split over the bucket's devices."""
        return NamedSharding(self.mesh(), P("data", *(None,) * (ndim - 1)))

    def place(self, arr):
        """Pad axis 0 with zero rows to batch_padded and device_put.

        For staged tables use tables.pad_batch first (pads are identity
        rotations there, not zeros) and place each leaf with this."""
        arr = jax.numpy.asarray(arr)
        pad = self.batch_padded - arr.shape[0]
        if pad > 0:
            arr = jax.numpy.concatenate(
                [arr, jax.numpy.zeros((pad,) + arr.shape[1:], arr.dtype)])
        elif arr.shape[0] != self.batch_padded:
            raise ValueError(
                f"place: leading dim {arr.shape[0]} exceeds "
                f"batch_padded={self.batch_padded}")
        return jax.device_put(arr, self.sharding(arr.ndim))

    def place_leaf(self, arr):
        """device_put an already-padded leaf (no shape change)."""
        if arr.shape[0] != self.batch_padded:
            raise ValueError(
                f"place_leaf: leading dim {arr.shape[0]} != "
                f"batch_padded={self.batch_padded}")
        return jax.device_put(arr, self.sharding(arr.ndim))


class FleetPlacement:
    """Bucket key -> BucketPlacement over one serving mesh (disjoint
    device subsets; a bucket's refit can only occupy its own devices)."""

    def __init__(self, buckets: Mapping[Any, BucketPlacement],
                 num_devices: int):
        self.buckets = dict(buckets)
        self.num_devices = int(num_devices)

    def __getitem__(self, key) -> BucketPlacement:
        return self.buckets[key]

    def __contains__(self, key) -> bool:
        return key in self.buckets

    def items(self):
        return self.buckets.items()

    def manifest(self) -> Dict[str, Any]:
        """JSON-serializable placement record for shard-aware checkpoints."""
        return {
            "num_devices": self.num_devices,
            "buckets": {str(k): {"device_ids": list(p.device_ids),
                                 "batch": p.batch}
                        for k, p in self.buckets.items()},
        }


def fleet_placement(mesh: Mesh, bucket_sizes: Mapping[Any, int],
                    weights: Optional[Mapping[Any, float]] = None,
                    ) -> FleetPlacement:
    """Assign whole ragged-router buckets to the mesh's data-axis devices.

    Each bucket gets a contiguous, disjoint device subset sized by
    ``weights`` (default: batch count; the router passes batch x width).
    Within a bucket, whole graphs partition along the batch axis over the
    subset — no tensor is ever split across devices, which is what makes
    the steady-state step HLO collective-free."""
    devs = data_devices(mesh)
    assignment = assign_buckets(len(devs), bucket_sizes, weights)
    buckets = {
        k: BucketPlacement(
            device_ids=tuple(devs[i].id for i in idxs),
            batch=int(bucket_sizes[k]))
        for k, idxs in assignment.items()}
    return FleetPlacement(buckets, num_devices=len(devs))


def single_bucket_placement(mesh: Mesh, batch: int) -> BucketPlacement:
    """All data-axis devices as one bucket (the non-ragged engine)."""
    return fleet_placement(mesh, {"all": batch})["all"]

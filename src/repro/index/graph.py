"""Fixed-degree proximity-graph container (single-slice and sharded).

TPU-friendly representation: one dense int32 array `neighbors[N, R]`
(padded with -1). Fixed out-degree makes every traversal step a static-shape
gather + distance block, which is what the lockstep search engine and the
Pallas distance kernel consume.

For index-axis sharding (`core.sharded`), `ShardedGraphIndex` holds one
independent `GraphIndex` per contiguous corpus slice. Each shard graph uses
shard-*local* node ids in [0, n_s) — edges never cross slices — and carries
its slice coordinates (`shard`, `offset`) so validation errors name the
offending shard instead of surfacing later as a silent bad gather.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def random_regular_neighbors(n: int, degree: int, rng) -> np.ndarray:
    """Self-loop-free random out-degree-`degree` neighbor lists [n, degree].

    A graph of this shape at any N in well under a second: it exercises
    the traversal, the kernels and the memory layout at scales the host
    NN-descent builder cannot reach in time. It is not a proximity graph,
    so recall on it says nothing about the index.
    """
    nb = rng.integers(0, n, size=(n, degree)).astype(np.int32)
    rows = np.arange(n, dtype=np.int32)[:, None]
    return np.where(nb == rows, (nb + 1) % n, nb).astype(np.int32)


@dataclasses.dataclass
class GraphIndex:
    neighbors: np.ndarray  # [N, R] int32, -1 padded, shard-local ids
    entry_point: int       # medoid node id (shard-local)
    dim: int
    shard: int | None = None  # shard ordinal when part of a ShardedGraphIndex
    offset: int = 0           # global row id of local row 0 (slice start)

    @property
    def n(self) -> int:
        return self.neighbors.shape[0]

    @property
    def degree(self) -> int:
        return self.neighbors.shape[1]

    def out_degrees(self) -> np.ndarray:
        return (self.neighbors >= 0).sum(axis=1)

    def _where(self) -> str:
        """Locator suffix for error messages: which shard/slice is bad."""
        if self.shard is None:
            return ""
        return (f" (shard {self.shard}, global rows "
                f"[{self.offset}, {self.offset + self.n}))")

    def validate(self) -> None:
        """Structural invariants the traversal stack relies on.

        Raises TypeError/ValueError with actionable messages (`assert`
        would vanish under `python -O`, silently admitting a graph whose
        out-of-range ids scribble across the visited bitset and gathers).
        `SearchEngine.build` calls this on every engine construction; for
        sharded graphs every message carries the shard/slice coordinates,
        because an id that is ≥ n_s but < N is a *cross-shard* edge — valid
        globally, fatal locally — and the global range alone can't show it.
        """
        if self.neighbors.ndim != 2:
            raise ValueError(
                f"neighbors must be [N, R], got shape "
                f"{self.neighbors.shape}{self._where()}")
        n, r = self.neighbors.shape
        if self.neighbors.dtype != np.int32:
            raise TypeError(
                f"neighbors must be int32 (the gather/bitset index type), "
                f"got {self.neighbors.dtype}{self._where()}; cast with "
                ".astype(np.int32) after checking ids fit")
        mx = int(self.neighbors.max())
        if mx >= n:
            row = int(np.argmax(self.neighbors.max(axis=1) >= n))
            raise ValueError(
                f"neighbor id {mx} out of range for N={n} nodes (first bad "
                f"row: local {row} = global {self.offset + row})"
                f"{self._where()} — ids must be shard-local; a value in "
                f"[{n}, ∞) usually means a global id leaked into a shard "
                "slice")
        mn = int(self.neighbors.min())
        if mn < -1:
            raise ValueError(
                f"neighbor id {mn} < -1 (only -1 marks an empty slot)"
                f"{self._where()}")
        rows = np.arange(n)[:, None]
        valid = self.neighbors >= 0
        loops = np.any((self.neighbors == rows) & valid, axis=1)
        if loops.any():
            bad = int(np.argmax(loops))
            raise ValueError(
                f"self loop at node {bad} ({int(loops.sum())} total)"
                f"{self._where()} — prune self edges before building an "
                "engine")
        if not 0 <= self.entry_point < n:
            raise ValueError(
                f"entry_point {self.entry_point} outside [0, {n})"
                f"{self._where()}")

    def save(self, path: str) -> None:
        np.savez_compressed(
            path, neighbors=self.neighbors, entry_point=self.entry_point, dim=self.dim
        )

    @staticmethod
    def load(path: str) -> "GraphIndex":
        z = np.load(path)
        return GraphIndex(
            neighbors=z["neighbors"].astype(np.int32),
            entry_point=int(z["entry_point"]),
            dim=int(z["dim"]),
        )


@dataclasses.dataclass
class ShardedGraphIndex:
    """S independent per-slice graphs over one corpus (JAG-style partition).

    Slices are contiguous and equal-sized: shard s owns global rows
    [s·n_s, (s+1)·n_s). Every shard graph is self-contained (local ids,
    its own medoid entry point), which is what lets per-shard traversal run
    with an unmodified lockstep loop; the cross-shard top-k merge
    (`distributed.merge`) is the only global operation.
    """

    shards: list  # [S] GraphIndex, equal n and degree

    def __post_init__(self):
        if not self.shards:
            raise ValueError("ShardedGraphIndex needs at least one shard")
        ns = {g.n for g in self.shards}
        if len(ns) != 1:
            raise ValueError(
                f"shard sizes must match for stacked shard_map placement, "
                f"got {sorted(ns)}")
        rs = {g.degree for g in self.shards}
        if len(rs) != 1:
            raise ValueError(f"shard degrees must match, got {sorted(rs)}")

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def shard_size(self) -> int:
        return self.shards[0].n

    @property
    def n(self) -> int:
        return self.shard_size * self.n_shards

    @property
    def degree(self) -> int:
        return self.shards[0].degree

    @property
    def dim(self) -> int:
        return self.shards[0].dim

    @property
    def offsets(self) -> np.ndarray:
        """[S] global row id of each shard's local row 0."""
        return np.asarray([g.offset for g in self.shards], np.int32)

    @property
    def entry_points(self) -> np.ndarray:
        """[S] shard-local entry node ids."""
        return np.asarray([g.entry_point for g in self.shards], np.int32)

    def validate(self) -> None:
        for s, g in enumerate(self.shards):
            if g.shard != s:
                raise ValueError(
                    f"shard list order broken: position {s} holds shard "
                    f"{g.shard}")
            if g.offset != s * self.shard_size:
                raise ValueError(
                    f"shard {s} offset {g.offset} != contiguous slice start "
                    f"{s * self.shard_size}")
            g.validate()

"""Memory tiering for the full-precision vector store.

With a quantized traversal (int8 / PQ), device memory only needs the
compressed codes — the float32 vectors are touched exactly once per query,
by the terminal rerank, and only for the ≤ (M + K) pool rows that survived.
That access pattern (tiny, batched, index-driven) is what lets the float32
store leave the device entirely:

  VMEM   per-step traversal working set (queue merge, persistent kernel)
  HBM    compressed codes + norms + err, graph, packed attributes
  host   float32 vectors — `HostVectorStore`, streamed per rerank batch

`HostVectorStore` keeps the float32 store as host numpy. A rerank batch
gathers its rows there and makes one host→device copy of the [B, P, d]
result, so the device never holds the [N, d] float32 array, which is the
term that bounded N before tiering (float32 d=64 at 10M rows = 2.4 GiB vs
56 B/vec PQ = 0.5 GiB). There is no `pinned_host` placement: an XLA gather
cannot mix a host-memory operand with device indices, and a failed
placement must raise, not pick another path.

`DeviceVectorStore` is the degenerate tier for small corpora and float32
engines — same gather interface, vectors device-resident.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class DeviceVectorStore:
    """Device-resident float32 vector tier (the pre-tiering layout)."""

    kind = "device"

    def __init__(self, vectors):
        self.vectors = jnp.asarray(vectors, jnp.float32)

    @property
    def shape(self):
        return tuple(self.vectors.shape)

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape)) * 4

    def gather(self, idx) -> jax.Array:
        """Rows `idx` [B, P] (negative ids clipped to row 0 — callers mask
        by validity, matching `exact_rerank`'s clip-then-mask contract)."""
        return self.vectors[jnp.maximum(jnp.asarray(idx), 0)]


class HostVectorStore:
    """Host-memory float32 vector tier with batched streaming gather."""

    kind = "host"

    def __init__(self, vectors, chunk_rows: int = 1 << 18):
        self._np = np.ascontiguousarray(np.asarray(vectors), np.float32)
        self._chunk = int(chunk_rows)

    @property
    def shape(self):
        return tuple(self._np.shape)

    @property
    def nbytes(self) -> int:
        return self._np.nbytes

    def gather(self, idx) -> jax.Array:
        """Stream rows `idx` [B, P] to device; negative ids clip to row 0.

        P is the rerank pool width (≤ M + K), so the transferred slab is
        B·P·d floats per batch — independent of N. Very large requests
        stream in `chunk_rows` row-chunks to bound peak host scratch.
        """
        idx = np.maximum(np.asarray(idx), 0)
        flat = idx.reshape(-1)
        if flat.size <= self._chunk:
            rows = self._np[flat]
        else:
            rows = np.empty((flat.size, self._np.shape[1]), np.float32)
            for s in range(0, flat.size, self._chunk):
                e = min(s + self._chunk, flat.size)
                rows[s:e] = self._np[flat[s:e]]
        return jnp.asarray(rows.reshape(*idx.shape, self._np.shape[1]))


def as_vector_store(vectors, tier: str = "device"):
    """Construct the tier named by `tier` ("device" | "host")."""
    if tier == "device":
        return DeviceVectorStore(vectors)
    if tier == "host":
        return HostVectorStore(vectors)
    raise ValueError(f"unknown vector tier {tier!r} "
                     "(expected 'device' or 'host')")

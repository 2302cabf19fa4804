"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode -- the
kernel body runs in Python for correctness validation; on TPU the same
calls compile to Mosaic.

Also hosts the sorted-coordinate co-iteration primitives used by the
vectorized execution backend (``repro.core.vectorized``): skip-ahead
intersection and merge-path union over *offset-keyed* fibers (many
fibers packed into one globally sorted key array).  The module-level
seam functions (``intersect_keys`` / ``union_k_keys`` / ``lookup_keys``
/ ``segmented_reduce``) dispatch through the pluggable kernel-backend
registry in ``repro.kernels.backends`` -- numpy ``searchsorted``
reference lowerings, jitted XLA programs, or the Pallas kernels
(interpret mode on CPU, Mosaic on TPU) -- selected per process via
``$REPRO_KERNEL_BACKEND`` (see ``backends.resolve_kernel_backend``).
``VectorBackend`` holds its own resolved backend instance and bypasses
these wrappers; they remain the stable entry points for tests and
external callers.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import block_sparse_matmul as _bsmm
from repro.kernels import flash_attention as _fa
from repro.kernels import intersect as _isect
from repro.kernels import ssd_chunk as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128) -> jnp.ndarray:
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=not _on_tpu())


def ssd_chunk(x, a, b, c) -> jnp.ndarray:
    return _ssd.ssd_chunk(x, a, b, c, interpret=not _on_tpu())


def intersect_sorted(a, b) -> jnp.ndarray:
    return _isect.intersect_sorted(a, b, interpret=not _on_tpu())


def multi_merge_ranks(arrs) -> jnp.ndarray:
    return _isect.multi_merge_ranks(arrs, interpret=not _on_tpu())


def pad_sorted(coords: np.ndarray) -> np.ndarray:
    """Pad a sorted int32 coordinate array with INT32_MAX to the next
    power-of-two multiple of the rank kernel's block (the kernels'
    input contract; one compile per power of two)."""
    out = np.full(_isect.bucket(len(coords)), np.iinfo(np.int32).max,
                  np.int32)
    out[:len(coords)] = coords
    return out


# ---------------------------------------------------------------------- #
# offset-keyed co-iteration primitives (vector backend entry points)
# ---------------------------------------------------------------------- #
def _kb():
    """The process-default kernel backend (env-resolved per call, so
    tests may flip ``$REPRO_KERNEL_BACKEND`` between calls)."""
    from repro.kernels import backends as _backends
    return _backends.resolve_kernel_backend()


def intersect_keys(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Positions in ``b`` of every element of ``a`` (both sorted int64
    key arrays; keys unique per array), -1 where absent.

    Dispatches to the active kernel backend: numpy ``searchsorted``,
    a jitted XLA binary search, or the Pallas rank kernel (int32 key
    domain)."""
    return _kb().intersect_keys(a, b)


def union_keys(a: np.ndarray, b: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sorted union of two sorted int64 key arrays (keys unique per
    array).  Returns (union, pos_a, pos_b): for every union element its
    position in ``a`` / ``b`` or -1.

    Pallas backends run the merge-rank kernel + host dedup."""
    return _kb().union_keys(a, b)


def union_k_keys(arrays) -> Tuple[np.ndarray, list]:
    """Sorted union of k sorted int64 key arrays (keys unique per
    array).  Returns (union, [pos_i]): for every union element its
    position in array i, or -1 where absent.

    The pallas backends rank every element in the stable k-way merge
    with the ``multi_merge_ranks`` kernel (any k); numpy runs a
    concatenate-and-unique ``searchsorted`` lowering."""
    return _kb().union_k_keys(arrays)


def lookup_keys(hay: np.ndarray, probes: np.ndarray) -> np.ndarray:
    """Gather path for ``Lookup`` IR ops: positions in ``hay`` (sorted
    int64, unique) of every ``probes`` element (arbitrary order,
    duplicates fine), -1 where absent.

    Pallas backends sort the probes, push them through the intersection
    kernel, and unsort; numpy is one vectorized ``searchsorted``."""
    return _kb().lookup_keys(hay, probes)


def lookup_keys_shifted(hay: np.ndarray, probes: np.ndarray,
                        shift: int = 0) -> np.ndarray:
    """Affine-shifted gather: positions in ``hay`` of ``probes + shift``,
    -1 where absent.  Negative shifted probes are reported as misses
    *before* dispatch -- a negative coordinate folded into an offset-key
    pack would alias into the preceding fiber's key range.

    The shift folds into the probe stream, so this rides the exact same
    kernel-backend seam as ``lookup_keys``."""
    probes = np.asarray(probes, dtype=np.int64)
    shifted = probes + int(shift)
    neg = shifted < 0
    if neg.any():
        idx = lookup_keys(hay, np.where(neg, 0, shifted))
        return np.where(neg, -1, idx)
    return lookup_keys(hay, shifted)


def intersect_keys_shifted(a: np.ndarray, b: np.ndarray,
                           shift: int = 0) -> np.ndarray:
    """Positions in ``b`` of every element of ``a + shift`` (windowed
    intersection: a constant shift keeps ``a`` sorted, so the shifted
    stream reuses ``intersect_keys``\'s skip-ahead kernel unchanged).
    Negative shifted elements are misses (-1)."""
    a = np.asarray(a, dtype=np.int64)
    shifted = a + int(shift)
    neg = shifted < 0
    if neg.any():
        idx = np.full(len(a), -1, dtype=np.int64)
        idx[~neg] = intersect_keys(shifted[~neg], b)
        return idx
    return intersect_keys(shifted, b)


def segmented_reduce(vals: np.ndarray, starts: np.ndarray,
                     semiring=None,
                     group_ids: Optional[np.ndarray] = None) -> np.ndarray:
    """Semiring-parameterized segmented reduction over a fused-key-sorted
    value stream: ``starts[g]`` is the first index of group ``g``
    (ascending, ``starts[0] == 0``); returns one reduced value per group.
    Values fold strictly left-to-right within each group, bit-identical
    to the interpreter\'s sequential ``semiring.add`` chain (lowering
    notes: ``backends.NumpyKernels.segmented_reduce``)."""
    return _kb().segmented_reduce(vals, starts, semiring,
                                  group_ids=group_ids)


# ---------------------------------------------------------------------- #
# block-sparse matmul: host-side tile compaction (the SIGMA filter
# cascade S = take(A, B, 0); T = take(A, S, 0) at tile granularity)
# ---------------------------------------------------------------------- #
def compact_tiles(a: np.ndarray, bm: int = 128, bk: int = 128
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Compact the nonzero (bm x bk) tiles of ``a``.

    Returns (a_tiles [T, bm, bk], rows [T], cols [T]) sorted by
    (row, col), padded so every tile-row appears at least once (zero
    tile at col 0) -- guaranteeing each output block is initialized.
    """
    a = np.asarray(a)
    m, k = a.shape
    assert m % bm == 0 and k % bk == 0
    nr, nc = m // bm, k // bk
    tiles, rows, cols = [], [], []
    for i in range(nr):
        row_tiles = 0
        for j in range(nc):
            t = a[i * bm:(i + 1) * bm, j * bk:(j + 1) * bk]
            if np.any(t != 0):
                tiles.append(t)
                rows.append(i)
                cols.append(j)
                row_tiles += 1
        if row_tiles == 0:                      # keep output block defined
            tiles.append(np.zeros((bm, bk), a.dtype))
            rows.append(i)
            cols.append(0)
    return (np.stack(tiles), np.asarray(rows, np.int32),
            np.asarray(cols, np.int32))


def block_sparse_matmul(a_tiles, rows, cols, b, m: int,
                        bn: int = 128) -> jnp.ndarray:
    return _bsmm.block_sparse_matmul(a_tiles, rows, cols, b, m=m, bn=bn,
                                     interpret=not _on_tpu())


def block_sparse_matmul_dense_a(a: np.ndarray, b, bm: int = 128,
                                bk: int = 128, bn: int = 128
                                ) -> jnp.ndarray:
    """Convenience: compact a dense-with-zero-tiles A, then multiply."""
    tiles, rows, cols = compact_tiles(np.asarray(a), bm, bk)
    return block_sparse_matmul(tiles, rows, cols, b, m=a.shape[0], bn=bn)

"""Sorted-coordinate rank Pallas TPU kernel (ExTensor intersection and
merge-path union, adapted).

ExTensor's [MICRO'19] skip-ahead intersection unit walks two sorted
coordinate fibers and jumps over non-matching runs in ~1 cycle.  TPUs
have no pointer-chasing unit, and Mosaic has no vector-index gather, so
the skip happens in two steps:

1. XLA locates each block of A in B: one ``searchsorted`` of the
   block's first and last key gives the window of B tiles that can
   hold a key of the block (everything before the window is smaller,
   everything after it larger).  The window bounds reach the kernel as
   scalar prefetch.
2. The kernel DMAs the window's tiles from HBM one at a time and ranks
   by broadcast compare-and-count: every key of the A block (one
   ``(8, 128)`` vreg) is compared with every key of the B tile (each
   tile row broadcast over the sublanes, the lanes rotated through all
   128 offsets), accumulating ``#(b < x)`` and ``#(b == x)``.

Keys are int32, laid out ``(rows, 128)``; arrays are padded with
INT32_MAX (``PAD``) to a power-of-two multiple of ``BLOCK`` keys, so
compiles grow with log n.  Nothing is held whole in VMEM: one A block,
one B tile and the two accumulators.

From the two counts: the intersection position of ``x`` in B is
``lt`` where ``eq > 0``; the stable k-way merge rank of ``x`` (row i) is
``sum_j lt_j + sum_{j<i} eq_j``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PAD = jnp.iinfo(jnp.int32).max
SUBLANES, LANES = 8, 128
#: keys per A block and per B tile: one int32 vreg
BLOCK = SUBLANES * LANES
#: grid steps per pallas_call: bounds the two scalar-prefetch window
#: arrays (2 x 4 B per step) well inside v5e's 1 MiB of SMEM
MAX_GRID = 8192


def bucket(n: int) -> int:
    """Padded length for ``n`` keys: the next power of two, at least
    one block."""
    return max(BLOCK, 1 << max(n - 1, 0).bit_length())


def _rank_kernel(lo_ref, nt_ref, a_ref, b_hbm, lt_ref, eq_ref, buf, sem):
    i = pl.program_id(0)
    a = a_ref[...]                                   # (8, 128) sorted
    lo = lo_ref[i]

    def tile(t, carry):
        cp = pltpu.make_async_copy(
            b_hbm.at[pl.ds((lo + t) * SUBLANES, SUBLANES)], buf, sem)
        cp.start()
        cp.wait()
        b = buf[...]

        def shift(s, c):
            lt, eq = c
            bs = pltpu.roll(b, s, 1)
            for r in range(SUBLANES):
                row = jnp.broadcast_to(bs[r:r + 1, :], (SUBLANES, LANES))
                lt = lt + (row < a).astype(jnp.int32)
                eq = eq + (row == a).astype(jnp.int32)
            return lt, eq

        return jax.lax.fori_loop(0, LANES, shift, carry)

    z = jnp.zeros((SUBLANES, LANES), jnp.int32)
    lt, eq = jax.lax.fori_loop(0, nt_ref[i], tile, (z, z))
    # every B key before the window's first tile is below the block
    lt_ref[...] = lt + lo * BLOCK
    eq_ref[...] = eq


def rank_sorted(a: jnp.ndarray, b: jnp.ndarray, interpret: bool = False
                ) -> tuple:
    """a: [na], b: [nb] int32 sorted, PAD-padded to ``bucket`` lengths.
    Returns (lt, eq) [na] int32: the number of keys of ``b`` below /
    equal to each key of ``a`` (meaningless for pads of ``a``).

    Traceable: callers jit it together with what they derive."""
    na, nb = a.shape[0], b.shape[0]
    blocks = a.reshape(-1, BLOCK)
    first = blocks[:, 0]
    # the last real key; an all-pad block gets an empty window
    last = jnp.max(jnp.where(blocks != PAD, blocks,
                             jnp.iinfo(jnp.int32).min), axis=1)
    ws = jnp.searchsorted(b, first, side="left").astype(jnp.int32)
    we = jnp.searchsorted(b, last, side="right").astype(jnp.int32)
    lo = ws // BLOCK
    nt = jnp.maximum((we + BLOCK - 1) // BLOCK - lo, 0)
    a2 = a.reshape(-1, LANES)
    b2 = b.reshape(-1, LANES)
    spec = pl.BlockSpec((SUBLANES, LANES), lambda i, lo, nt: (i, 0))
    call = pl.pallas_call(
        _rank_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(min(len(first), MAX_GRID),),
            in_specs=[spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[spec, spec],
            scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.int32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct(
            (min(na, MAX_GRID * BLOCK) // LANES, LANES), jnp.int32)] * 2,
        interpret=interpret)
    lts, eqs = [], []
    rows = MAX_GRID * SUBLANES
    for c in range(0, len(first), MAX_GRID):
        lt, eq = call(lo[c:c + MAX_GRID], nt[c:c + MAX_GRID],
                      a2[c * SUBLANES:c * SUBLANES + rows], b2)
        lts.append(lt)
        eqs.append(eq)
    return (jnp.concatenate(lts).reshape(na),
            jnp.concatenate(eqs).reshape(na))


@functools.partial(jax.jit, static_argnames=("interpret",))
def intersect_sorted(a: jnp.ndarray, b: jnp.ndarray,
                     interpret: bool = False) -> jnp.ndarray:
    """a: [n], b: [m] int32 sorted (PAD-padded to ``bucket`` lengths).
    Returns idx [n] int32: position of a[i] in b, or -1 if absent."""
    lt, eq = rank_sorted(a, b, interpret)
    return jnp.where((eq > 0) & (a != PAD), lt, -1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def multi_merge_ranks(arrs: jnp.ndarray, interpret: bool = False
                      ) -> jnp.ndarray:
    """arrs: [k, n] int32, each row sorted with unique keys and
    PAD-padded to a ``bucket`` length.  Returns the [k, n] global
    rank of every element in the stable k-way merge (ties resolve by
    row index; pad ranks are meaningless, callers slice to the real
    lengths)."""
    k, n = arrs.shape
    own = jnp.arange(n, dtype=jnp.int32)
    ranks = []
    for i in range(k):
        total = own
        for j in range(k):
            if j == i:
                continue
            lt, eq = rank_sorted(arrs[i], arrs[j], interpret)
            total = total + (lt + eq if j < i else lt)
        ranks.append(total)
    return jnp.stack(ranks)

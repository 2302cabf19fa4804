"""Pluggable kernel-backend registry for the vector engine's seams.

The columnar ``VectorBackend`` funnels every data-parallel primitive
through four *seams* -- ``intersect_keys`` / ``union_k_keys`` /
``lookup_keys`` / ``segmented_reduce`` (plus the 2-ary ``union_keys``
special case).  This module hosts the lowerings of those seams for each
kernel backend and the registry that selects between them:

  * ``numpy``            reference lowerings (vectorized ``searchsorted``
                         / ``bincount``) -- the parity oracle every other
                         backend must match bit-exactly.
  * ``jax-jit``          the same formulations as jitted XLA programs
                         (pow2-padded shapes to bound retraces, x64
                         enabled so packed int64 keys survive).
  * ``pallas-interpret`` the Pallas rank kernel behind
                         ``intersect_sorted`` / ``multi_merge_ranks``
                         (``kernels/intersect.py``) run in interpret mode
                         -- the CI leg that keeps the kernel body from
                         bit-rotting on CPU runners.
  * ``pallas-tpu``       the same kernel compiled to Mosaic; requires a
                         TPU backend and refuses to resolve without one.

Selection order: an explicit ``VectorBackend(kernel_backend=...)``
argument wins, else the ``REPRO_KERNEL_BACKEND`` environment variable,
else ``auto`` (pallas-tpu on TPU hosts, numpy otherwise).

Parity contract (DESIGN.md "kernel dispatch"): for any admissible
input, every backend returns arrays *bit-identical* to the numpy
lowering -- positions, union orders, and float accumulation order all
included.  Inputs outside a backend's admissible domain (e.g. keys
beyond int32 for the Pallas kernels, semirings without a vectorized
reduction for the jax scatter path) delegate to the numpy lowering per
call, so parity is preserved rather than approximated.  Each such
delegation counts on ``kernel.host_delegation/<seam>``, and each
program launched on the JAX device on ``kernel.device_call/<seam>``.
"""
from __future__ import annotations

import functools
import os
import threading
import time
from dataclasses import dataclass, replace
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

_I32_MAX = np.iinfo(np.int32).max
_I64_PAD = np.iinfo(np.int64).max


# ---------------------------------------------------------------------- #
# reference lowerings
# ---------------------------------------------------------------------- #
class NumpyKernels:
    """Vectorized ``searchsorted`` / ``bincount`` seam lowerings: the
    bit-exactness oracle for every other backend."""

    name = "numpy"

    # -------------------------------------------------------------- #
    def intersect_keys(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """Positions in ``b`` of every element of ``a`` (both sorted
        int64 key arrays; keys unique per array), -1 where absent."""
        a = np.asarray(a, dtype=np.int64)
        b = np.asarray(b, dtype=np.int64)
        if len(a) == 0 or len(b) == 0:
            return np.full(len(a), -1, dtype=np.int64)
        pos = np.searchsorted(b, a)
        safe = np.minimum(pos, len(b) - 1)
        hit = (pos < len(b)) & (b[safe] == a)
        return np.where(hit, safe, -1)

    # -------------------------------------------------------------- #
    def _positions(self, a: np.ndarray, u: np.ndarray) -> np.ndarray:
        pos = np.searchsorted(a, u)
        safe = np.minimum(pos, len(a) - 1)
        hit = (pos < len(a)) & (a[safe] == u)
        return np.where(hit, safe, -1).astype(np.int64)

    def _merged_union(self, arrays: List[np.ndarray]) -> np.ndarray:
        """Sorted union of the non-empty arrays (hook point: subclasses
        override just the merge and inherit the position gathers)."""
        if len(arrays) == 2:
            return np.union1d(arrays[0], arrays[1])
        return np.unique(np.concatenate(arrays))

    def union_keys(self, a: np.ndarray, b: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Sorted union of two sorted int64 key arrays (keys unique per
        array).  Returns (union, pos_a, pos_b): for every union element
        its position in ``a`` / ``b`` or -1."""
        a = np.asarray(a, dtype=np.int64)
        b = np.asarray(b, dtype=np.int64)
        if len(a) == 0:
            return (b.copy(), np.full(len(b), -1, dtype=np.int64),
                    np.arange(len(b), dtype=np.int64))
        if len(b) == 0:
            return (a.copy(), np.arange(len(a), dtype=np.int64),
                    np.full(len(a), -1, dtype=np.int64))
        u = self._merged_union([a, b])
        return u, self._positions(a, u), self._positions(b, u)

    def union_k_keys(self, arrays) -> Tuple[np.ndarray, list]:
        """Sorted union of k sorted int64 key arrays (keys unique per
        array).  Returns (union, [pos_i]): for every union element its
        position in array i, or -1 where absent."""
        arrays = [np.asarray(a, dtype=np.int64) for a in arrays]
        if len(arrays) == 1:
            a = arrays[0]
            return a.copy(), [np.arange(len(a), dtype=np.int64)]
        if len(arrays) == 2:
            u, pa, pb = self.union_keys(arrays[0], arrays[1])
            return u, [pa, pb]
        nonempty = [a for a in arrays if len(a)]
        if not nonempty:
            z = np.zeros(0, dtype=np.int64)
            return z, [z.copy() for _ in arrays]
        u = self._merged_union(nonempty)
        out = []
        for a in arrays:
            if len(a) == 0:
                out.append(np.full(len(u), -1, dtype=np.int64))
            else:
                out.append(self._positions(a, u))
        return u, out

    # -------------------------------------------------------------- #
    def lookup_keys(self, hay: np.ndarray, probes: np.ndarray
                    ) -> np.ndarray:
        """Positions in ``hay`` (sorted int64, unique) of every
        ``probes`` element (arbitrary order, duplicates fine), -1 where
        absent."""
        hay = np.asarray(hay, dtype=np.int64)
        probes = np.asarray(probes, dtype=np.int64)
        if len(probes) == 0 or len(hay) == 0:
            return np.full(len(probes), -1, dtype=np.int64)
        pos = np.searchsorted(hay, probes)
        safe = np.minimum(pos, len(hay) - 1)
        hit = (pos < len(hay)) & (hay[safe] == probes)
        return np.where(hit, safe, -1)

    # -------------------------------------------------------------- #
    def segmented_reduce(self, vals: np.ndarray, starts: np.ndarray,
                         semiring=None,
                         group_ids: Optional[np.ndarray] = None
                         ) -> np.ndarray:
        """Semiring-parameterized segmented reduction over a
        fused-key-sorted value stream: ``starts[g]`` is the first index
        of group ``g`` (ascending, ``starts[0] == 0``); returns one
        reduced value per group.

        Values fold strictly left-to-right within each group,
        bit-identical to the interpreter's sequential ``semiring.add``
        chain.  Three lowerings, fastest admissible wins:

        * float addition (``add_vec is np.add``, the arithmetic
          semiring) -- one ``np.bincount`` pass: its weighted
          accumulation is a plain C loop in input order, and seeding
          from 0.0 is exact for the nonzero payloads the nz-filtered
          stream carries.  (NOT ``np.add.reduceat``: reduceat
          pairwise-sums like ``reduce``, verified non-bit-identical to
          the sequential fold.)
        * a declared ``add_ufunc`` (min-plus: min is exact under any
          association) -- one ``ufunc.reduceat``.
        * otherwise -- a step-loop over ``add_vec`` bounded by the
          largest group.

        ``group_ids`` (optional, 0-based group index per element) lets
        a caller that already materialized the group boundaries skip
        their reconstruction on the bincount path."""
        vals = np.asarray(vals)
        starts = np.asarray(starts, dtype=np.int64)
        n = len(vals)
        if len(starts) == 0:
            return vals[:0].copy()
        if (semiring is None or semiring.add_vec is np.add) and \
                vals.dtype == np.float64:
            gids = group_ids
            if gids is None:
                gids = np.zeros(n, dtype=np.int64)
                gids[starts[1:]] = 1
                np.cumsum(gids, out=gids)
            return np.bincount(gids, weights=vals, minlength=len(starts))
        ufunc = None if semiring is None else semiring.add_ufunc
        if ufunc is not None:
            return ufunc.reduceat(vals, starts)
        add_vec = np.add if semiring is None else semiring.add_vec
        counts = np.diff(np.append(starts, n))
        sums = vals[starts].copy()
        step = 1
        max_c = int(counts.max())
        while step < max_c:
            act = np.flatnonzero(counts > step)
            sums[act] = add_vec(sums[act], vals[starts[act] + step])
            step += 1
        return sums


# ---------------------------------------------------------------------- #
# device launches: counted, waited for, compiled into a persistent cache
# ---------------------------------------------------------------------- #
#: where compiled programs persist when ``$JAX_COMPILATION_CACHE_DIR``
#: does not say: a fixed directory of the checkout (the path is part of
#: the cache key, so a directory that moves never hits)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The persistent compile cache's directory on a TPU host."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)


@functools.cache
def _init_device() -> None:
    """First use of a device backend: on a TPU, keep every compile
    (kernels compile in about a second, under JAX's default threshold)
    in the persistent cache.  JAX reads ``$JAX_COMPILATION_CACHE_DIR``
    itself; CPU runs configure nothing and write nothing.

    The cache keys a Pallas kernel by its Mosaic payload, which holds
    the kernel's source locations.  With full tracebacks those name
    every caller up the stack, so one program reached along another
    call path (a traced run's seam dispatch, another seam) missed the
    cache and compiled again; locations keep the kernel's own frame
    only."""
    import jax
    if jax.default_backend() != "tpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)


def _device(seam: str, fn, *args, **kwargs) -> np.ndarray:
    """Launch one seam program and wait for it before its result
    leaves the device.

    Under a tracer the launch, the wait and the read-back are one
    ``device:<seam>`` span whose args name the padded ``bucket`` (the
    shapes of the array arguments) and whether JAX built a program
    inside it (``compiled``: a backend-compile event, which fires for a
    compile and for a read of the persistent compile cache alike;
    ``cache_hit``: the persistent cache served it)."""
    import jax
    tr = _obs_tracer()
    if tr is None:
        out = jax.block_until_ready(fn(*args, **kwargs))
        _obs_metrics().counter("kernel.device_call/" + seam).inc()
        return np.asarray(out)
    builds = _compile_log()
    compiles, hits = builds.compiles, builds.cache_hits
    with tr.span("device:" + seam, cat="device") as sp:
        out = jax.block_until_ready(fn(*args, **kwargs))
        _obs_metrics().counter("kernel.device_call/" + seam).inc()
        out = np.asarray(out)
        sp.set("bucket", [list(a.shape) for a in args
                          if isinstance(a, np.ndarray)])
        sp.set("compiled", builds.compiles != compiles)
        sp.set("cache_hit", builds.cache_hits != hits)
    return out


class _CompileLog:
    """Programs JAX has built in this process, as ``jax.monitoring``
    reports them (read by the ``device:`` spans)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0

    def on_duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.compiles += 1

    def on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1


@functools.cache
def _compile_log() -> _CompileLog:
    """The process's compile log, listening from the first traced
    device launch on."""
    import jax
    log = _CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)
    return log


def _delegated(seam: str) -> None:
    """A device backend handing one seam call to the numpy lowering."""
    _obs_metrics().counter("kernel.host_delegation/" + seam).inc()


# ---------------------------------------------------------------------- #
# jax-jit: the same formulations as XLA programs
# ---------------------------------------------------------------------- #
def _pad_pow2(a: np.ndarray, fill) -> np.ndarray:
    """Pad to the next power-of-two length (min 1) so jit retraces stay
    O(log n) across the chunked frontier's varying stream sizes."""
    n = len(a)
    m = 1 << max(n, 1).bit_length() if n & (n - 1) or n == 0 else n
    if m == n:
        return a
    out = np.full(m, fill, a.dtype)
    out[:n] = a
    return out


@functools.cache
def _jx():
    """Jitted seam programs, built once.  All run under
    ``jax.enable_x64`` (packed offset keys reach 2^62)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def positions(hay, probes):
        # positions of probes in hay, -1 where absent; pads
        # (INT64_MAX) in hay sort past every real key, pad probes
        # resolve to hay pads and are sliced off by the caller
        n = hay.shape[0]
        pos = jnp.searchsorted(hay, probes)
        safe = jnp.minimum(pos, n - 1)
        hit = (pos < n) & (hay[safe] == probes)
        return jnp.where(hit, safe, -1)

    @jax.jit
    def merge_sort(cat):
        return jnp.sort(cat)

    @functools.partial(jax.jit, static_argnums=(2,))
    def seg_sum(vals, gids, out_len):
        return jnp.zeros(out_len, vals.dtype).at[gids].add(vals)

    @functools.partial(jax.jit, static_argnums=(2,))
    def seg_min(vals, gids, out_len):
        init = jnp.full(out_len, jnp.inf, vals.dtype)
        return init.at[gids].min(vals)

    @functools.partial(jax.jit, static_argnums=(2,))
    def seg_max(vals, gids, out_len):
        init = jnp.full(out_len, -jnp.inf, vals.dtype)
        return init.at[gids].max(vals)

    return positions, merge_sort, seg_sum, seg_min, seg_max


class JaxJitKernels(NumpyKernels):
    """XLA lowerings of the seams via ``jax.jit``: one fused program
    per seam, shapes padded to powers of two to bound retraces.

    Positions/unions are the identical binary-search formulation
    (bit-exact by construction); the float segmented reduction uses an
    XLA scatter-add, which applies duplicate updates in stream order on
    CPU -- the same sequential fold as the bincount oracle (parity is
    CI-asserted, not assumed).  A TPU emulates float64: a value copied
    to a v5e and back already differs in its last bits, so there every
    segmented reduction delegates to numpy (``f64_exact``)."""

    name = "jax-jit"

    def __init__(self):
        import jax
        #: the device holds float64 exactly (false on a TPU)
        self.f64_exact = jax.default_backend() != "tpu"

    def _jpositions(self, hay: np.ndarray, probes: np.ndarray,
                    seam: str) -> np.ndarray:
        import jax
        positions, _, _, _, _ = _jx()
        with jax.enable_x64(True):
            out = _device(seam, positions, _pad_pow2(hay, _I64_PAD),
                          _pad_pow2(probes, _I64_PAD))
        # hits against hay's pad tail are pad probes only (real keys
        # are < 2^63-1), already sliced off; misses are already -1
        return out[:len(probes)].astype(np.int64)

    def intersect_keys(self, a, b):
        a = np.asarray(a, dtype=np.int64)
        b = np.asarray(b, dtype=np.int64)
        if len(a) == 0 or len(b) == 0:
            return np.full(len(a), -1, dtype=np.int64)
        return self._jpositions(b, a, "intersect_keys")

    def _positions(self, a, u):
        return self._jpositions(a, u, "union_k_keys")

    def _merged_union(self, arrays):
        import jax
        _, merge_sort, _, _, _ = _jx()
        total = sum(len(a) for a in arrays)
        cat = _pad_pow2(np.concatenate(arrays), _I64_PAD)
        with jax.enable_x64(True):
            merged = _device("union_k_keys", merge_sort, cat)[:total]
        keep = np.ones(total, dtype=bool)
        keep[1:] = merged[1:] != merged[:-1]
        return merged[keep]

    def lookup_keys(self, hay, probes):
        hay = np.asarray(hay, dtype=np.int64)
        probes = np.asarray(probes, dtype=np.int64)
        if len(probes) == 0 or len(hay) == 0:
            return np.full(len(probes), -1, dtype=np.int64)
        if int(probes.max()) >= _I64_PAD:
            _delegated("lookup_keys")
            return super().lookup_keys(hay, probes)
        return self._jpositions(hay, probes, "lookup_keys")

    def segmented_reduce(self, vals, starts, semiring=None,
                         group_ids=None):
        vals = np.asarray(vals)
        starts = np.asarray(starts, dtype=np.int64)
        n = len(vals)
        if len(starts) == 0 or n == 0:
            return super().segmented_reduce(vals, starts, semiring,
                                            group_ids)
        ufunc = None if semiring is None else semiring.add_ufunc
        is_sum = (semiring is None or semiring.add_vec is np.add) and \
            vals.dtype == np.float64
        if not self.f64_exact or (
                not is_sum and ufunc not in (np.minimum, np.maximum)):
            _delegated("segmented_reduce")
            return super().segmented_reduce(vals, starts, semiring,
                                            group_ids)
        gids = group_ids
        if gids is None:
            gids = np.zeros(n, dtype=np.int64)
            gids[starts[1:]] = 1
            np.cumsum(gids, out=gids)
        n_groups = len(starts)
        # pad the scatter stream with writes to a dummy slot past the
        # real groups, so the output length is a pow2 static shape
        out_len = 1 << max(n_groups + 1, 2).bit_length()
        import jax
        _, _, seg_sum, seg_min, seg_max = _jx()
        fill = 0.0 if is_sum else (np.inf if ufunc is np.minimum
                                   else -np.inf)
        pv = _pad_pow2(np.ascontiguousarray(vals, dtype=np.float64), fill)
        pg = np.full(len(pv), out_len - 1, dtype=np.int64)
        pg[:n] = gids
        fn = seg_sum if is_sum else (seg_min if ufunc is np.minimum
                                     else seg_max)
        with jax.enable_x64(True):
            res = _device("segmented_reduce", fn, pv, pg,
                          int(out_len))[:n_groups]
        if vals.dtype != np.float64:
            res = res.astype(vals.dtype)
        return res


# ---------------------------------------------------------------------- #
# pallas: the device kernels (interpret mode on CPU, Mosaic on TPU)
# ---------------------------------------------------------------------- #
def _fits_i32(a: np.ndarray) -> bool:
    return len(a) == 0 or int(a[-1]) < _I32_MAX


class PallasKernels(NumpyKernels):
    """The Pallas rank kernel behind the seams (``kernels/intersect.py``):
    intersection positions for ``intersect_keys`` / ``lookup_keys`` and
    stable k-way merge ranks for the unions.  Keys go to the kernel as
    int32, padded with INT32_MAX to a power-of-two bucket.  Inputs whose
    key domain exceeds int32 delegate to the numpy lowering per call; so
    does every segmented reduction, which has no kernel.  Each delegation
    counts on ``kernel.host_delegation/<seam>``."""

    def __init__(self, interpret: bool):
        self.interpret = interpret
        self.name = "pallas-interpret" if interpret else "pallas-tpu"

    def _isect(self, a: np.ndarray, b: np.ndarray, seam: str
               ) -> np.ndarray:
        from repro.kernels import intersect as _isect
        from repro.kernels import ops as _ops
        idx = _device(seam, _isect.intersect_sorted,
                      _ops.pad_sorted(a.astype(np.int32)),
                      _ops.pad_sorted(b.astype(np.int32)),
                      interpret=self.interpret)
        return idx[:len(a)].astype(np.int64)

    def intersect_keys(self, a, b):
        a = np.asarray(a, dtype=np.int64)
        b = np.asarray(b, dtype=np.int64)
        if len(a) == 0 or len(b) == 0:
            return np.full(len(a), -1, dtype=np.int64)
        if not (_fits_i32(a) and _fits_i32(b)):
            _delegated("intersect_keys")
            return super().intersect_keys(a, b)
        return self._isect(a, b, "intersect_keys")

    def _merged_union(self, arrays):
        if not all(_fits_i32(a) for a in arrays):
            _delegated("union_k_keys")
            return super()._merged_union(arrays)
        from repro.kernels import intersect as _isect
        # every element finds its global rank in the stable k-way merge
        # in one launch; real keys are < INT32_MAX, so real ranks land
        # in [0, total) and pad ranks are never read
        n_pad = _isect.bucket(max(len(a) for a in arrays))
        stacked = np.full((len(arrays), n_pad), _I32_MAX, np.int32)
        for i, a in enumerate(arrays):
            stacked[i, :len(a)] = a
        ranks = _device("union_k_keys", _isect.multi_merge_ranks, stacked,
                        interpret=self.interpret)
        merged = np.empty(sum(len(a) for a in arrays), dtype=np.int64)
        for i, a in enumerate(arrays):
            merged[ranks[i, :len(a)]] = a
        keep = np.ones(len(merged), dtype=bool)
        keep[1:] = merged[1:] != merged[:-1]
        return merged[keep]

    def lookup_keys(self, hay, probes):
        hay = np.asarray(hay, dtype=np.int64)
        probes = np.asarray(probes, dtype=np.int64)
        if len(probes) == 0 or len(hay) == 0:
            return np.full(len(probes), -1, dtype=np.int64)
        if not (_fits_i32(hay) and int(probes.max()) < _I32_MAX
                and int(probes.min()) >= 0):
            _delegated("lookup_keys")
            return super().lookup_keys(hay, probes)
        # probes are sorted, pushed through the intersection kernel, and
        # unsorted
        order = np.argsort(probes, kind="stable")
        idx = np.empty(len(probes), dtype=np.int64)
        idx[order] = self._isect(probes[order], hay, "lookup_keys")
        return idx

    def segmented_reduce(self, vals, starts, semiring=None,
                         group_ids=None):
        _delegated("segmented_reduce")
        return super().segmented_reduce(vals, starts, semiring, group_ids)


# ---------------------------------------------------------------------- #
# registry
# ---------------------------------------------------------------------- #
_INSTANCES: dict = {}

KERNEL_BACKENDS = ("numpy", "jax-jit", "pallas-interpret", "pallas-tpu")

#: environment override consulted when no explicit backend is passed
ENV_VAR = "REPRO_KERNEL_BACKEND"


def _make(name: str):
    if name == "numpy":
        return NumpyKernels()
    if name not in KERNEL_BACKENDS:
        raise ValueError(
            f"unknown kernel backend {name!r}; choose from "
            f"{KERNEL_BACKENDS} or 'auto'")
    if name == "pallas-tpu":
        import jax
        if jax.default_backend() != "tpu":
            raise RuntimeError(
                "kernel backend 'pallas-tpu' requires a TPU jax backend "
                f"(found {jax.default_backend()!r}); use "
                "'pallas-interpret' for CPU validation")
    _init_device()
    if name == "jax-jit":
        return JaxJitKernels()
    return PallasKernels(interpret=name == "pallas-interpret")


#: why the last ``auto`` probe found no TPU (None when it found one or
#: has not run); surfaced instead of silently swallowed
AUTO_PROBE_ERROR: Optional[str] = None


def _probe_tpu() -> bool:
    """Is a TPU jax backend available?  A missing jax (ImportError) or
    unreadable device files (OSError) mean no, with the reason recorded
    on ``AUTO_PROBE_ERROR``.  A TPU runtime that fails to initialize
    raises its RuntimeError: resolving ``auto`` to numpy then would
    hide the chip."""
    global AUTO_PROBE_ERROR
    try:
        import jax
        on_tpu = jax.default_backend() == "tpu"
    except (ImportError, OSError) as exc:
        AUTO_PROBE_ERROR = f"{type(exc).__name__}: {exc}"
        return False
    AUTO_PROBE_ERROR = None
    return on_tpu


def resolve_kernel_backend(which=None):
    """Resolve a kernel backend: an instance passes through, a name hits
    the registry, ``None`` consults ``$REPRO_KERNEL_BACKEND`` then
    ``auto`` (pallas-tpu on TPU hosts, numpy elsewhere)."""
    if which is not None and not isinstance(which, str):
        return which
    name = which or os.environ.get(ENV_VAR) or "auto"
    if name == "auto":
        name = "pallas-tpu" if _probe_tpu() else "numpy"
    inst = _INSTANCES.get(name)
    if inst is None:
        inst = _INSTANCES[name] = _make(name)
    return inst


# ---------------------------------------------------------------------- #
# guarded dispatch: the per-seam degradation chain
# ---------------------------------------------------------------------- #
#: the rungs below every primary backend: each seam call starts at its
#: primary and walks right until one lowering succeeds.  The Pallas
#: interpreter is never a rung: below ``pallas-tpu`` it would run a
#: kernel Mosaic refused on the host CPU and hide that the chip was not
#: used.
FALLBACK_CHAIN = ("jax-jit", "numpy")


def degradation_chain(primary: str) -> Tuple[str, ...]:
    """The degradation chain a seam call walks from ``primary``."""
    return (primary,) + tuple(b for b in FALLBACK_CHAIN if b != primary)


#: the five seam methods the guard mediates
GUARDED_SEAMS = ("intersect_keys", "union_keys", "union_k_keys",
                 "lookup_keys", "segmented_reduce")

#: substrings of backend error text classified transient (worth a
#: bounded retry on the *same* backend before downgrading)
TRANSIENT_MARKERS = ("RESOURCE_EXHAUSTED", "DEADLINE_EXCEEDED",
                     "UNAVAILABLE", "ABORTED")


@dataclass(frozen=True)
class DowngradeEvent:
    """One structured record of the guard acting on a seam fault.

    ``action`` is one of:

    * ``retry``       a transient fault; the same seam x backend pair is
                      retried after backoff,
    * ``downgrade``   the seam call moved to ``fallback`` (the next
                      backend in the chain),
    * ``demote``      the seam x backend pair crossed the failure
                      threshold and is skipped for the rest of the
                      process,
    * ``unavailable`` the backend could not even be constructed (e.g.
                      pallas-tpu on a CPU host).

    Every caught seam fault produces at least one event -- the guard
    never swallows silently.

    ``ts_us`` is a monotonic microsecond timestamp and ``einsum`` the
    Einsum active on the owning executor, both stamped at record time
    (``GuardedKernels._record``) so exported traces order events
    deterministically even though the executor drains them per-Einsum
    batch."""
    seam: str
    backend: str
    fallback: str            # next backend tried ("" for retry/demote)
    action: str              # retry | downgrade | demote | unavailable
    reason: str
    exc_type: str
    attempts: int = 1
    ts_us: float = 0.0       # monotonic; stamped by _record
    einsum: str = ""         # active Einsum at record time

    def as_dict(self) -> Dict[str, object]:
        return {"seam": self.seam, "backend": self.backend,
                "fallback": self.fallback, "action": self.action,
                "reason": self.reason, "exc_type": self.exc_type,
                "attempts": self.attempts, "ts_us": self.ts_us,
                "einsum": self.einsum}


class KernelChainExhausted(RuntimeError):
    """Every backend in the degradation chain failed for a seam call.
    ``VectorBackend`` treats this like any other execution fault: the
    affected Einsum falls back to the interpreter oracle."""


class SeamPostconditionError(RuntimeError):
    """A seam lowering returned an output violating the seam's
    contract (wrong length, out-of-range positions, unsorted union,
    non-finite reduction under an arithmetic semiring)."""


# process-wide guard state: demotions are permanent for the process (a
# backend that failed N times is not coming back), and the event
# counter is what chaos runs compare against injected-fault counts
_GUARD_LOCK = threading.Lock()
_DEMOTED: Set[Tuple[str, str]] = set()
_FAIL_COUNTS: Dict[Tuple[str, str], int] = {}
_EVENTS_RECORDED = 0


def events_recorded() -> int:
    """Total DowngradeEvents recorded process-wide (chaos accounting:
    must cover every injected seam fault, else the run was silent)."""
    return _EVENTS_RECORDED


def reset_guard_state() -> None:
    """Test hook: forget demotions, failure tallies and the event
    counter."""
    global _EVENTS_RECORDED
    with _GUARD_LOCK:
        _DEMOTED.clear()
        _FAIL_COUNTS.clear()
        _EVENTS_RECORDED = 0


def _is_transient(exc: BaseException) -> bool:
    if type(exc).__name__ == "InjectedTransientFault":
        return True
    msg = str(exc)
    return any(tok in msg for tok in TRANSIENT_MARKERS)


# lazily-resolved cross-module hooks, cached after the first call:
# these run on every guarded seam call, so repeated import-machinery
# lookups would tax the hot path
_INJECTOR_FN = None
_GUARDS_ENABLED_FN = None


def _active_injector():
    global _INJECTOR_FN
    if _INJECTOR_FN is None:
        try:
            from repro.testing.faults import active_injector
        except ImportError:              # pragma: no cover - stripped
            _INJECTOR_FN = lambda: None  # noqa: E731
        else:
            _INJECTOR_FN = active_injector
    return _INJECTOR_FN()


def _guards_enabled() -> bool:
    # lazy: repro.core imports this module transitively at package
    # import time, so the reverse edge must resolve at call time only
    global _GUARDS_ENABLED_FN
    if _GUARDS_ENABLED_FN is None:
        from repro.core import guards
        _GUARDS_ENABLED_FN = guards.enabled
    return _GUARDS_ENABLED_FN()


_TRACER_FN = None
_METRICS_FN = None


def _obs_tracer():
    # same cached-hook pattern as the fault injector: one global read
    # plus a call per guarded seam call; returns None when telemetry
    # is disabled, and the caller takes the pre-telemetry path
    global _TRACER_FN
    if _TRACER_FN is None:
        from repro.obs.spans import active_tracer
        _TRACER_FN = active_tracer
    return _TRACER_FN()


def _obs_metrics():
    global _METRICS_FN
    if _METRICS_FN is None:
        from repro.obs.metrics import metrics
        _METRICS_FN = metrics
    return _METRICS_FN()


def _n_keys(x) -> int:
    """Elements of every array in ``x`` (an array, or a tuple or list
    of them, nested); anything else counts nothing."""
    if isinstance(x, np.ndarray):
        return x.size
    if isinstance(x, (tuple, list)):
        return sum(_n_keys(v) for v in x)
    return 0


def _postcheck(seam: str, args, kwargs, out) -> None:
    """Cheap seam-contract postconditions (O(n) vectorized compares).
    A violation is *actionable* here -- the caller downgrades to the
    next backend -- unlike the warn-or-raise guards in core.guards."""
    if seam == "intersect_keys":
        a, b = args[0], args[1]
        arr = np.asarray(out)
        if len(arr) != len(a):
            raise SeamPostconditionError(
                f"intersect_keys returned {len(arr)} positions for "
                f"{len(a)} keys")
        if len(arr) and (int(arr.max()) >= len(b) or int(arr.min()) < -1):
            raise SeamPostconditionError(
                "intersect_keys position out of range")
    elif seam == "lookup_keys":
        hay, probes = args[0], args[1]
        arr = np.asarray(out)
        if len(arr) != len(probes):
            raise SeamPostconditionError(
                f"lookup_keys returned {len(arr)} positions for "
                f"{len(probes)} probes")
        if len(arr) and (int(arr.max()) >= len(hay) or int(arr.min()) < -1):
            raise SeamPostconditionError("lookup_keys position out of range")
    elif seam == "union_keys":
        u, pa, pb = out
        u = np.asarray(u)
        if len(u) > 1 and bool((np.diff(u) <= 0).any()):
            raise SeamPostconditionError("union_keys output not "
                                         "strictly sorted")
        if len(pa) != len(u) or len(pb) != len(u):
            raise SeamPostconditionError("union_keys position length "
                                         "mismatch")
    elif seam == "union_k_keys":
        u, pos_list = out
        u = np.asarray(u)
        if len(u) > 1 and bool((np.diff(u) <= 0).any()):
            raise SeamPostconditionError("union_k_keys output not "
                                         "strictly sorted")
        if any(len(p) != len(u) for p in pos_list):
            raise SeamPostconditionError("union_k_keys position length "
                                         "mismatch")
    elif seam == "segmented_reduce":
        starts = args[1]
        arr = np.asarray(out)
        if len(arr) != len(starts):
            raise SeamPostconditionError(
                f"segmented_reduce returned {len(arr)} groups for "
                f"{len(starts)} starts")
        semiring = kwargs.get("semiring",
                              args[2] if len(args) > 2 else None)
        arithmetic = semiring is None or semiring.add_vec is np.add
        if arr.dtype.kind == "f" and len(arr):
            with np.errstate(invalid="ignore"):
                if arithmetic:
                    # inf is as illegal as NaN under plain addition
                    bad = not bool(np.isfinite(arr).all())
                else:
                    # tropical semirings use inf legitimately (the
                    # additive identity of min-plus) -- but NaN never is
                    bad = bool(np.isnan(arr).any())
            if bad:
                raise SeamPostconditionError(
                    "segmented_reduce produced "
                    + ("non-finite values under an arithmetic semiring"
                       if arithmetic else "NaN values"))


class GuardedKernels:
    """Degradation-chain wrapper around the kernel-backend registry.

    Exposes the same five seam methods as the raw backends; each call
    walks the chain from the primary backend rightwards until a
    lowering succeeds, with

    * transient faults retried on the same backend with capped
      exponential backoff (``max_retries`` / ``backoff_base`` /
      ``backoff_cap``; ``sleep`` is injectable for tests),
    * permanent faults downgrading to the next backend,
    * a seam x backend pair demoted for the rest of the process after
      ``demote_after`` permanent failures,
    * seam postconditions (when ``REPRO_GUARDS`` != off) converting a
      *corrupted* output into a downgrade as well,
    * every action recorded as a :class:`DowngradeEvent` -- drained by
      the executor via :meth:`pop_events` onto ``SimResult.report``.

    The terminal numpy lowering has no further fallback: if it fails
    too, :class:`KernelChainExhausted` propagates to the executor,
    whose per-Einsum isolation falls back to the interpreter oracle."""

    def __init__(self, primary: str = "numpy", *,
                 max_retries: int = 2, backoff_base: float = 0.05,
                 backoff_cap: float = 1.0, demote_after: int = 3,
                 sleep=time.sleep):
        if isinstance(primary, str):
            if primary not in KERNEL_BACKENDS:
                raise ValueError(
                    f"unknown kernel backend {primary!r}; choose from "
                    f"{KERNEL_BACKENDS}")
            self._chain: Tuple = degradation_chain(primary)
            self.name = primary
        else:
            # a raw backend instance: guard it with the numpy oracle as
            # the only fallback
            self._chain = (primary, "numpy")
            self.name = getattr(primary, "name", type(primary).__name__)
        self.max_retries = max_retries
        self.backoff_base = backoff_base
        self.backoff_cap = backoff_cap
        self.demote_after = demote_after
        self._sleep = sleep
        self._unavailable: Dict[str, str] = {}
        self._events: List[DowngradeEvent] = []
        self._lock = threading.Lock()
        #: the Einsum currently executing on the owning backend; set by
        #: ``VectorBackend`` around ``_run`` so DowngradeEvents and seam
        #: spans carry their Einsum attribution
        self.current_einsum = ""
        # hot-path precomputation: (entry, name) pairs so _call does
        # not re-derive names per seam call, and a per-wrapper instance
        # cache so resolved entries skip the registry dict walk
        self._chain_info: Tuple = tuple(
            (e, e if isinstance(e, str)
             else getattr(e, "name", type(e).__name__))
            for e in self._chain)
        self._inst_cache: Dict[str, object] = {}

    # -------------------------------------------------------------- #
    @property
    def chain_names(self) -> Tuple[str, ...]:
        return tuple(b if isinstance(b, str)
                     else getattr(b, "name", type(b).__name__)
                     for b in self._chain)

    def pop_events(self) -> List[DowngradeEvent]:
        """Drain the events recorded since the last drain."""
        with self._lock:
            out, self._events = self._events, []
        return out

    def _record(self, ev: DowngradeEvent) -> None:
        global _EVENTS_RECORDED
        if ev.ts_us == 0.0:
            ev = replace(ev, ts_us=time.perf_counter() * 1e6,
                         einsum=ev.einsum or self.current_einsum)
        with self._lock:
            self._events.append(ev)
        with _GUARD_LOCK:
            _EVENTS_RECORDED += 1
        # rare-event telemetry: counters always, trace instant only
        # when a tracer is installed
        _obs_metrics().counter("kernel.downgrade/" + ev.action).inc()
        tr = _obs_tracer()
        if tr is not None:
            tr.instant("downgrade:" + ev.action, cat="downgrade",
                       args=ev.as_dict())

    # -------------------------------------------------------------- #
    def _instantiate(self, entry, seam: str):
        """The backend instance for a chain entry, or None (recorded as
        unavailable) when it cannot be constructed."""
        if not isinstance(entry, str):
            return entry
        key = entry
        if key in self._unavailable:
            return None
        inst = _INSTANCES.get(key)
        if inst is None:
            try:
                inst = _INSTANCES[key] = _make(key)
            except (ImportError, RuntimeError, OSError, ValueError) as exc:
                reason = f"{type(exc).__name__}: {exc}"
                self._unavailable[key] = reason
                self._record(DowngradeEvent(
                    seam=seam, backend=key,
                    fallback=self._next_name(key),
                    action="unavailable", reason=str(exc),
                    exc_type=type(exc).__name__))
                return None
        return inst

    def _next_name(self, after) -> str:
        names = self.chain_names
        key = after if isinstance(after, str) else getattr(
            after, "name", type(after).__name__)
        try:
            i = names.index(key)
        except ValueError:
            return ""
        return names[i + 1] if i + 1 < len(names) else ""

    # -------------------------------------------------------------- #
    def _call(self, seam: str, *args, **kwargs):
        tr = _obs_tracer()
        if tr is None:
            # disabled path: identical to the pre-telemetry dispatch,
            # no span or counter touched
            return self._dispatch(seam, args, kwargs, None)
        with tr.span("seam:" + seam, cat="seam",
                     args={"einsum": self.current_einsum}
                     if self.current_einsum else None) as sp:
            out = self._dispatch(seam, args, kwargs, sp)
            # the seam's work in keys, the same whatever serves it:
            # every element it consumed plus every element it returned
            keys = (_n_keys(args) + _n_keys(tuple(kwargs.values()))
                    + _n_keys(out))
            sp.set("keys", keys)
            _obs_metrics().counter("kernel.seam_keys/" + seam).inc(keys)
            return out

    def _dispatch(self, seam: str, args, kwargs, span):
        inj = _active_injector()
        check = _guards_enabled()
        last_exc: Optional[BaseException] = None
        for entry, bname in self._chain_info:
            # lock-free read: set membership is atomic under the GIL
            # and demotions only ever grow the set (writes take the
            # lock in _note_failure)
            if (seam, bname) in _DEMOTED:
                continue
            backend = self._inst_cache.get(bname)
            if backend is None:
                backend = self._instantiate(entry, seam)
                if backend is None:
                    continue
                self._inst_cache[bname] = backend
            attempts = 0
            while True:
                attempts += 1
                try:
                    if inj is not None:
                        inj.before_seam(seam, bname)
                    out = getattr(backend, seam)(*args, **kwargs)
                    if span is not None:
                        span.set("backend", bname)
                        if attempts > 1:
                            span.set("attempts", attempts)
                    if inj is not None:
                        out = inj.after_seam(seam, bname, out)
                    if check:
                        _postcheck(seam, args, kwargs, out)
                    return out
                except Exception as exc:
                    last_exc = exc
                    if _is_transient(exc) and attempts <= self.max_retries:
                        self._record(DowngradeEvent(
                            seam=seam, backend=bname, fallback="",
                            action="retry", reason=str(exc),
                            exc_type=type(exc).__name__,
                            attempts=attempts))
                        self._sleep(min(
                            self.backoff_base * (2 ** (attempts - 1)),
                            self.backoff_cap))
                        continue
                    self._note_failure(seam, bname, exc, attempts)
                    break
        raise KernelChainExhausted(
            f"all kernel backends failed for seam {seam!r} "
            f"(chain {self.chain_names}); last error: "
            f"{type(last_exc).__name__ if last_exc else '?'}: "
            f"{last_exc}") from last_exc

    def _note_failure(self, seam: str, bname: str,
                      exc: BaseException, attempts: int) -> None:
        fallback = self._next_name(bname)
        self._record(DowngradeEvent(
            seam=seam, backend=bname, fallback=fallback,
            action="downgrade", reason=str(exc),
            exc_type=type(exc).__name__, attempts=attempts))
        with _GUARD_LOCK:
            key = (seam, bname)
            _FAIL_COUNTS[key] = _FAIL_COUNTS.get(key, 0) + 1
            demote = (_FAIL_COUNTS[key] >= self.demote_after
                      and key not in _DEMOTED)
            if demote:
                _DEMOTED.add(key)
        if demote:
            self._record(DowngradeEvent(
                seam=seam, backend=bname, fallback=fallback,
                action="demote",
                reason=f"{_FAIL_COUNTS[key]} failures "
                       f"(threshold {self.demote_after})",
                exc_type=type(exc).__name__, attempts=attempts))

    # -------------------------------------------------------------- #
    # the seam surface (mirrors NumpyKernels)
    # -------------------------------------------------------------- #
    def intersect_keys(self, a, b):
        return self._call("intersect_keys", a, b)

    def union_keys(self, a, b):
        return self._call("union_keys", a, b)

    def union_k_keys(self, arrays):
        return self._call("union_k_keys", arrays)

    def lookup_keys(self, hay, probes):
        return self._call("lookup_keys", hay, probes)

    def segmented_reduce(self, vals, starts, semiring=None,
                         group_ids=None):
        return self._call("segmented_reduce", vals, starts,
                          semiring=semiring, group_ids=group_ids)


def resolve_guarded_kernels(which=None, **opts) -> GuardedKernels:
    """Like :func:`resolve_kernel_backend` but returns the backend
    wrapped in the degradation chain.  Unlike the raw resolver this
    never raises for an unavailable primary (``pallas-tpu`` on a CPU
    host degrades at the first seam call instead): resolution is by
    *name*, instantiation is lazy and guarded."""
    if isinstance(which, GuardedKernels):
        return which
    if which is not None and not isinstance(which, str):
        return GuardedKernels(which, **opts)
    name = which or os.environ.get(ENV_VAR) or "auto"
    if name == "auto":
        name = "pallas-tpu" if _probe_tpu() else "numpy"
    return GuardedKernels(name, **opts)

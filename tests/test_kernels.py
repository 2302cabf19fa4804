"""Per-kernel interpret-mode allclose sweeps against the ref.py
oracles (shapes x dtypes, as the brief requires)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_chunk import ssd_chunk


# ---------------------------------------------------------------------- #
# flash attention
# ---------------------------------------------------------------------- #
ATTN_SHAPES = [
    # (b, h, hkv, sq, sk, d)
    (1, 2, 2, 128, 128, 64),       # MHA square
    (2, 4, 2, 256, 256, 64),       # GQA 2:1
    (1, 8, 1, 128, 256, 32),       # MQA, sk > sq
    (2, 2, 2, 64, 192, 128),       # blocks > sq (clamped)
]


@pytest.mark.parametrize("b,h,hkv,sq,sk,d", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, h, hkv, sq, sk, d, dtype, causal):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, h, sq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    atol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_flash_attention_fully_masked_rows():
    """Non-causal with sk < block: ragged tail must not produce NaNs."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 1, 64, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 40, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 40, 32)), jnp.float32)
    got = flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                          interpret=True)
    assert bool(jnp.all(jnp.isfinite(got)))


# ---------------------------------------------------------------------- #
# block-sparse matmul (SIGMA -> TPU adaptation)
# ---------------------------------------------------------------------- #
BSMM_SHAPES = [
    # (M, K, N, bm, bk, bn, tile_density)
    (128, 128, 128, 64, 64, 64, 0.5),
    (256, 128, 192, 64, 64, 64, 0.3),
    (256, 256, 64, 128, 128, 64, 0.2),
    (128, 256, 128, 64, 128, 128, 0.0),     # fully-empty A
]


@pytest.mark.parametrize("M,K,N,bm,bk,bn,density", BSMM_SHAPES)
@pytest.mark.parametrize("dtype", [np.float32])
def test_block_sparse_matmul_sweep(M, K, N, bm, bk, bn, density, dtype):
    rng = np.random.default_rng(2)
    a = rng.standard_normal((M, K)).astype(dtype)
    mask = rng.random((M // bm, K // bk)) < density
    a = a * np.kron(mask, np.ones((bm, bk), dtype))
    b = jnp.asarray(rng.standard_normal((K, N)), dtype)
    got = ops.block_sparse_matmul_dense_a(a, b, bm, bk, bn)
    want = ref.block_sparse_matmul_ref(jnp.asarray(a), b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4)


def test_compact_tiles_covers_all_rows():
    a = np.zeros((256, 128))
    a[130, 5] = 1.0                          # only tile-row 2 nonzero
    tiles, rows, cols = ops.compact_tiles(a, 64, 64)
    assert set(rows.tolist()) == {0, 1, 2, 3}  # every row covered
    # exactly one real tile + three zero pads
    assert sum(np.any(t != 0) for t in tiles) == 1


# ---------------------------------------------------------------------- #
# SSD intra-chunk kernel (Mamba2)
# ---------------------------------------------------------------------- #
SSD_SHAPES = [
    # (B, nc, l, H, P, N)
    (1, 2, 64, 2, 32, 16),
    (2, 3, 128, 4, 64, 32),
    (1, 1, 256, 8, 64, 128),     # the production chunk config
]


@pytest.mark.parametrize("B,nc,l,H,P,N", SSD_SHAPES)
def test_ssd_chunk_sweep(B, nc, l, H, P, N):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((B, nc, l, H, P)), jnp.float32)
    a = -jnp.abs(jnp.asarray(rng.standard_normal((B, H, nc, l)),
                             jnp.float32)) * 0.1
    b = jnp.asarray(rng.standard_normal((B, nc, l, N)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((B, nc, l, N)), jnp.float32)
    got = ssd_chunk(x, a, b, c, interpret=True)
    want = ref.ssd_chunk_ref(x, a, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ssd_kernel_inside_model_path():
    """models.ssm.ssd(use_kernel=True) equals the pure-jnp cascade."""
    from repro.models.ssm import ssd
    rng = np.random.default_rng(4)
    B, S, H, P, N, chunk = 2, 128, 2, 32, 16, 64
    x = jnp.asarray(rng.standard_normal((B, S, H, P)), jnp.float32)
    a = -jnp.abs(jnp.asarray(rng.standard_normal((B, S, H)),
                             jnp.float32)) * 0.1
    b = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
    y0, f0 = ssd(x, a, b, c, chunk, use_kernel=False)
    y1, f1 = ssd(x, a, b, c, chunk, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(f0), np.asarray(f1),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------- #
# sorted-coordinate intersection (ExTensor skip-ahead -> TPU)
# ---------------------------------------------------------------------- #
ISECT_CASES = [
    # (n_a, n_b, overlap_frac, trailing pads carried in A)
    (100, 400, 0.5, 64),
    (1000, 1000, 0.1, 256),
    (64, 2048, 0.9, 64),
    (5, 7, 1.0, 32),
    (0, 100, 0.0, 32),          # empty A (all padding)
]


@pytest.mark.parametrize("na,nb,frac,pads", ISECT_CASES)
def test_intersect_sorted_sweep(na, nb, frac, pads):
    rng = np.random.default_rng(7)
    universe = rng.choice(10 * (na + nb) + 10, size=na + nb,
                          replace=False)
    b = np.sort(universe[:nb]).astype(np.int32)
    n_common = int(na * frac)
    a_vals = list(rng.choice(b, size=min(n_common, nb), replace=False)
                  ) if nb and n_common else []
    a_vals += list(universe[nb:nb + (na - len(a_vals))])
    a = np.sort(np.asarray(a_vals, np.int32)) if a_vals else \
        np.zeros((0,), np.int32)

    ap = ops.pad_sorted(np.concatenate(
        [a, np.full(pads, np.iinfo(np.int32).max, np.int32)]))
    bp = ops.pad_sorted(b)
    got = np.asarray(ops.intersect_sorted(jnp.asarray(ap),
                                          jnp.asarray(bp)))
    want = np.asarray(ref.intersect_sorted_ref(ap, bp))
    np.testing.assert_array_equal(got, want)
    # semantic check: every hit points at the right coordinate
    for i in range(len(a)):
        if got[i] >= 0:
            assert bp[got[i]] == ap[i]
        else:
            assert ap[i] not in b


def test_rank_sorted_windows_and_grid_chunks(monkeypatch):
    """Windows spanning several B tiles, an all-pad A block, and a grid
    split into several launches (``MAX_GRID``) still count exactly."""
    from repro.kernels import intersect as isect
    monkeypatch.setattr(isect, "MAX_GRID", 2)
    rng = np.random.default_rng(5)
    a = np.sort(rng.choice(1 << 20, size=3000, replace=False))
    b = np.sort(rng.choice(1 << 20, size=8000, replace=False))
    ap, bp = ops.pad_sorted(a.astype(np.int32)), \
        ops.pad_sorted(b.astype(np.int32))
    lt, eq = isect.rank_sorted(jnp.asarray(ap), jnp.asarray(bp),
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(lt)[:len(a)],
                                  np.searchsorted(b, a, side="left"))
    np.testing.assert_array_equal(
        np.asarray(eq)[:len(a)],
        np.searchsorted(b, a, side="right") - np.searchsorted(b, a))


def test_intersect_matches_fibertree_intersection():
    """The kernel computes the same coordinate set as the fibertree
    two-finger intersection (the simulator's semantic authority)."""
    from repro.core.fibertree import Fiber
    rng = np.random.default_rng(11)
    a_c = np.unique(rng.integers(0, 500, size=80)).astype(np.int32)
    b_c = np.unique(rng.integers(0, 500, size=120)).astype(np.int32)
    fa = Fiber(list(map(int, a_c)), [1.0] * len(a_c))
    fb = Fiber(list(map(int, b_c)), [1.0] * len(b_c))
    want = {c for c, _, _ in fa.intersect(fb)}

    ap = ops.pad_sorted(a_c)
    bp = ops.pad_sorted(b_c)
    idx = np.asarray(ops.intersect_sorted(jnp.asarray(ap),
                                          jnp.asarray(bp)))
    got = {int(ap[i]) for i in range(len(a_c)) if idx[i] >= 0}
    assert got == want


# ---------------------------------------------------------------------- #
# k-ary multi-merge (UnionK) and the Lookup gather path
# ---------------------------------------------------------------------- #
def _rand_sorted(rng, n, hi):
    return np.sort(rng.choice(hi, size=n, replace=False)).astype(np.int64)


@pytest.mark.parametrize("k,sizes", [
    (3, (40, 60, 25)),
    (4, (100, 1, 50, 80)),
    (3, (0, 30, 30)),            # one empty operand
    (5, (8, 8, 8, 8, 8)),
])
def test_union_k_keys_matches_reference(k, sizes):
    rng = np.random.default_rng(13)
    arrays = [_rand_sorted(rng, n, 1000) for n in sizes]
    u, pos = ops.union_k_keys(arrays)
    want = np.unique(np.concatenate([a for a in arrays if len(a)]))
    np.testing.assert_array_equal(u, want)
    assert len(pos) == k
    for a, p in zip(arrays, pos):
        hit = p >= 0
        # every union element present in a points at its position
        np.testing.assert_array_equal(u[hit], a[p[hit]])
        np.testing.assert_array_equal(np.sort(p[hit]),
                                      np.arange(len(a)))
        assert not np.isin(u[~hit], a).any()


@pytest.mark.parametrize("k,n,scale", [(3, 64, 32), (4, 100, 64),
                                       (2, 256, 128), (6, 33, 16)])
def test_multi_merge_ranks_interpret(k, n, scale):
    """The Pallas k-way merge-rank kernel (interpret mode) agrees with
    the stable numpy merge; ``scale`` spreads the keys over a wider
    range."""
    rng = np.random.default_rng(17)
    rows = [np.sort(rng.choice(5000, size=rng.integers(1, n),
                               replace=False) * scale).astype(np.int32)
            for _ in range(k)]
    n_pad = max(len(ops.pad_sorted(r)) for r in rows)
    stacked = np.stack([
        np.concatenate([r, np.full(n_pad - len(r),
                                   np.iinfo(np.int32).max, np.int32)])
        for r in rows])
    ranks = np.asarray(ops.multi_merge_ranks(jnp.asarray(stacked)))
    total = sum(len(r) for r in rows)
    merged = np.empty(total, dtype=np.int64)
    for i, r in enumerate(rows):
        got = ranks[i, :len(r)]
        assert got.min() >= 0 and got.max() < total
        merged[got] = r
    # stable k-way merge == plain sort of the concatenation (ties are
    # value-equal, so stability only affects which copy lands where)
    np.testing.assert_array_equal(merged,
                                  np.sort(np.concatenate(rows)))


def test_lookup_keys_probe_path():
    rng = np.random.default_rng(19)
    hay = _rand_sorted(rng, 200, 10_000)
    probes = np.concatenate([rng.choice(hay, size=50),
                             rng.integers(0, 10_000, size=50)])
    rng.shuffle(probes)
    idx = ops.lookup_keys(hay, probes)
    for p, i in zip(probes, idx):
        if i >= 0:
            assert hay[i] == p
        else:
            assert p not in hay
    assert len(ops.lookup_keys(hay, np.zeros(0, dtype=np.int64))) == 0
    assert (ops.lookup_keys(np.zeros(0, dtype=np.int64), probes)
            == -1).all()


# ---------------------------------------------------------------------- #
# kernel-backend registry: parity of the four dispatch seams
# ---------------------------------------------------------------------- #
from repro.kernels import backends as kbk
from repro.core.einsum import Semiring

CPU_BACKENDS = ["numpy", "jax-jit", "pallas-interpret"]

#: adversarial key domains: dense duplicates-across-arrays, empty
#: arrays, sparse, and keys hugging the int32 / packed-int64 boundaries
_KEY_DOMAINS = [
    ("dense", 0, 500),
    ("empty", 0, 1),
    ("sparse", 0, 1 << 20),
    ("i32_boundary", np.iinfo(np.int32).max - 400,
     np.iinfo(np.int32).max),
    ("i64_packed", (1 << 62) - 2000, (1 << 62) - 1),
]


def _keys(rng, lo, hi, n):
    n = min(n, hi - lo)
    if n <= 0:
        return np.zeros(0, dtype=np.int64)
    return np.sort(rng.choice(np.arange(lo, hi, dtype=np.int64),
                              size=n, replace=False))


@pytest.mark.parametrize("name", CPU_BACKENDS)
@pytest.mark.parametrize("dom", _KEY_DOMAINS, ids=lambda d: d[0])
def test_registry_seam_parity(name, dom):
    """Every CPU kernel backend is bit-identical to the numpy oracle on
    all four dispatch seams, including empty and boundary domains."""
    _, lo, hi = dom
    rng = np.random.default_rng(11)
    ref_kb = kbk.resolve_kernel_backend("numpy")
    kb = kbk.resolve_kernel_backend(name)
    for trial in range(5):
        a = _keys(rng, lo, hi, int(rng.integers(0, 300)))
        b = _keys(rng, lo, hi, int(rng.integers(0, 300)))
        c = _keys(rng, lo, hi, int(rng.integers(0, 300)))
        np.testing.assert_array_equal(kb.intersect_keys(a, b),
                                      ref_kb.intersect_keys(a, b))
        u, pos = kb.union_k_keys([a, b, c])
        ur, posr = ref_kb.union_k_keys([a, b, c])
        np.testing.assert_array_equal(u, ur)
        for p, pr in zip(pos, posr):
            np.testing.assert_array_equal(p, pr)
        # duplicate-heavy probes (arbitrary order)
        probes = rng.choice(np.concatenate([a, [lo, hi - 1]]),
                            size=200) if len(a) else \
            np.zeros(0, dtype=np.int64)
        np.testing.assert_array_equal(kb.lookup_keys(a, probes),
                                      ref_kb.lookup_keys(a, probes))


@pytest.mark.parametrize("name", CPU_BACKENDS)
@pytest.mark.parametrize("sr", ["arithmetic", "min_plus", "or_and"],
                         ids=str)
def test_registry_segmented_reduce_parity(name, sr):
    rng = np.random.default_rng(13)
    kb = kbk.resolve_kernel_backend(name)
    ref_kb = kbk.resolve_kernel_backend("numpy")
    semiring = getattr(Semiring, sr)()
    for n in (0, 1, 7, 1000):
        vals = (rng.random(n) * 2 - 1 if sr != "or_and"
                else (rng.random(n) < 0.5).astype(np.float64))
        gids = np.sort(rng.integers(0, max(n // 3, 1), size=n))
        gids = np.cumsum(np.diff(gids, prepend=-1) > 0) - 1
        starts = np.flatnonzero(np.diff(gids, prepend=-1) > 0)
        got = kb.segmented_reduce(vals, starts, semiring, group_ids=gids)
        want = ref_kb.segmented_reduce(vals, starts, semiring,
                                       group_ids=gids)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("shift", [-3, 0, 5, 10_000])
def test_shifted_seams_vs_numpy(shift):
    """lookup_keys_shifted / intersect_keys_shifted agree with a plain
    numpy model on duplicate-heavy, empty, and i32-boundary inputs,
    whatever kernel backend is active."""
    rng = np.random.default_rng(23)
    cases = [
        (np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64)),
        (_keys(rng, 0, 100, 60), _keys(rng, 0, 100, 60)),
        (_keys(rng, np.iinfo(np.int32).max - 300,
               np.iinfo(np.int32).max, 100),
         _keys(rng, np.iinfo(np.int32).max - 300,
               np.iinfo(np.int32).max, 100)),
    ]
    for hay, srt in cases:
        probes = (rng.choice(hay, size=150) if len(hay)
                  else np.zeros(0, dtype=np.int64))
        got = ops.lookup_keys_shifted(hay, probes, shift=shift)
        want = np.full(len(probes), -1, dtype=np.int64)
        for i, p in enumerate(probes):
            j = np.searchsorted(hay, p + shift)
            if (p + shift >= 0 and j < len(hay)
                    and hay[j] == p + shift):
                want[i] = j
        np.testing.assert_array_equal(got, want)

        got = ops.intersect_keys_shifted(srt, hay, shift=shift)
        want = np.full(len(srt), -1, dtype=np.int64)
        for i, p in enumerate(srt):
            j = np.searchsorted(hay, p + shift)
            if (p + shift >= 0 and j < len(hay)
                    and hay[j] == p + shift):
                want[i] = j
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("k,n_max", [(3, 40), (4, 200), (6, 90)])
def test_multi_merge_ranks_adversarial(k, n_max):
    """The k-way merge-rank kernel (interpret) against the numpy stable
    merge on duplicate-heavy rows (same keys in many rows), ragged
    lengths, and keys at the int32 boundary."""
    rng = np.random.default_rng(29)
    base = np.sort(rng.choice(120, size=30, replace=False))
    hi = np.iinfo(np.int32).max
    rows = []
    for i in range(k):
        if i % 3 == 0:        # duplicate-heavy: overlaps `base` a lot
            r = np.sort(rng.choice(base, size=min(len(base), n_max),
                                   replace=False))
        elif i % 3 == 1:      # i32-boundary keys
            r = np.sort(rng.choice(np.arange(hi - 500, hi - 1),
                                   size=rng.integers(1, n_max),
                                   replace=False))
        else:
            r = np.sort(rng.choice(5000, size=rng.integers(1, n_max),
                                   replace=False))
        rows.append(r.astype(np.int32))
    n_pad = len(ops.pad_sorted(max(rows, key=len)))
    stacked = np.stack([
        np.concatenate([r, np.full(n_pad - len(r), hi, np.int32)])
        for r in rows])
    ranks = np.asarray(ops.multi_merge_ranks(jnp.asarray(stacked)))
    total = sum(len(r) for r in rows)
    merged = np.empty(total, dtype=np.int64)
    for i, r in enumerate(rows):
        got = ranks[i, :len(r)]
        assert got.min() >= 0 and got.max() < total
        merged[got] = r
    np.testing.assert_array_equal(
        merged, np.sort(np.concatenate(rows)))


def test_env_var_selects_backend(monkeypatch):
    monkeypatch.setenv(kbk.ENV_VAR, "jax-jit")
    assert kbk.resolve_kernel_backend().name == "jax-jit"
    monkeypatch.setenv(kbk.ENV_VAR, "pallas-interpret")
    assert kbk.resolve_kernel_backend().name == "pallas-interpret"
    monkeypatch.delenv(kbk.ENV_VAR)
    assert kbk.resolve_kernel_backend("numpy").name == "numpy"
    with pytest.raises(Exception):
        kbk.resolve_kernel_backend("no-such-backend")


# ---------------------------------------------------------------------- #
# no fallback that hides the device
# ---------------------------------------------------------------------- #
def test_pallas_tpu_chain_has_no_interpreter_rung():
    assert kbk.GuardedKernels("pallas-tpu").chain_names == \
        ("pallas-tpu", "jax-jit", "numpy")
    assert "pallas-interpret" not in kbk.degradation_chain("pallas-tpu")


def test_probe_tpu_propagates_runtime_init_failure(monkeypatch):
    """A TPU runtime that fails to initialize is an error, not a quiet
    resolution of ``auto`` to numpy."""
    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "default_backend", broken)
    monkeypatch.delenv(kbk.ENV_VAR, raising=False)
    with pytest.raises(RuntimeError, match="initialize"):
        kbk._probe_tpu()
    with pytest.raises(RuntimeError, match="initialize"):
        kbk.resolve_guarded_kernels()


@pytest.mark.parametrize("name", ["pallas-interpret", "jax-jit"])
def test_host_delegation_counted_per_seam(name):
    """Keys beyond the device lowering's domain go to numpy, counted on
    ``kernel.host_delegation/<seam>``; in-domain calls count as device
    calls instead."""
    from repro.obs.metrics import metrics
    kb = kbk.resolve_kernel_backend(name)
    reg = metrics()

    def count(kind, seam):
        return reg.counter(f"kernel.{kind}/{seam}").value

    rng = np.random.default_rng(3)
    packed = _keys(rng, (1 << 62) - 2000, (1 << 62) - 1, 200)
    small = _keys(rng, 0, 500, 200)
    if name == "pallas-interpret":
        d0, h0 = count("device_call", "intersect_keys"), \
            count("host_delegation", "intersect_keys")
        kb.intersect_keys(packed, packed[::2])
        assert count("host_delegation", "intersect_keys") == h0 + 1
        kb.intersect_keys(small, small[::2])
        assert count("device_call", "intersect_keys") == d0 + 1
        assert count("host_delegation", "intersect_keys") == h0 + 1
        h0 = count("host_delegation", "union_k_keys")
        kb.union_k_keys([packed, packed[::3], packed[1::3]])
        assert count("host_delegation", "union_k_keys") == h0 + 1
    else:
        # probes at the int64 pad sentinel are beyond the jitted search
        h0 = count("host_delegation", "lookup_keys")
        kb.lookup_keys(packed, np.array([np.iinfo(np.int64).max]))
        assert count("host_delegation", "lookup_keys") == h0 + 1
    h0 = count("host_delegation", "segmented_reduce")
    vals = np.ones(6)
    kb.segmented_reduce(vals, np.array([0, 2, 5]), Semiring.or_and())
    assert count("host_delegation", "segmented_reduce") == h0 + 1


def test_jax_jit_reductions_stay_on_host_on_a_tpu(monkeypatch):
    """TPU float64 is emulated (inexact on a v5e), so jax-jit's f64
    segmented reductions delegate to numpy there, counted."""
    from repro.obs.metrics import metrics
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kb = kbk.JaxJitKernels()
    assert not kb.f64_exact
    rng = np.random.default_rng(4)
    vals = rng.random(50)
    starts = np.array([0, 10, 31])
    c = metrics().counter("kernel.host_delegation/segmented_reduce")
    d = metrics().counter("kernel.device_call/segmented_reduce")
    h0, d0 = c.value, d.value
    for sr in (Semiring.arithmetic(), Semiring.min_plus()):
        np.testing.assert_array_equal(
            kb.segmented_reduce(vals, starts, sr),
            kbk.NumpyKernels().segmented_reduce(vals, starts, sr))
    assert (c.value, d.value) == (h0 + 2, d0)


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """``$JAX_COMPILATION_CACHE_DIR`` wins; otherwise the cache is the
    fixed in-checkout directory.  Only a TPU host configures either, and
    there it also keeps kernel locations to the kernel's own frame."""
    from pathlib import Path
    repo = Path(__file__).resolve().parent.parent
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert kbk.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert kbk.compile_cache_dir() == str(repo / ".jax_cache")

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_include_full_tracebacks_in_locations")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        kbk._init_device.__wrapped__()          # CPU: sets nothing
        assert {k: getattr(jax.config, k) for k in keys} == saved
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        kbk._init_device.__wrapped__()
        assert jax.config.jax_compilation_cache_dir == \
            str(repo / ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        # kernel locations keep their own frame: the cache key does not
        # depend on the call path (tests/test_tpu_compile.py)
        assert jax.config.jax_include_full_tracebacks_in_locations is False
        jax.config.update(keys[0], saved[keys[0]])
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        kbk._init_device.__wrapped__()          # JAX reads the variable
        assert jax.config.jax_compilation_cache_dir == saved[keys[0]]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)

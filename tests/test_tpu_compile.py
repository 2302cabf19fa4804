"""Ahead-of-time compiles of the device seam programs for a TPU v5e,
described but not attached: the Pallas rank kernel (through its two
entry points) at the largest padded shapes the chip smoke run meets,
and the jax-jit XLA seam programs under x64 at about 1M keys.  What
Mosaic or XLA would refuse on the chip fails here, at no chip time.
Also: the kernel's payload, part of the persistent compile cache's
key, does not depend on the call path that reaches it."""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import backends as kbk
from repro.kernels import intersect as isect


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described chip's executables cannot be read back: keep them
    out of any persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (n_a, n_b): Gamma's largest take() intersection at wiki-Vote scale
# (58,609 keys into 120.8M), BFS's largest at 131,044 vertices, and a
# 16M-key A whose grid splits over several launches (MAX_GRID)
@pytest.mark.parametrize("na,nb", [(1 << 16, 1 << 27), (1 << 17, 1 << 17),
                                   (1 << 24, 1 << 20)])
def test_intersect_sorted_compiles_for_v5e(one_chip, na, nb):
    compiled = isect.intersect_sorted.lower(
        _spec((na,), jnp.int32, one_chip),
        _spec((nb,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (k, n): BFS's largest 2-way union (13,470 keys) and a 3-way one
@pytest.mark.parametrize("k,n", [(2, 1 << 14), (3, 1 << 17)])
def test_multi_merge_ranks_compiles_for_v5e(one_chip, k, n):
    compiled = isect.multi_merge_ranks.lower(
        _spec((k, n), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


N_KEYS = 1 << 20


def test_jax_jit_seam_programs_compile_for_v5e(one_chip):
    positions, merge_sort, seg_sum, seg_min, seg_max = kbk._jx()
    i64 = lambda n: _spec((n,), jnp.int64, one_chip)   # noqa: E731
    f64 = _spec((N_KEYS,), jnp.float64, one_chip)
    with jax.enable_x64(True):
        progs = {
            "positions": positions.lower(i64(N_KEYS), i64(N_KEYS)),
            "merge_sort": merge_sort.lower(i64(N_KEYS)),
            **{name: fn.lower(f64, i64(N_KEYS), N_KEYS // 2)
               for name, fn in (("seg_sum", seg_sum), ("seg_min", seg_min),
                                ("seg_max", seg_max))},
        }
        for name, lowered in progs.items():
            compiled = lowered.compile()
            assert compiled.memory_analysis() is not None, name


def _mosaic_body(spec):
    """The Mosaic payload of the rank kernel lowered afresh for ``spec``
    (its bytes are part of the persistent compile cache's key)."""
    jax.clear_caches()
    text = isect.intersect_sorted.trace(spec, spec).lower().as_text(
        debug_info=True)
    return re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)', text).group(1)


def _lowered_here(spec):
    return _mosaic_body(spec)


def _lowered_there(spec):
    return _mosaic_body(spec)


def test_kernel_cache_key_does_not_depend_on_the_call_path(one_chip):
    """With full tracebacks in MLIR locations, the kernel's payload
    names the line of every caller, so one program reached from two
    call paths has two cache keys (a traced run missed the untraced
    runs' cache this way).  Under the setting a TPU backend applies
    (``kernels.backends._init_device``) the paths agree."""
    spec = _spec((2048,), jnp.int32, one_chip)
    key = "jax_include_full_tracebacks_in_locations"
    prev = getattr(jax.config, key)
    try:
        jax.config.update(key, True)
        assert _lowered_here(spec) != _lowered_there(spec)
        jax.config.update(key, False)
        assert _lowered_here(spec) == _lowered_there(spec)
    finally:
        jax.config.update(key, prev)
        jax.clear_caches()

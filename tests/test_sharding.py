"""Sharding rules + the TeAAL mapping->PartitionSpec compiler."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch import sharding as S
from repro.launch.mesh import make_mesh
from repro.sharding.compiler import (compile_mapping,
                                     mapping_spec_for_step,
                                     step_partition_specs)
from repro.sharding.logical import spec_for, AxisRules


# ---------------------------------------------------------------------- #
# generic param heuristic
# ---------------------------------------------------------------------- #
def test_param_pspec_tp_last_divisible():
    assert S.param_pspec((512, 1024), tp=16, dp=8) == P("data", "model")
    # last dim not divisible -> TP moves to an earlier dim
    assert S.param_pspec((512, 1000), tp=16, dp=8) == P("model", "data")


def test_param_pspec_scan_leading_dim_skipped():
    # [L, d, f]: the layer-stack dim never takes TP; FSDP picks the
    # largest remaining divisible dim (512 here, not the 48-layer dim)
    sp = S.param_pspec((48, 512, 1024), tp=16, dp=8)
    assert sp == P(None, "data", "model")


def test_param_pspec_indivisible_stays_replicated():
    assert S.param_pspec((7, 5), tp=16, dp=16) == P(None, None)


def test_embedding_path_aware():
    mesh = jax.sharding.AbstractMesh((4, 4), ("data", "model"))
    params = {"embed": {"tok": jnp.zeros((1024, 64))},
              "blocks": {"w": jnp.zeros((64, 256))}}
    specs = S.param_pspecs(params, mesh)
    # vocab dim sharded over model (so tied-lm-head logits shard)
    assert specs["embed"]["tok"] == P("model", "data")


def test_divisibility_fallback_in_rules():
    mesh = jax.sharding.AbstractMesh((4, 4), ("data", "model"))
    rules = AxisRules({"batch": ("data",), "heads": ("model",)})
    # 6 heads % 4 != 0 -> replicated, batch 8 % 4 == 0 -> sharded
    sp = spec_for((8, 6), ("batch", "heads"), mesh=mesh)
    import repro.sharding.logical as L
    L.set_rules(rules)
    try:
        sp = spec_for((8, 6), ("batch", "heads"), mesh=mesh)
        assert sp == P("data", None)
    finally:
        L.set_rules(None)


# ---------------------------------------------------------------------- #
# TeAAL mapping -> PartitionSpec compiler
# ---------------------------------------------------------------------- #
def test_compile_mapping_spatial_ranks_shard():
    spec = mapping_spec_for_step(dp=4, tp=4)
    out = compile_mapping(spec, "H", {"B1": "data", "F1": "model"},
                          params={"B0S": 2, "F0S": 8})
    assert out["X"] == P("data", None)         # B sharded, D local
    assert out["Wi"] == P(None, "model")       # F sharded
    assert out["H"] == P("data", "model")


def test_step_partition_specs_end_to_end():
    out = step_partition_specs(global_batch=64, d_model=128, d_ff=512,
                               dp=4, tp=4)
    assert out["H"] == P("data", "model")


def test_compile_mapping_unbound_spatial_rank_raises():
    spec = mapping_spec_for_step(dp=4, tp=4)
    with pytest.raises(ValueError):
        compile_mapping(spec, "H", {"B1": "data"},
                        params={"B0S": 2, "F0S": 8})


# ---------------------------------------------------------------------- #
# cache specs
# ---------------------------------------------------------------------- #
def test_cache_pspecs_shard_kv_seq():
    import repro.configs as C
    mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
    cfg = C.get_smoke("qwen3-14b")
    specs = S.cache_pspecs(cfg, batch=4, max_len=64, mesh=mesh)
    # [L, b, s, kv, h]: batch over pod(data), seq over (data, model)
    assert specs["k"][1] is not None or specs["k"][2] is not None


# ---------------------------------------------------------------------- #
# real multi-device lowering (subprocess: needs its own XLA_FLAGS)
# ---------------------------------------------------------------------- #
SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import repro.configs as C
from repro.launch import sharding as S, steps as ST
from repro.sharding import logical
import dataclasses

cfg = dataclasses.replace(C.get_smoke("olmo-1b"), scan_layers=True)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
logical.set_mesh(mesh); logical.set_rules(S.rules_for("train"))
step = ST.make_train_step(cfg)
import repro.optim.optimizers as opt
specs = {
    "params": ST.param_specs(cfg),
    "opt_state": ST.opt_state_specs(cfg, opt.for_config(cfg)),
}
from repro.configs.base import ShapeSpec
shape = ShapeSpec("t", 64, 8, "train")
specs["batch"] = ST.batch_specs(cfg, shape)
p_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                              S.param_pspecs(specs["params"], mesh))
o_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                              S.param_pspecs(specs["opt_state"], mesh))
b_p = S.batch_pspecs(cfg, shape, mesh)
b_sh = {k: NamedSharding(mesh, b_p[k]) for k in specs["batch"]}
with mesh:
    lowered = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh)).lower(
        specs["params"], specs["opt_state"], specs["batch"])
    compiled = lowered.compile()
ca = compiled.cost_analysis()
ca = ca[0] if isinstance(ca, (list, tuple)) else ca
assert float(ca.get("flops", 0)) > 0
txt = compiled.as_text()
assert ("all-reduce" in txt) or ("all-gather" in txt) or \
       ("reduce-scatter" in txt)
print("SUBPROCESS_OK")
"""


def test_multi_device_train_step_compiles():
    """8 virtual devices, 4x2 mesh, smoke olmo: lower+compile must
    succeed and emit collectives (run in a subprocess so the main
    pytest process keeps its single-device view)."""
    r = subprocess.run([sys.executable, "-c", SUBPROC],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": os.environ.get("HOME", ""),
                            # the child stays off any accelerator: the
                            # chip belongs to one process at a time
                            "JAX_PLATFORMS": "cpu"})
    assert "SUBPROCESS_OK" in r.stdout, r.stderr[-2000:]

"""Fault-tolerant training loop.

Wires together: sharded data -> jit train_step (TeAAL-mapped shardings)
-> async checkpointing -> heartbeat/straggler monitoring -> crash
recovery (restore from the last complete checkpoint) -> elastic resize
hooks (plan_mesh + restore_resharded).

On the offline container this runs the real loop on the 1-CPU mesh
with smoke configs; on a pod the identical code runs under
``jax.distributed`` (host-sharded data via ``Dataset.iterate``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs.base import ModelConfig
from repro.data import DataConfig, ShardedSyntheticDataset
from repro.launch import sharding as S
from repro.launch import steps as ST
from repro.models import api
from repro.optim import optimizers as opt
from repro.runtime.health import HeartbeatMonitor
from repro.sharding import logical

Params = Any


@dataclass
class TrainerConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    log_every: int = 10
    seq_len: int = 128
    global_batch: int = 8
    seed: int = 0
    async_checkpoint: bool = True
    accum_steps: int = 1        # gradient-accumulation microbatches


@dataclass
class TrainState:
    params: Params
    opt_state: Params
    step: int = 0


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainerConfig,
                 mesh: Optional[Mesh] = None,
                 optimizer: Optional[opt.Optimizer] = None):
        self.cfg = cfg
        self.tcfg = tcfg
        self.mesh = mesh or jax.make_mesh(
            (1, 1), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        self.optimizer = optimizer or opt.for_config(cfg)
        self.ckpt = CheckpointManager(tcfg.checkpoint_dir,
                                      keep=tcfg.keep_checkpoints)
        self.monitor = HeartbeatMonitor(n_hosts=jax.process_count())
        self.data = ShardedSyntheticDataset(DataConfig(
            vocab=cfg.vocab, seq_len=tcfg.seq_len,
            global_batch=tcfg.global_batch, seed=tcfg.seed,
            n_patches=cfg.n_patches if cfg.family == "vlm" else 0,
            enc_frames=cfg.enc_frames if cfg.family == "encdec" else 0,
            d_model=cfg.d_model))
        self._step_fn = None
        self.metrics_log: list = []

    # ------------------------------------------------------------------ #
    def init_state(self, seed: int = 0) -> TrainState:
        logical.set_mesh(self.mesh)
        logical.set_rules(S.rules_for("train"))
        with self.mesh:
            params = api.init(self.cfg, jax.random.PRNGKey(seed))
            p_sh = S.param_shardings(params, self.mesh)
            params = jax.tree_util.tree_map(jax.device_put, params, p_sh)
            opt_state = self.optimizer.init(params)
        return TrainState(params=params, opt_state=opt_state, step=0)

    def _compiled_step(self):
        if self._step_fn is None:
            fn = ST.make_train_step(self.cfg, self.optimizer,
                                    accum_steps=self.tcfg.accum_steps)
            self._step_fn = jax.jit(fn, donate_argnums=(0, 1))
        return self._step_fn

    def _device_batch(self, batch: Dict[str, np.ndarray]):
        fixed = {}
        for k, v in batch.items():
            if k in ("patches", "frames"):
                v = v.astype(np.float32)
            fixed[k] = jnp.asarray(v)
        return fixed

    # ------------------------------------------------------------------ #
    def restore_or_init(self) -> TrainState:
        state = self.init_state()
        latest = self.ckpt.latest_step()
        if latest is None:
            return state
        tree = {"params": state.params, "opt_state": state.opt_state}
        shardings = {
            "params": S.param_shardings(state.params, self.mesh),
            "opt_state": jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s),
                S.param_pspecs(state.opt_state, self.mesh)),
        }
        restored, step = self.ckpt.restore(tree, shardings)
        return TrainState(params=restored["params"],
                          opt_state=restored["opt_state"], step=step)

    def train(self, state: Optional[TrainState] = None,
              on_step: Optional[Callable[[int, Dict], None]] = None
              ) -> TrainState:
        state = state or self.restore_or_init()
        step_fn = self._compiled_step()
        logical.set_mesh(self.mesh)
        logical.set_rules(S.rules_for("train"))
        host = jax.process_index()
        try:
            with self.mesh:
                while state.step < self.tcfg.total_steps:
                    t0 = time.time()
                    batch = self._device_batch(
                        self.data.global_batch_at(state.step))
                    params, opt_state, metrics = step_fn(
                        state.params, state.opt_state, batch)
                    loss = float(metrics["loss"])
                    if not np.isfinite(loss):
                        raise FloatingPointError(
                            f"non-finite loss at step {state.step}")
                    state = TrainState(params, opt_state, state.step + 1)
                    dt = time.time() - t0
                    self.monitor.heartbeat(host, state.step, dt)
                    if state.step % self.tcfg.log_every == 0:
                        rec = {"step": state.step, "loss": loss,
                               "grad_norm": float(metrics["grad_norm"]),
                               "s_per_step": dt}
                        self.metrics_log.append(rec)
                        if on_step:
                            on_step(state.step, rec)
                    if state.step % self.tcfg.checkpoint_every == 0:
                        self._save(state)
        finally:
            self.ckpt.wait()
            logical.set_mesh(None)
            logical.set_rules(None)
        self._save(state)
        self.ckpt.wait()
        return state

    def _save(self, state: TrainState) -> None:
        tree = {"params": state.params, "opt_state": state.opt_state}
        if self.tcfg.async_checkpoint:
            self.ckpt.save_async(state.step, tree)
        else:
            self.ckpt.save(state.step, tree)

    # ------------------------------------------------------------------ #
    def run_with_recovery(self, max_restarts: int = 2) -> TrainState:
        """Crash-tolerant outer loop: on any step failure, reload the
        newest complete checkpoint and continue."""
        attempts = 0
        while True:
            try:
                return self.train()
            except (FloatingPointError, RuntimeError) as ex:
                attempts += 1
                if attempts > max_restarts:
                    raise
                print(f"[trainer] step failure ({ex}); restoring from "
                      f"checkpoint (attempt {attempts})")
                self._step_fn = None

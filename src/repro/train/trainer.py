"""Production trainer: jit'd step with explicit shardings, iterative
magnitude pruning (the paper's sparsity source) as a first-class schedule,
checkpoint/restart with exact data resume, preemption handling, and a
straggler watchdog.

Fault-tolerance model (designed for 1000+ nodes, exercised here in-process):
  * checkpoints are topology-agnostic -> restart may change pod count
    (elastic re-shard happens in checkpoint.restore via target shardings);
  * the data pipeline is a pure function of step -> restart resumes the
    exact stream (``SyntheticDataset.skip_to``);
  * SIGTERM/SIGINT trigger a final checkpoint before exit (preemption);
  * a watchdog flags steps slower than ``straggler_factor`` x the rolling
    median — on real fleets this feeds the scheduler; here it logs.
"""

from __future__ import annotations

import dataclasses
import signal
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from ..checkpoint.ckpt import Checkpointer
from ..configs.base import ArchConfig
from ..core.pruning import apply_masks, masks_tree, polynomial_sparsity, tree_sparsity
from ..data.pipeline import SyntheticDataset
from ..dist.sharding import act_rules, batch_shardings, params_shardings
from ..launch.mesh import make_local_mesh
from ..models import build_model
from ..models.common import mesh_context
from ..optim import AdamState, adamw_init
from .step import TrainHParams, make_train_step

__all__ = ["TrainConfig", "Trainer"]


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    global_batch: int = 8
    seq_len: int = 128
    hp: TrainHParams = dataclasses.field(default_factory=TrainHParams)
    # pruning schedule (VUSA): ramp to cfg.sparsity between these steps
    prune_begin: int = 20
    prune_end: int = 80
    prune_every: int = 10
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    straggler_factor: float = 2.0
    seed: int = 0
    token_range: int = 0  # >0: narrow token distribution (learnable synthetic)


class Trainer:
    def __init__(self, cfg: ArchConfig, tc: TrainConfig, mesh=None):
        self.cfg, self.tc = cfg, tc
        self.mesh = mesh or make_local_mesh(1, 1)
        self.rules = act_rules(self.mesh)
        self.model = build_model(cfg)
        self.p_shard = params_shardings(self.model.specs(), self.mesh)
        self.step_fn = make_train_step(self.model.loss, tc.hp)
        self.ckpt = Checkpointer(tc.ckpt_dir) if tc.ckpt_dir else None
        self._preempted = False
        self.metrics_log: List[Dict] = []

    # -- fault tolerance hooks ------------------------------------------------
    def _install_signal_handlers(self):
        def handler(signum, frame):
            self._preempted = True

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, handler)
            except ValueError:
                pass  # not on main thread (tests)

    # -- setup ----------------------------------------------------------------
    def init_state(self):
        with jax.default_device(jax.devices()[0]):
            params = self.model.init(jax.random.key(self.tc.seed))
        params = jax.device_put(params, self.p_shard)
        opt = adamw_init(params)
        return params, opt

    def train(self) -> Dict:
        tc, cfg = self.tc, self.cfg
        self._install_signal_handlers()
        params, opt = self.init_state()
        start_step = 0
        if self.ckpt:
            got, restored = self.ckpt.restore_latest(
                {"params": params, "opt": opt},
                {
                    "params": self.p_shard,
                    "opt": AdamState(step=None, mu=self.p_shard, nu=self.p_shard),
                },
            )
            if got is not None:
                params, opt = restored["params"], restored["opt"]
                start_step = got
        data = SyntheticDataset(
            cfg, tc.global_batch, tc.seq_len, seed=tc.seed, token_range=tc.token_range
        ).skip_to(start_step)

        jit_step = jax.jit(self.step_fn, donate_argnums=(0, 1))
        jit_mask = jax.jit(apply_masks, donate_argnums=0)
        times: List[float] = []
        it = iter(data)
        final_loss = float("nan")
        masks = None  # persistent keep-masks once pruning starts
        with mesh_context(self.mesh, self.rules):
            for step in range(start_step, tc.steps):
                batch = {
                    k: jax.device_put(v, batch_shardings(self.mesh, {k: v})[k])
                    for k, v in next(it).items()
                }
                t0 = time.time()
                params, opt, metrics = jit_step(params, opt, batch)
                jax.block_until_ready(metrics["loss"])
                dt = time.time() - t0

                # straggler watchdog
                times.append(dt)
                med = float(np.median(times[-20:]))
                if len(times) > 5 and dt > tc.straggler_factor * med:
                    print(f"[straggler] step {step}: {dt:.2f}s vs median {med:.2f}s", flush=True)

                # iterative magnitude pruning toward cfg.sparsity: refresh the
                # keep-masks on the schedule, re-apply them every step so the
                # optimizer cannot resurrect pruned weights
                if (
                    cfg.sparsity > 0
                    and step >= tc.prune_begin
                    and step % tc.prune_every == 0
                ):
                    target = polynomial_sparsity(step, tc.prune_begin, tc.prune_end, cfg.sparsity)
                    masks = jax.jit(lambda p: masks_tree(p, target))(params)
                if masks is not None:
                    params = jit_mask(params, masks)

                final_loss = float(metrics["loss"])
                if step % tc.log_every == 0 or step == tc.steps - 1:
                    rec = {"step": step, "loss": final_loss, "dt": dt,
                           "lr": float(metrics["lr"]), "gnorm": float(metrics["gnorm"])}
                    self.metrics_log.append(rec)
                    print(f"step {step:5d} loss {final_loss:.4f} dt {dt*1e3:.0f}ms", flush=True)

                if self.ckpt and ((step + 1) % tc.ckpt_every == 0 or self._preempted):
                    self.ckpt.save(step + 1, {"params": params, "opt": opt})
                if self._preempted:
                    print(f"[preempt] checkpointed at step {step + 1}, exiting", flush=True)
                    break

        if self.ckpt:
            self.ckpt.wait()
        return {
            "params": params,
            "opt": opt,
            "final_loss": final_loss,
            "sparsity": tree_sparsity(params),
            "steps_run": step + 1 - start_step,
        }

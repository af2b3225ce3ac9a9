"""Training loop: QAT, microbatch accumulation, compression, recovery.

``build_train_step`` assembles the jitted step for a (ModelConfig,
RunConfig) pair:

  fake-quant params per PrecisionPolicy (QAT plane, STE)      [paper]
  -> loss/grad (scan-over-layers model, remat per config)
  -> per-microbatch gradient accumulation (lax.scan)          [overlap: the
     per-microbatch reduce-scatter pattern is overlappable on real HW]
  -> posit8 gradient compression with error feedback          [paper-aligned]
  -> global-norm clip -> warmup-cosine LR -> AdamW (8-bit opt)

``train_loop`` adds checkpoint/restart (atomic, async), preemption
recovery (any step may raise; we restore and continue -- except while
lowering or compiling, which raises), and straggler
mitigation hooks (deterministic data re-sharding).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..checkpoint import CheckpointManager
from ..configs.base import ModelConfig, RunConfig
from ..core import sensitivity
from ..core.policy import PrecisionPolicy
from ..models import zoo
from ..optim import OptConfig, adamw_init, adamw_update, warmup_cosine
from ..parallel import collectives
from ..parallel.sharding import (batch_pspec, param_sharding_tree, use_mesh)

__all__ = ["TrainState", "build_train_step", "train_loop", "make_policy",
           "init_state"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    residuals: Any  # grad-compression error feedback (None if unused)

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state, self.residuals), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def make_policy(run: RunConfig, params=None, grads=None) -> PrecisionPolicy:
    name = run.precision_policy
    if name == "mixed":
        return PrecisionPolicy.paper_mixed()
    if name == "adaptive":
        assert params is not None and grads is not None, \
            "adaptive policy needs a calibration gradient"
        return sensitivity.assign_layer_adaptive(
            params, grads, target_avg_bits=run.target_avg_bits)
    return PrecisionPolicy.uniform(name)


def init_state(key, cfg: ModelConfig, run: RunConfig) -> TrainState:
    params = zoo.init_model(key, cfg)
    opt_cfg = OptConfig(weight_decay=run.weight_decay,
                        moment_dtype=run.opt_state_dtype)
    opt_state = adamw_init(params, opt_cfg)
    residuals = (jax.tree.map(jnp.zeros_like, params)
                 if run.grad_compression == "posit8" else None)
    return TrainState(jnp.zeros((), jnp.int32), params, opt_state, residuals)


def build_train_step(cfg: ModelConfig, run: RunConfig,
                     policy: Optional[PrecisionPolicy] = None,
                     mesh=None, donate: bool = True):
    """Returns jitted ``(state, batch) -> (state, metrics)``."""
    opt_cfg = OptConfig(weight_decay=run.weight_decay,
                        moment_dtype=run.opt_state_dtype)
    policy = policy or make_policy(run)
    use_qat = run.qat and policy.default != "fp32"

    def loss_fn(params, batch):
        # QAT happens per-layer inside the scan body (policy threaded in),
        # so only one layer's quantized copy is live at a time.
        return zoo.loss_fn(params, batch, cfg,
                           policy=policy if use_qat else None)

    def grads_of(params, batch):
        (loss, (ce, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return grads, loss, ce, aux

    def step_fn(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        params = state.params
        if run.microbatch > 1:
            # UNROLLED accumulation (python loop, not lax.scan): each
            # microbatch's reduce-scatter is separately schedulable
            # (compute/comm overlap on real HW), and the dry-run's
            # cost_analysis sees every microbatch's FLOPs (a scan body
            # is only counted once by XLA's analysis).
            mb = run.microbatch

            def slice_mb(x, i):
                b = x.shape[0] // mb
                return jax.lax.dynamic_slice_in_dim(x, i * b, b, axis=0)

            grads = loss = ce = aux = None
            for i in range(mb):
                mb_batch = jax.tree.map(lambda x: slice_mb(x, i), batch)
                g, l, c, a = grads_of(params, mb_batch)
                if grads is None:
                    grads, loss, ce, aux = g, l, c, a
                else:
                    grads = jax.tree.map(jnp.add, grads, g)
                    loss, ce, aux = loss + l, ce + c, aux + a
            grads = jax.tree.map(lambda g: g / mb, grads)
            loss, ce, aux = loss / mb, ce / mb, aux / mb
        else:
            grads, loss, ce, aux = grads_of(params, batch)

        residuals = state.residuals
        if run.grad_compression == "posit8":
            grads, residuals = collectives.error_feedback_update(
                grads, residuals)

        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, run.grad_clip / (gnorm + 1e-9)) \
            if run.grad_clip > 0 else 1.0
        grads = jax.tree.map(lambda g: g * scale, grads)

        lr = warmup_cosine(state.step, run.lr, run.warmup_steps, run.steps)
        new_params, new_opt = adamw_update(params, grads, state.opt_state,
                                           lr, opt_cfg)
        new_state = TrainState(state.step + 1, new_params, new_opt, residuals)
        metrics = {"loss": loss, "ce": ce, "aux": aux, "grad_norm": gnorm,
                   "lr": lr}
        return new_state, metrics

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0,) if donate else ())

    # production path: explicit shardings
    def shard_state(state):
        return TrainState(
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            param_sharding_tree(mesh, state.params),
            param_sharding_tree(mesh, state.opt_state),
            param_sharding_tree(mesh, state.residuals)
            if state.residuals is not None else None,
        )
    return step_fn, shard_state  # caller lowers with explicit shardings


def train_loop(cfg: ModelConfig, run: RunConfig, data,
               state: Optional[TrainState] = None,
               policy: Optional[PrecisionPolicy] = None,
               log_every: int = 10,
               hooks: Optional[Dict[str, Callable]] = None) -> Tuple[
                   TrainState, Dict[str, list]]:
    """Single-host training driver with checkpoint/restart."""
    hooks = hooks or {}
    mgr = CheckpointManager(run.checkpoint_dir, keep=run.keep_checkpoints,
                            async_save=True)
    if state is None:
        state = init_state(jax.random.PRNGKey(run.seed), cfg, run)
    # resume if a checkpoint exists
    if mgr.latest_step() is not None:
        state, extra, at = mgr.restore(state)
        if "data" in extra:
            data.load_state_dict(extra["data"])
        print(f"[train] resumed from step {at}")

    step_fn = build_train_step(cfg, run, policy)
    history: Dict[str, list] = {"loss": [], "ce": [], "step": []}
    t0 = time.perf_counter()      # monotonic: immune to NTP clock steps
    compiled = False
    while int(state.step) < run.steps:
        batch = data.next_batch()
        if not compiled:
            # lower and compile outside the retry below (the call reuses
            # this executable): a program the compiler refuses is a
            # fault, not a preemption, so it raises instead of looping
            # through restores
            step_fn.lower(state, batch).compile()
            compiled = True
        try:
            state, metrics = step_fn(state, batch)
        except Exception:
            # preemption / transient failure: restore and retry
            if mgr.latest_step() is None:
                raise
            state, extra, at = mgr.restore(state)
            if "data" in extra:
                data.load_state_dict(extra["data"])
            print(f"[train] step failed; restored from {at}")
            continue
        s = int(state.step)
        if "on_step" in hooks:
            hooks["on_step"](s, state, metrics)
        if s % log_every == 0 or s == run.steps:
            history["loss"].append(float(metrics["loss"]))
            history["ce"].append(float(metrics["ce"]))
            history["step"].append(s)
            dt = (time.perf_counter() - t0) / max(s, 1)
            print(f"[train] step {s:5d} loss {float(metrics['loss']):.4f} "
                  f"ce {float(metrics['ce']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f} ms/step")
        if run.checkpoint_every and s % run.checkpoint_every == 0:
            mgr.save(s, state, {"data": data.state_dict()})
    mgr.wait()
    return state, history

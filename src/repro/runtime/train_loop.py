"""Training runtime: jitted step factory + orchestration loop.

``make_train_step`` assembles the full step the planner's decision vector
describes: remat policy, microbatch accumulation (lax.scan), gradient
compression, AdamW — all inside ONE jit so XLA/GSPMD generates a single
runtime plan that ``hlo_cost`` can cost (the paper's object of study).

``Trainer`` adds the operational shell: cost-based plan selection,
sharded data pipeline, async checkpointing + resume, straggler monitoring,
and elastic re-mesh on cluster-size change.

``OnlineRecalibrator`` closes the estimate↔reality loop at runtime: it
watches the measured/estimated step-time ratio (EWMA), refits a
:class:`repro.core.calibration.CalibrationProfile` when the drift leaves
a band, and — only when the *re-costed plan ranking changes* — routes
through :func:`repro.runtime.elastic.replan` to switch plans.
"""
from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import store
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.calibration import (CalibrationProfile, CalibrationSample,
                                    features_from_totals, fit_profile)
from repro.core.cluster import ClusterConfig
from repro.core.costmodel import (PlanCostCache, VPU_FRACTION, estimate)
from repro.core.planner import (OVERLAP_FRACTION, ShardingPlan,
                                build_step_program, choose_plan)
from repro.data.pipeline import make_pipeline
from repro.models.model import Model, build_model
from repro.optim import adamw, compress
from repro.runtime import tracing
from repro.runtime.straggler import StepTimeMonitor, decide_remesh


def make_train_step(model: Model, opt_cfg: adamw.AdamWConfig,
                    plan: ShardingPlan, *, compress_scheme: str = "none",
                    use_kernel: bool = False) -> Callable:
    """Returns train_step(params, opt_state, ef_state, batch) ->
    (params, opt_state, ef_state, metrics)."""

    def loss_of(params, batch):
        loss, metrics = model.loss(params, batch, remat=plan.remat,
                                   use_kernel=use_kernel)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)
    micro = max(plan.microbatches, 1)

    def train_step(params, opt_state, ef_state, batch):
        if micro > 1:
            def split(x):
                # microbatch k takes rows k, k + micro, ...: each keeps a
                # share of every data shard's rows, so it stays spread
                # over the batch axes (contiguous blocks of rows would put
                # each microbatch on one data shard and leave that axis
                # to shard the model's width)
                b = x.shape[0]
                return x.reshape((b // micro, micro) + x.shape[1:]).swapaxes(0, 1)
            micro_batches = jax.tree.map(split, batch)

            def mb_step(carry, mb):
                gacc, lacc = carry
                (loss, _), grads = grad_fn(params, mb)
                gacc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / micro, gacc, grads)
                return (gacc, lacc + loss / micro), None

            gacc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(
                mb_step, (gacc0, jnp.zeros((), jnp.float32)), micro_batches)
            metrics = {}
        else:
            (loss, metrics), grads = grad_fn(params, batch)

        with jax.named_scope("optimizer"):
            grads, ef_state = compress.compress_grads(grads, ef_state,
                                                      compress_scheme)
            new_params, new_opt, opt_metrics = adamw.apply(opt_cfg, opt_state,
                                                           grads, params)
        out_metrics = {"loss": loss, **opt_metrics,
                       **{k: v for k, v in metrics.items()}}
        return new_params, new_opt, ef_state, out_metrics

    return train_step


# ---------------------------------------------------------------------------
# Online recalibration (estimate↔reality loop)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RecalibrationEvent:
    """One drift-triggered refit: the EWMA ratio that tripped the band,
    the profile fitted from it, and — when the re-costed ranking changed —
    the elastic replan that switches the job onto the new winner."""

    step: int
    ratio: float                        # EWMA measured/estimated at refit
    profile: CalibrationProfile
    replanned: bool
    old_plan: str
    new_plan: str
    elastic: Optional[Any] = None       # ElasticPlan when replanned


class OnlineRecalibrator:
    """Maintains an EWMA of measured/estimated step time and refits the
    calibration profile when drift leaves the band.

    The refit path: the incumbent plan's charged :class:`ProgramTotals`
    become one peak-rate feature vector (``features_from_totals``), the
    EWMA measured time its target, and :func:`fit_profile`'s min-norm
    least squares distributes the drift across the plan's term mix —
    comm-heavy drift lands mostly on the fabric factors, compute-heavy
    drift on the MXU factors.  The candidate ranking is then re-costed
    under the fitted profile (through the shared :class:`PlanCostCache`;
    the calibration-aware cluster fingerprint keeps calibrated and
    uncalibrated entries apart) and :func:`repro.runtime.elastic.replan`
    fires only when the winner actually changes — a uniform slowdown
    rescales every candidate and changes nothing, which is exactly the
    "not merely when the ratio moves" contract.
    """

    def __init__(self, arch: ArchConfig, shape: ShapeConfig,
                 cc: ClusterConfig, *,
                 plan: Optional[ShardingPlan] = None,
                 band: Tuple[float, float] = (0.85, 1.18),
                 alpha: float = 0.25,
                 min_observations: int = 8,
                 cooldown_steps: int = 16,
                 candidates: Optional[List[ShardingPlan]] = None,
                 cache: Optional[PlanCostCache] = None):
        self.arch, self.shape = arch, shape
        self.cc = cc
        self.band = band
        self.alpha = alpha
        self.min_observations = min_observations
        self.cooldown_steps = cooldown_steps
        # an optional vetted plan family: both the ranking check and the
        # elastic replan stay inside it (None = the full enumeration)
        self.candidates = list(candidates) if candidates is not None else None
        self.cache = cache if cache is not None else PlanCostCache()
        self.events: List[RecalibrationEvent] = []
        if plan is None:
            plan = choose_plan(arch, shape, cc, top_k=1,
                               candidates=self.candidates,
                               cache=self.cache)[0].plan
        self._n = 0
        self._step = 0
        self._last_refit: Optional[int] = None
        self.ewma: Optional[float] = None
        self._set_plan(plan)

    # ------------------------------------------------------------------
    def _set_plan(self, plan: ShardingPlan) -> None:
        """Re-cost the incumbent plan under the current (possibly
        calibrated) cc: the estimate the measured ratio is taken against,
        its charged totals (the refit features), and the non-calibratable
        part of the estimate (VPU work, IO, latency)."""
        cc_p = self.cc.with_overlap(OVERLAP_FRACTION if plan.overlap else 0.0)
        est = estimate(build_step_program(self.arch, self.shape, plan, cc_p),
                       cc_p, cache=self.cache)
        self.plan = plan
        self.estimated = est.total
        self._totals = est.totals
        vpu_t = est.totals.vpu_flops / (cc_p.chip.peak("float32")
                                        * VPU_FRACTION)
        self._fixed = est.breakdown.io + est.breakdown.latency + vpu_t

    # ------------------------------------------------------------------
    def observe(self, measured_seconds: float,
                step: Optional[int] = None) -> Optional[RecalibrationEvent]:
        """Feed one measured step time; returns a
        :class:`RecalibrationEvent` when drift triggered a refit."""
        self._n += 1
        self._step = step if step is not None else self._n
        ratio = measured_seconds / self.estimated
        self.ewma = (ratio if self.ewma is None
                     else (1.0 - self.alpha) * self.ewma + self.alpha * ratio)
        if self._n < self.min_observations:
            return None
        if self.band[0] <= self.ewma <= self.band[1]:
            return None
        if (self._last_refit is not None
                and self._step - self._last_refit < self.cooldown_steps):
            return None
        return self._refit()

    # ------------------------------------------------------------------
    def _refit(self) -> RecalibrationEvent:
        from repro.runtime import elastic

        self._last_refit = self._step
        measured = self.ewma * self.estimated
        sample = CalibrationSample(
            features=features_from_totals(self._totals, self.cc),
            measured_seconds=measured,
            estimated_seconds=self.estimated,
            # the fixed offset can't exceed the measurement it is
            # subtracted from (clock noise on very fast steps)
            fixed_seconds=min(self._fixed, 0.5 * measured),
            label=f"online:{self.plan.name}@{self._step}")
        profile = fit_profile([sample], chip_name=self.cc.chip.name).profile
        new_cc = self.cc.with_calibration(profile)
        winner = choose_plan(self.arch, self.shape, new_cc, top_k=1,
                             candidates=self.candidates,
                             cache=self.cache)[0].plan
        replanned = winner != self.plan
        event = RecalibrationEvent(
            step=self._step, ratio=self.ewma, profile=profile,
            replanned=replanned, old_plan=self.plan.describe(),
            new_plan=winner.describe())
        old_plan = self.plan
        self.cc = new_cc
        if replanned:
            event.elastic = elastic.replan(
                self.arch, self.shape, old_cc=new_cc,
                new_mesh_shape=new_cc.mesh_shape,
                new_mesh_axes=new_cc.mesh_axes,
                candidates=self.candidates, cache=self.cache)
            self.cc = event.elastic.cc
            self._set_plan(event.elastic.decision.plan)
        else:
            self._set_plan(old_plan)
        # rebase the EWMA against the calibrated estimate: the fit just
        # explained the drift, so the loop restarts near ratio 1 and only
        # *new* drift can trip the band again
        self.ewma = measured / self.estimated if not replanned else None
        self._n = 0 if replanned else self._n
        self.events.append(event)
        return event


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    log_every: int = 10
    checkpoint_every: int = 50
    ckpt_dir: Optional[str] = None
    seed: int = 0
    compress_scheme: str = "none"
    use_kernel: bool = False
    donate: bool = True
    # Enable the online estimate↔reality loop: an OnlineRecalibrator
    # watches measured step times and refits the calibration profile when
    # drift leaves its band (see OnlineRecalibrator for the replan rule).
    recalibrate: bool = False


class Trainer:
    """End-to-end orchestration (CPU-runnable at reduced scale)."""

    def __init__(self, arch: ArchConfig, shape: ShapeConfig,
                 cc: ClusterConfig, mesh, *,
                 plan: Optional[ShardingPlan] = None,
                 opt_cfg: Optional[adamw.AdamWConfig] = None,
                 tcfg: Optional[TrainerConfig] = None):
        from repro.launch import shardings as S
        self.arch, self.shape, self.cc, self.mesh = arch, shape, cc, mesh
        self.tcfg = tcfg or TrainerConfig()
        self.opt_cfg = opt_cfg or adamw.AdamWConfig(
            total_steps=self.tcfg.steps)
        if plan is None:
            plan = choose_plan(arch, shape, cc, top_k=1)[0].plan
        self.plan = plan
        self.model = build_model(arch)

        # --- shardings from the plan ---
        pshapes = self.model.init_shapes()
        self.param_sh = S.params_shardings(mesh, plan, pshapes)
        batch_shapes = {"tokens": jax.ShapeDtypeStruct(
            (shape.global_batch, shape.seq_len), jnp.int32)}
        fshape = self.model.frontend_shape(shape.global_batch)
        if fshape is not None:
            batch_shapes["frontend"] = jax.ShapeDtypeStruct(
                fshape, jnp.float32)
        self.batch_sh = S.batch_shardings(mesh, plan, batch_shapes)
        opt_shapes = jax.eval_shape(partial(adamw.init, self.opt_cfg), pshapes)
        self.opt_sh = S.opt_state_shardings(mesh, plan, self.param_sh,
                                            opt_shapes)

        step_fn = make_train_step(self.model, self.opt_cfg, plan,
                                  compress_scheme=self.tcfg.compress_scheme,
                                  use_kernel=self.tcfg.use_kernel)
        # the error-feedback state: per-parameter residuals for int8_ef,
        # otherwise one replicated placeholder scalar per parameter
        rep = NamedSharding(mesh, PartitionSpec())
        self.ef_sh = compress.EFState(
            residual=self.param_sh if self.tcfg.compress_scheme == "int8_ef"
            else jax.tree.map(lambda _: rep, self.param_sh))
        donate = (0, 1) if self.tcfg.donate else ()
        # outputs keep the input shardings, so step N+1 reuses step N's
        # executable instead of recompiling for GSPMD's choice of layout
        self.train_step = jax.jit(
            step_fn, donate_argnums=donate,
            out_shardings=(self.param_sh, self.opt_sh, self.ef_sh, rep))
        self.monitor = StepTimeMonitor()
        self.recalibrator = (OnlineRecalibrator(arch, shape, cc,
                                                plan=self.plan)
                             if self.tcfg.recalibrate else None)
        self.checkpointer = (store.AsyncCheckpointer(self.tcfg.ckpt_dir)
                             if self.tcfg.ckpt_dir else None)

    # ------------------------------------------------------------------
    def init_state(self, rng=None):
        rng = rng if rng is not None else jax.random.PRNGKey(self.tcfg.seed)
        with self.mesh:
            params = jax.jit(self.model.init,
                             out_shardings=self.param_sh)(rng)
            opt_state = jax.jit(partial(adamw.init, self.opt_cfg),
                                out_shardings=self.opt_sh)(params)
            if self.tcfg.compress_scheme == "int8_ef":
                init_ef = compress.init_error_feedback
            else:
                def init_ef(ps):
                    return compress.EFState(residual=jax.tree.map(
                        lambda p: jnp.zeros((), jnp.float32), ps))
            ef = jax.jit(init_ef, out_shardings=self.ef_sh)(params)
        return params, opt_state, ef

    def maybe_resume(self, params, opt_state):
        if not self.tcfg.ckpt_dir:
            return params, opt_state, 0
        step = store.latest_step(self.tcfg.ckpt_dir)
        if step is None:
            return params, opt_state, 0
        tree = {"params": params, "opt": opt_state}
        sh = {"params": self.param_sh, "opt": self.opt_sh}
        restored, step = store.restore(self.tcfg.ckpt_dir, tree, shardings=sh)
        # the checkpoint holds post-step-N state: resume at N+1
        return restored["params"], restored["opt"], step + 1

    def run(self, *, start_step: int = 0, params=None, opt_state=None,
            ef=None, on_metrics: Optional[Callable] = None) -> Dict[str, Any]:
        """Train from ``start_step`` until ``tcfg.steps``.  Each step is one
        ``train.step`` span (:mod:`repro.runtime.tracing`) whose children
        are ``input_wait``, ``device_put``, ``dispatch``, ``device_wait``
        (the metrics' read-back, which waits for the step to end),
        ``observe`` and, when it fires, ``checkpoint``; ``time_s`` runs from
        the end of ``input_wait`` to the end of ``device_wait``."""
        if params is None:
            params, opt_state, ef = self.init_state()
            params, opt_state, start_step = self.maybe_resume(params, opt_state)
        fshape = self.model.frontend_shape(self.shape.global_batch)
        pipe = make_pipeline(self.arch.vocab_size, self.shape.seq_len,
                             self.shape.global_batch, seed=self.tcfg.seed,
                             frontend_shape=fshape, start_step=start_step)
        history = []
        gstep = start_step
        try:
            with self.mesh:
                # tcfg.steps is read each step: on_metrics may lower it
                while gstep < self.tcfg.steps:
                    with tracing.step_span("train.step", step_num=gstep):
                        with tracing.span("train.input_wait"):
                            gstep, batch = next(pipe)
                        t0 = time.perf_counter()
                        with tracing.span("train.device_put"):
                            batch = {k: jax.device_put(v, self.batch_sh[k])
                                     for k, v in batch.items()}
                        with tracing.span("train.dispatch"):
                            params, opt_state, ef, metrics = self.train_step(
                                params, opt_state, ef, batch)
                        with tracing.span("train.device_wait"):
                            metrics = {k: float(v) for k, v in metrics.items()}
                        dt = time.perf_counter() - t0
                        with tracing.span("train.observe"):
                            self.monitor.record({0: dt})
                            if self.recalibrator is not None:
                                # observation only: acting on a replan
                                # (restore under new shardings) stays with
                                # the caller, who reads .events / the
                                # returned history
                                self.recalibrator.observe(dt, step=gstep)
                            if gstep % self.tcfg.log_every == 0:
                                history.append({"step": gstep, "time_s": dt,
                                                **metrics})
                                if on_metrics:
                                    on_metrics(history[-1])
                        if (self.checkpointer and gstep > 0
                                and gstep % self.tcfg.checkpoint_every == 0):
                            with tracing.span("train.checkpoint"):
                                self.checkpointer.save(
                                    gstep, {"params": params, "opt": opt_state})
                    gstep += 1
        finally:
            pipe.close()
            if self.checkpointer:
                self.checkpointer.wait()
        return {"params": params, "opt_state": opt_state,
                "history": history}

"""Inconsistent Stochastic Gradient Descent (the paper's contribution).

``isgd(base_rule, ...)`` wraps any base update rule (SGD / Momentum /
Nesterov, §4.3) with inconsistent training:

  1. every iteration runs the normal base update (Alg.1 line 21);
  2. the batch loss is pushed into the O(1) epoch-window queue and the upper
     control limit ψ̄ + kσ is recomputed (lines 13–20);
  3. if the loss exceeded the limit (and warm-up is over), the conservative
     subproblem (Eq. 17) is solved on the same batch with early stopping
     (Alg.2) — extra gradient updates that stay proximal to the entry
     weights w_{t-1} via the ε/(2 n_w)·‖w − w_{t−1}‖² term.

Everything is jit-able: the accelerate branch is a ``lax.cond`` whose
predicate is a *globally reduced* scalar (identical on every device under
pjit — DESIGN.md §2), and the inner solver is a ``lax.while_loop``.

The global reduction is enforced (not just assumed) via the ``reduce_ctx``
argument: every ``loss_and_grad`` evaluation — the main step's and each
subproblem trip's — goes through ``ReduceCtx.wrap_loss_and_grad``, so under
``AxisReduce("data")`` inside a ``shard_map`` the gradients are pmean'd and
ψ is the global-batch mean, making the cond/while control flow identical on
every device (see ``repro.distributed.data_parallel``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis.mode import in_analysis_mode
from repro.core import control
from repro.core.reduce import LOCAL, ReduceCtx
from repro.obs.timing import ACCELERATE, PSI_PUSH, UPDATE, named_scope
from repro.optim.base import UpdateRule


class ISGDState(NamedTuple):
    base: tuple
    queue: control.LossQueue
    iter: jnp.ndarray            # global iteration counter
    accel_count: jnp.ndarray     # how many batches were accelerated
    sub_iters: jnp.ndarray       # total subproblem iterations spent


@dataclass(frozen=True)
class ISGDConfig:
    n_batches: int               # n_b: batches per epoch = queue length
    k_sigma: float = 3.0         # control-limit multiplier (2–3 recommended)
    stop: int = 5                # early-stopping bound for Alg.2
    epsilon: float = 0.1         # conservative-constraint weight (paper: 1e-1)
    zeta: float | None = None    # Alg.2 constant step; default = current lr


def _tree_param_count(params) -> float:
    return float(sum(x.size for x in jax.tree.leaves(params)))


def solve_subproblem(loss_and_grad, params, limit, entry_loss, lr,
                     cfg: ISGDConfig):
    """Alg.2: minimize ½‖ψ(w)−limit‖² + ε/(2n_w)‖w−w_{t-1}‖² by early-stopped
    constant-step descent.  Returns (params, iterations_used)."""
    n_w = _tree_param_count(params)
    zeta = cfg.zeta if cfg.zeta is not None else lr
    w0 = params

    def cond(carry):
        _, psi, it = carry
        return (it < cfg.stop) & (psi > limit)

    def body(carry):
        w, _, it = carry
        psi, grads = loss_and_grad(w)
        scale = (psi - limit)

        def upd(wi, gi, w0i):
            d = (scale * gi.astype(jnp.float32)
                 + cfg.epsilon * (wi.astype(jnp.float32) - w0i.astype(jnp.float32)) / n_w)
            return (wi.astype(jnp.float32) - zeta * d).astype(wi.dtype)

        w = jax.tree.map(upd, w, grads, w0)
        return (w, psi, it + 1)

    if in_analysis_mode():
        # unrolled, convergence-masked loop of exactly ``stop`` iterations —
        # the early-stopping upper bound, so compiled cost counts every trip
        carry = (params, entry_loss, jnp.zeros((), jnp.int32))
        for _ in range(cfg.stop):
            live = cond(carry)
            new = body(carry)
            carry = jax.tree.map(
                lambda a, b: jnp.where(live, b, a), carry, new)
        w, _, used = carry
        return w, used

    w, _, used = jax.lax.while_loop(cond, body, (params, entry_loss, jnp.zeros((), jnp.int32)))
    return w, used


def isgd_init(rule: UpdateRule, cfg: ISGDConfig, params) -> ISGDState:
    return ISGDState(
        base=rule.init(params),
        queue=control.init_queue(cfg.n_batches),
        iter=jnp.zeros((), jnp.int32),
        accel_count=jnp.zeros((), jnp.int32),
        sub_iters=jnp.zeros((), jnp.int32),
    )


def isgd_step(rule: UpdateRule, cfg: ISGDConfig, loss_and_grad: Callable,
              state: ISGDState, params, batch, lr,
              reduce_ctx: ReduceCtx = LOCAL, slot=None):
    """One inconsistent-training iteration (Alg.1 body).

    ``loss_and_grad(params, batch) -> ((loss, aux), grads)`` computes the
    per-shard loss/gradients; ``reduce_ctx`` turns them into the globally
    reduced ψ/grads the controller monitors (identity for single device).

    ``slot`` (static ``None`` or a traced batch index) picks the SPC queue
    write: ``None`` = FIFO push (FCPR: window = one epoch); an index =
    per-batch table write (``control.push_at``), used by non-FCPR batch
    schedules so the limit statistics stay one-entry-per-batch
    (``repro.sched``).
    """
    loss_and_grad = reduce_ctx.wrap_loss_and_grad(loss_and_grad)
    (loss, aux), grads = loss_and_grad(params, batch)

    # line 21: vanilla base update
    with named_scope(UPDATE):
        base_state, params = rule.apply(state.base, params, grads, lr)

    # lines 13-20: queue + control limit
    with named_scope(PSI_PUSH):
        queue = (control.push(state.queue, loss) if slot is None
                 else control.push_at(state.queue, slot, loss))
        limit = control.control_limit(queue, cfg.k_sigma)
    accelerate = (loss > limit)          # warm-up handled by limit=+inf

    # line 22-23: conservative subproblem on the under-trained batch
    def on_accel(p):
        def lg(w):
            (l, _), g = loss_and_grad(w, batch)
            return l, g
        with named_scope(ACCELERATE):
            return solve_subproblem(lg, p, limit, loss, lr, cfg)

    def no_accel(p):
        return p, jnp.zeros((), jnp.int32)

    params, used = jax.lax.cond(accelerate, on_accel, no_accel, params)

    new_state = ISGDState(
        base=base_state,
        queue=queue,
        iter=state.iter + 1,
        accel_count=state.accel_count + accelerate.astype(jnp.int32),
        sub_iters=state.sub_iters + used,
    )
    metrics = {
        "loss": loss,
        "aux": aux,
        "psi_bar": control.mean(queue),
        "psi_std": control.std(queue),
        "limit": limit,
        "accelerated": accelerate,
        "sub_iters": used,
    }
    return new_state, params, metrics


def consistent_step(rule: UpdateRule, loss_and_grad: Callable, state, params,
                    batch, lr, reduce_ctx: ReduceCtx = LOCAL, slot=None):
    """Baseline SGD/Momentum/Nesterov step (no inconsistent training) with the
    same metrics surface, so benchmarks are single-factor (paper §5.2).
    ``slot`` as in :func:`isgd_step`."""
    loss_and_grad = reduce_ctx.wrap_loss_and_grad(loss_and_grad)
    (loss, aux), grads = loss_and_grad(params, batch)
    with named_scope(UPDATE):
        base_state, params = rule.apply(state.base, params, grads, lr)
    queue = (control.push(state.queue, loss) if slot is None
             else control.push_at(state.queue, slot, loss))
    metrics = {
        "loss": loss,
        "aux": aux,
        "psi_bar": control.mean(queue),
        "psi_std": control.std(queue),
        "limit": control.control_limit(queue),
        "accelerated": jnp.zeros((), bool),
        "sub_iters": jnp.zeros((), jnp.int32),
    }
    new_state = ISGDState(base=base_state, queue=queue, iter=state.iter + 1,
                          accel_count=state.accel_count,
                          sub_iters=state.sub_iters)
    return new_state, params, metrics

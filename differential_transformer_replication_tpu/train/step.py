"""The jitted training and evaluation steps.

Replaces the reference's eager hot loop (train.py:261-283) with a single
compiled XLA program per optimizer step: forward, backward, clip, AdamW
update, and (when grad_acc_steps > 1) a ``lax.scan`` over microbatches —
the counter-based Python accumulation at train.py:265-283 becomes part of
the compiled step.

The train state is a plain pytree dict so sharding specs apply uniformly:
``{"params": ..., "opt_state": ..., "step": ...}``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import optax

from differential_transformer_replication_tpu.config import ModelConfig, TrainConfig
from differential_transformer_replication_tpu.models import init_model, model_forward
from differential_transformer_replication_tpu.train.anomaly import (
    apply_guard,
    init_guard_state,
)
from differential_transformer_replication_tpu.train.optim import make_optimizer


def create_train_state(key: jax.Array, cfg: TrainConfig) -> dict:
    model_cfg = cfg.resolved_model()
    params = init_model(key, model_cfg)
    tx, _ = make_optimizer(cfg)
    state = {
        "params": params,
        "opt_state": tx.init(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if cfg.anomaly_guard:
        # guard scalars ride inside the state so the skip/streak logic is
        # part of the one compiled step; checkpointing strips them
        # (train/checkpoint.py), keeping the on-disk format unchanged
        state["guard"] = init_guard_state()
    return state


def loss_fn(
    params: dict,
    x: jnp.ndarray,
    y: jnp.ndarray,
    model_cfg: ModelConfig,
    rng: Optional[jax.Array] = None,
    mesh=None,
) -> jnp.ndarray:
    _, loss = model_forward(params, x, model_cfg, targets=y, rng=rng, mesh=mesh)
    return loss


def make_step_fn(cfg: TrainConfig, mesh=None, param_sync=None,
                 loss_sync=None, grad_sync=None):
    """The raw (un-jitted) optimizer-step function — reused by the
    single-device jit below and by the sharded jit in parallel/dp_step.py
    (which passes its Mesh so attention can go sequence-parallel).

    ``batch`` is ``{"x": (A, B, T), "y": (A, B, T)}`` with A =
    grad_acc_steps microbatches (A=1 for the reference default,
    train.py:68). Gradients are averaged over microbatches, matching the
    reference's ``loss / grad_acc_steps`` scaling (train.py:265).

    ``param_sync``/``loss_sync`` are the overlap-scheduled DP hooks
    (parallel/dp_step.py): ``param_sync`` is an identity-forward pytree
    transform applied to the params INSIDE the differentiated loss, so
    its custom-VJP backward (a per-bucket ``lax.pmean``) fires the
    gradient all-reduce for each layer group as soon as that group's
    cotangents exist — overlapped with the rest of backward instead of
    exposed after it. ``loss_sync`` maps the shard-local loss to the
    global mean for metrics and the anomaly guard. ``grad_sync``
    directly pmeans a gradient pytree; when grad_acc_steps > 1 the
    microbatch scan uses the LOCAL loss and applies it ONCE to the
    accumulated grads — baking param_sync into the scanned loss would
    fire the full per-bucket all-reduce set every microbatch (A x the
    collective volume for a numerically identical result, pmean being
    linear). All three default to None (single-device / GSPMD
    placement, where the partitioner inserts the collectives).
    """
    model_cfg = cfg.resolved_model()
    tx, schedule = make_optimizer(cfg)
    if param_sync is None:
        _loss = loss_fn
    else:
        def _loss(params, x, y, model_cfg, r, mesh):
            return loss_fn(param_sync(params), x, y, model_cfg, r, mesh)

    # the accumulation scan differentiates the LOCAL loss when grad_sync
    # handles the post-scan sync (module docstring)
    _scan_loss = loss_fn if grad_sync is not None else _loss

    def run_grad(params, x, y, r, scale, lf=_loss):
        """value_and_grad of ``lf``, optionally loss-scaled: ``scale`` is
        the fault-injection poison (utils/faults.py) — NaN there makes the
        loss AND every gradient NaN, the exact failure the anomaly guard
        must catch. None (no fault armed) is the production path."""
        if scale is None:
            return jax.value_and_grad(lf)(params, x, y, model_cfg, r, mesh)
        return jax.value_and_grad(
            lambda p: lf(p, x, y, model_cfg, r, mesh) * scale
        )(params)

    def step(state: dict, batch: dict, rng: Optional[jax.Array] = None):
        n_micro = batch["x"].shape[0]
        # (A,) poison scales, present ONLY when NaN faults are armed (the
        # trainer then includes it in EVERY batch so the pytree structure
        # — and the compiled program — never changes mid-run)
        poison = batch.get("poison")
        if n_micro == 1:
            # the reference default (grad_acc_steps=1, train.py:68): skip
            # the scan entirely — the zero-init + accumulate + loop
            # slice/carry machinery costs ~5% of the step at recipe scale
            # (measured via profile; the adds alone pass over all 94M
            # params) for a one-iteration loop
            r = None if rng is None else jax.random.fold_in(rng, 0)
            loss, grads = run_grad(
                state["params"], batch["x"][0], batch["y"][0], r,
                None if poison is None else poison[0],
            )
        else:
            def micro(carry, xs):
                grads_acc, loss_acc, i = carry
                if poison is None:
                    x, y = xs
                    sc = None
                else:
                    x, y, sc = xs
                r = None if rng is None else jax.random.fold_in(rng, i)
                loss, grads = run_grad(state["params"], x, y, r, sc,
                                       lf=_scan_loss)
                grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
                return (grads_acc, loss_acc + loss, i + 1), None

            xs = (batch["x"], batch["y"])
            if poison is not None:
                xs = xs + (poison,)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, state["params"])
            (grads, loss_sum, _), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros(()), jnp.zeros((), jnp.int32)),
                xs,
            )
            grads = jax.tree_util.tree_map(lambda g: g / n_micro, grads)
            loss = loss_sum / n_micro
            if grad_sync is not None:
                # one full-gradient all-reduce per STEP; pmean-of-mean ==
                # mean-of-per-microbatch-pmeans, at 1/A the traffic
                grads = grad_sync(grads)

        if loss_sync is not None:
            # shard-local -> global mean loss, BEFORE the guard reads it:
            # every shard must judge the same scalar or lax.cond could
            # take different branches per device (grads are already
            # globally synced by param_sync's backward)
            loss = loss_sync(loss)
        # per-layer-group gradient norms ((L+2,): embed, blocks, head) —
        # the observability layer logs them next to the per-layer lambdas
        # every eval interval (obs/introspect.py). A handful of reduces
        # over already-materialized grads; the vector stays on device
        # unless the trainer actually fetches it.
        from differential_transformer_replication_tpu.obs.introspect import (
            group_norms,
        )

        # the norm passes over the gradients; the clip that uses the
        # global norm is the optimizer chain's first link, inside
        # "optimizer" below
        with jax.named_scope("grad_norm_clip"):
            grad_norm = optax.global_norm(grads)
            gg = group_norms(grads)
        metrics = {
            "loss": loss,
            "learning_rate": schedule(state["step"]),
            "grad_norm": grad_norm,
            "grad_norm_groups": jnp.concatenate([
                gg["embed"][None], gg["blocks"], gg["head"][None]
            ]),
        }

        def do_update():
            updates, opt_state = tx.update(
                grads, state["opt_state"], state["params"]
            )
            return optax.apply_updates(state["params"], updates), opt_state

        with jax.named_scope("optimizer"):
            if cfg.anomaly_guard:
                # skip the update on a bad step under lax.cond — one
                # compiled program either way (compile count pinned,
                # tests/test_faults.py); the step counter still advances
                # so the lr schedule and the epoch-sampler fast-forward
                # stay exact
                params, opt_state, guard, extra = apply_guard(
                    cfg, state["guard"], loss, grad_norm, do_update,
                    state["params"], state["opt_state"],
                )
                metrics.update(extra)
            else:
                params, opt_state = do_update()

        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        if cfg.anomaly_guard:
            new_state["guard"] = guard
        return new_state, metrics

    return step


def make_train_step(cfg: TrainConfig):
    """``step(state, batch, rng) -> (state, metrics)``, jitted for the
    default (single-device) placement. The state is donated — same
    throughput on v5e (XLA already aliases most buffers) but roughly
    halves peak HBM across the update, like the sharded path
    (parallel/dp_step.py)."""
    return jax.jit(make_step_fn(cfg), donate_argnums=(0,))


def make_multi_train_step(cfg: TrainConfig, steps_per_call: int):
    """``multi(state, batches, rngs) -> (state, stacked metrics)``: K
    optimizer steps per jitted call via ``lax.scan``.

    Why this exists: every program launch marshals each train-state leaf
    (params + two Adam moments per param, ~470 buffers at the reference
    recipe) through the PJRT layer on BOTH sides of the call — measured
    ~5 ms/launch on this platform against an ~81 ms busy step, i.e. ~6%
    of the whole step wasted on argument bookkeeping. Scanning K steps
    inside one program pays that cost once per K steps. The inner math
    is exactly :func:`make_step_fn`, so K=1 and K>1 runs are
    numerically identical given identical batch/rng sequences.

    ``batches``: ``{"x": (K, A, B, T), "y": ...}``; ``rngs``: stacked
    (K, ...) dropout keys, or None when dropout is off (the trainer
    folds one key per global iteration either way, so resume at any
    K-boundary reproduces the same mask sequence)."""
    step = make_step_fn(cfg)
    use_dropout = cfg.resolved_model().dropout > 0.0

    @partial(jax.jit, donate_argnums=(0,))
    def multi(state: dict, batches: dict, rngs=None):
        assert batches["x"].shape[0] == steps_per_call, (
            f"batches carry {batches['x'].shape[0]} steps, expected "
            f"{steps_per_call} (shape (K, A, B, T))"
        )

        def body(st, xs):
            if use_dropout:
                x, y, r = xs
            else:
                x, y = xs
                r = None
            return step(st, {"x": x, "y": y}, r)

        xs = (batches["x"], batches["y"])
        if use_dropout:
            xs = xs + (rngs,)
        return jax.lax.scan(body, state, xs)

    return multi


def make_eval_step(cfg: TrainConfig, mesh=None):
    """Returns ``eval_step(params, x, y) -> loss``, jitted; dropout off
    (model.eval() semantics, train.py:128). Pass the training mesh so a
    sequence-parallel run also evaluates through the ring path instead of
    all-gathering the sequence."""
    model_cfg = cfg.resolved_model()

    @jax.jit
    def eval_step(params: dict, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        return loss_fn(params, x, y, model_cfg, rng=None, mesh=mesh)

    return eval_step


def make_eval_many(cfg: TrainConfig, mesh=None):
    """Returns ``eval_many(params, xs, ys) -> (K,) losses``: a single
    jitted ``lax.scan`` over K stacked eval batches, so an eval pass does
    ONE device->host sync instead of one per batch (the reference's
    estimate_loss loop syncs 400 times per eval, train.py:125-139). The
    per-batch math is identical to :func:`make_eval_step`."""
    model_cfg = cfg.resolved_model()

    @jax.jit
    def eval_many(params: dict, xs: jnp.ndarray, ys: jnp.ndarray) -> jnp.ndarray:
        def body(_, xy):
            x, y = xy
            return None, loss_fn(params, x, y, model_cfg, rng=None, mesh=mesh)

        _, losses = jax.lax.scan(body, None, (xs, ys))
        return losses

    return eval_many

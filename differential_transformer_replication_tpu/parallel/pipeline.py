"""Pipeline parallelism: GPipe microbatch scheduling over the mesh's
``pipeline`` axis.

The reference has no pipeline (or any working distributed) machinery —
its DDP/NCCL imports are dormant (train.py:7-10, 88; SURVEY.md section
2.3). This module is the TPU-native scale-out lever the reference never
built: transformer layers are split into P contiguous stages, one per
device along the ``pipeline`` mesh axis, and microbatches stream through
the stages with activations handed to the next stage by
``jax.lax.ppermute``. The pipeline axis is the LAST, stride-1 mesh axis
(config.py) so neighboring stages are adjacent in ``jax.devices()``
enumeration order — a good default for the handoff, though physical
torus adjacency on large slices is the device-assignment problem
``mesh_utils.create_device_mesh`` exists for.

Design (the standard SPMD pipelining recipe, cf. the public JAX scaling
playbook):

  - **Stage-stacked parameters.** The per-layer ``blocks`` list is
    stacked on a leading layer axis and sharded ``P('pipeline')``: each
    device holds ``n_layer / P`` consecutive layers and scans over them
    (``lax.scan``), with the TRACED 1-based layer index
    ``stage * Lp + j + 1`` feeding the dynamic lambda-init schedule
    (ops/lambdas.py handles traced indices).
  - **GPipe schedule.** With M microbatches (the ``grad_acc_steps`` axis
    of the batch — pipeline microbatching IS gradient accumulation) the
    loop runs ``M + P - 1`` ticks. At tick t, stage s computes microbatch
    ``t - s``; stage 0 feeds ``h0[t]``; the last stage collects outputs
    for microbatch ``t - (P-1)``. Every stage computes every tick (the
    classic ``(P-1)/(M+P-1)`` bubble is idle-compute on garbage, masked
    out of the loss), so keep ``M >= P`` for efficiency.
  - **Embed / head placement.** Embedding and lm-head params are
    replicated over the pipeline axis; each stage computes the (cheap)
    embedding of its own feeds, and only the LAST stage's head output
    enters the loss (``where``-masked, then ``psum`` broadcasts the loss
    so the shard_map output is replicated).
  - **Autodiff does 1F1B's work.** ``jax.grad`` through the tick scan
    transposes each ``ppermute`` into the reverse rotation: the backward
    pass is automatically the mirrored pipeline, and cotangents for the
    replicated embed/head params are psummed across the mesh by
    shard_map's transpose.

Composition: the ``data`` (and ``fsdp``, treated as a second data axis)
mesh dims shard the microbatch batch dim — grads are averaged across
them inside the loss (``pmean``), so one shard_mapped function delivers
PP x DP. The ``tensor`` axis composes too, via shard_map's manual/auto
split: the schedule is MANUAL over ``data``/``fsdp``/``pipeline`` only
(``axis_names``), leaving ``tensor`` an AUTO axis that GSPMD partitions
inside each stage with the Megatron specs from parallel/sharding.py
(Q/K/V head-column, out-proj/down-proj row + psum, vocab-sharded
embedding and lm-head loss). One caveat, documented not hidden: a
``pallas_call`` cannot be GSPMD-partitioned, so under pipeline x tensor
the fused attention kernel's operands are gathered per tensor shard and
the kernel runs replicated over ``tensor`` — the MXU-heavy projections,
FFN, and lm-head still shard. Use ``attention_impl='xla'`` when tensor
sharding of the attention math itself matters under pipeline.

``sequence`` composes the same way, as a second AUTO axis: activations
and the token batch shard their T dim, so LN/FFN/projections/loss are
sequence-parallel and GSPMD inserts the K/V all-gather inside dense
attention (the Megatron-SP flavor of context parallelism — NOT the ring
schedule, which owns its own manual shard_map over ``sequence`` on the
GSPMD path, parallel/ring.py, and cannot nest inside this one).

Restrictions (checked): ``n_layer % P == 0`` and — at train-step
construction — ``micro_batch_size`` divisible by data*fsdp. Dropout is
supported: the step's rng is folded per (data-shard, microbatch, layer)
through the tick schedule (make_pipeline_loss).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from differential_transformer_replication_tpu.config import ModelConfig, TrainConfig
from differential_transformer_replication_tpu.models import common, model_module
from differential_transformer_replication_tpu.ops import causal_mask, rope_cos_sin
from differential_transformer_replication_tpu.parallel.sharding import spec_for
from differential_transformer_replication_tpu.train.optim import make_optimizer
from differential_transformer_replication_tpu.train.step import create_train_state

_DATA_AXES = ("data", "fsdp")
_PIPE_AXIS = "pipeline"


# ---------------------------------------------------------------------------
# Param layout: list-of-blocks <-> stage-stacked


def stack_blocks(params: dict) -> dict:
    """Model params with the per-layer ``blocks`` list stacked on a leading
    layer axis (so it can shard ``P('pipeline')``). All other entries
    (embeddings, final norm, lm head) pass through unchanged."""
    out = dict(params)
    out["blocks"] = common.stack_block_list(params["blocks"])
    return out


def unstack_blocks(params: dict, n_layer: int) -> dict:
    """Inverse of :func:`stack_blocks` — back to the list layout the
    single-device/GSPMD paths and ``save_pretrained`` use."""
    out = dict(params)
    out["blocks"] = common.unstack_block_tree(params["blocks"], n_layer)
    return out


def _path_names(path) -> list:
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


class _Rank:
    """Stand-in leaf for sharding.spec_for with the stacked leading
    layer axis stripped off."""

    def __init__(self, ndim: int):
        self.ndim = ndim


def _drop_fsdp(spec: P) -> tuple:
    """Under pipeline the fsdp mesh dim is a second DATA axis (params
    replicate over it, see the warning in _check_pipeline_cfg), so strip
    it from the GSPMD base spec."""
    return tuple(None if s == "fsdp" else s for s in spec)


def _pipe_spec(path, leaf) -> P:
    """Stacked block leaves shard their leading (layer) axis over
    ``pipeline`` and their remaining dims with the Megatron ``tensor``
    rules (parallel/sharding.py, minus fsdp — see _drop_fsdp); embed/head
    params take the same tensor rules without the layer axis; optimizer
    scalars replicate. Optimizer moments mirror the param tree so their
    paths also contain ``blocks`` and inherit the combined sharding."""
    rank = getattr(leaf, "ndim", 0)
    if "blocks" in _path_names(path) and rank >= 1:
        base = _drop_fsdp(spec_for(path, _Rank(rank - 1)))
        return P(_PIPE_AXIS, *base)
    return P(*_drop_fsdp(spec_for(path, leaf)))


def pipeline_state_sharding(state, mesh: Mesh):
    """NamedSharding pytree for a stage-stacked train state."""
    specs = jax.tree_util.tree_map_with_path(_pipe_spec, state)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )


# ---------------------------------------------------------------------------
# The pipelined loss


def _check_pipeline_cfg(model_cfg: ModelConfig, mesh: Mesh) -> int:
    n_stages = mesh.shape.get(_PIPE_AXIS, 1)
    if n_stages < 2:
        raise ValueError(f"pipeline axis must be > 1, got mesh {dict(mesh.shape)}")
    auto_sharded = [
        ax for ax in ("tensor", "sequence") if mesh.shape.get(ax, 1) != 1
    ]
    if auto_sharded and model_cfg.attention_impl == "pallas":
        import warnings

        warnings.warn(
            f"pipeline x {'/'.join(auto_sharded)} with attention_impl="
            "'pallas': GSPMD cannot partition the fused attention kernel, "
            "so its operands are gathered and the kernel runs REPLICATED "
            f"over the {'/'.join(auto_sharded)} axis "
            "(projections/FFN/lm-head still shard). Use attention_impl="
            "'xla' if attention-math sharding matters here",
            stacklevel=3,
        )
    if mesh.shape.get("sequence", 1) != 1:
        import warnings

        warnings.warn(
            "under pipeline parallelism the sequence axis is GSPMD-SP only: "
            f"sequence_impl={model_cfg.sequence_impl!r} (the ring / ulysses "
            "schedules own their own shard_map and cannot nest inside the "
            "pipeline's) is IGNORED here — activations shard their T dim and "
            "GSPMD inserts the K/V all-gather inside dense attention instead. "
            "Drop --pipeline-parallel if the ring/ulysses schedule itself "
            "matters",
            stacklevel=3,
        )
    if mesh.shape.get("fsdp", 1) != 1:
        import warnings

        warnings.warn(
            "under pipeline parallelism the fsdp axis acts as a SECOND DATA "
            "axis only: non-block params and all optimizer state are "
            "replicated, not ZeRO-sharded (parallel/pipeline.py:_pipe_spec). "
            "Use the GSPMD path (no --pipeline-parallel) for real parameter "
            "sharding",
            stacklevel=3,
        )
    if model_cfg.n_layer % n_stages:
        raise ValueError(
            f"n_layer={model_cfg.n_layer} not divisible by pipeline={n_stages}"
        )
    return n_stages


def make_pipeline_loss(model_cfg: ModelConfig, mesh: Mesh):
    """Returns ``loss(params_stacked, x, y, rng=None) -> scalar`` where
    ``x``/``y`` are ``(M, B, T)`` microbatched token/target ids. The
    scalar is the microbatch-mean loss, averaged over data shards —
    identical semantics to the grad-accumulation scan in train/step.py.

    With ``rng`` given and ``model_cfg.dropout > 0``, dropout is live:
    each (data-shard, microbatch, layer) gets an independent key — the
    base key is folded with the shard's mesh position, then with the
    microbatch index inside the tick, and block_forward splits per
    layer. Without a key, dropout is inert (eval semantics)."""
    n_stages = _check_pipeline_cfg(model_cfg, mesh)
    if model_cfg.ffn_impl != "xla":
        # the stage body is a per-device program (shard_map), so a bare
        # pallas_call would be legal here — but the fused FFN/norm
        # kernels are validated on the single-device and overlap-DP
        # paths only; keep pipeline placements on the reference XLA
        # composition, matching the documented use_fused_ffn fallback
        # for every other multi-device placement (models/common.py)
        model_cfg = model_cfg.replace(ffn_impl="xla")
    layers_per_stage = model_cfg.n_layer // n_stages
    mod = model_module(model_cfg)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def spmd(blocks_loc, rest, x, y, rng):
        # blocks_loc: stage's stacked layers (leading axis layers_per_stage)
        # rest: embed/ln_f/lm_head params, replicated; x/y: (M, B_loc, T)
        # rng: (2,) uint32 key or None (traced; replicated spec)
        stage = jax.lax.axis_index(_PIPE_AXIS)
        M, B, T = x.shape
        is_last = stage == n_stages - 1
        if rng is not None:
            # distinct masks per data shard (the batch is sharded, so the
            # same key on every shard would reuse masks across examples)
            pos = (
                jax.lax.axis_index(_DATA_AXES[0]) * mesh.shape[_DATA_AXES[1]]
                + jax.lax.axis_index(_DATA_AXES[1])
            )
            rng = jax.random.fold_in(rng, pos)

        cos, sin = (
            rope_cos_sin(model_cfg.head_size, T)
            if mod.USES_ROPE
            else (None, None)
        )
        mask = causal_mask(T)

        def stage_fn(h, mb_rng):
            def layer(h, xs):
                blk, j = xs
                li = stage * layers_per_stage + j + 1  # 1-based, traced
                r = None if mb_rng is None else jax.random.fold_in(mb_rng, li)
                fn = lambda h, blk: mod.block_forward(
                    h, blk, li, model_cfg, cos, sin, mask, r
                )
                if model_cfg.remat:
                    policy = common.resolve_remat_policy(
                        model_cfg.remat_policy
                    )
                    kw = {} if policy is None else {"policy": policy}
                    fn = jax.checkpoint(fn, **kw)
                return fn(h, blk), None

            h, _ = jax.lax.scan(
                layer, h, (blocks_loc, jnp.arange(layers_per_stage))
            )
            return h

        def tick(carry, t):
            state, loss_sum = carry
            # embed the fed microbatch lazily inside the tick (token-id
            # gather, cheap every tick) instead of prefetching all M
            # embedded microbatches — that buffer was (M, B, T, E), the
            # largest tensor in the schedule at long context
            feed = mod.embed(rest, x[jnp.clip(t, 0, M - 1)], model_cfg)
            inp = jnp.where(stage == 0, feed, state)
            # the microbatch this stage works on at tick t (clipped garbage
            # during bubble ticks — its output is never used)
            mb = jnp.clip(t - stage, 0, M - 1)
            mb_rng = None if rng is None else jax.random.fold_in(rng, mb)
            out = stage_fn(inp, mb_rng)
            o_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            valid = jnp.logical_and(is_last, t - (n_stages - 1) >= 0)

            # Head + loss on the just-finished microbatch, INSIDE the tick:
            # the carry stays O(B*T*E) plus a scalar instead of collecting
            # all M outputs for a second scan — at long context the
            # (M, B, T, E) collection was the largest tensor in the
            # schedule. lax.cond skips the lm-head matmul entirely on
            # bubble ticks and on every non-last stage; tail_and_loss
            # honors cfg.loss_chunk (the fused chunked head, ops/losses.py)
            # here too.
            def head_loss(op):
                h, idx = op
                yi = jax.lax.dynamic_index_in_dim(y, idx, 0, keepdims=False)
                _, l = common.tail_and_loss(h, rest, model_cfg, yi)
                return l
            l = jax.lax.cond(
                valid, head_loss, lambda op: jnp.zeros(()), (out, o_idx)
            )
            state = jax.lax.ppermute(out, _PIPE_AXIS, perm)
            return (state, loss_sum + l), None

        E = rest["tok_emb"].shape[-1]
        compute = jnp.dtype(model_cfg.compute_dtype)
        (_, loss_sum), _ = jax.lax.scan(
            tick,
            (jnp.zeros((B, T, E), compute), jnp.zeros(())),
            jnp.arange(M + n_stages - 1),
        )
        loss_loc = jnp.where(is_last, loss_sum / M, 0.0)
        loss = jax.lax.psum(loss_loc, _PIPE_AXIS)  # broadcast to all stages
        return jax.lax.pmean(loss, _DATA_AXES)

    # MANUAL over the schedule axes only: ``tensor`` stays an AUTO axis,
    # so GSPMD partitions each stage's matmuls/loss with the Megatron
    # shardings the params carry (pipeline_state_sharding) — in_specs
    # describe the manual axes and the tensor sharding rides along on the
    # arguments themselves.
    manual_axes = frozenset({*_DATA_AXES, _PIPE_AXIS})
    data_specs = (P(_PIPE_AXIS), P(), P(None, _DATA_AXES, None),
                  P(None, _DATA_AXES, None))
    # jit is required, not decorative: shard_map's EAGER impl path
    # (_unmatch_spec, jax 0.9) rejects a manual-subset axis_names; under
    # jit the auto axes partition correctly. Nested under the train-step
    # jit this inlines.
    smapped_plain = jax.jit(jax.shard_map(
        lambda b, r, x, y: spmd(b, r, x, y, None),
        mesh=mesh,
        in_specs=data_specs,
        out_specs=P(),
        axis_names=manual_axes,
        check_vma=False,
    ))
    smapped_dropout = jax.jit(jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=data_specs + (P(),),
        out_specs=P(),
        axis_names=manual_axes,
        check_vma=False,
    ))

    def loss_fn(
        params: dict, x: jnp.ndarray, y: jnp.ndarray, rng=None
    ) -> jnp.ndarray:
        blocks = params["blocks"]
        rest = {k: v for k, v in params.items() if k != "blocks"}
        if rng is not None and model_cfg.dropout > 0.0:
            return smapped_dropout(blocks, rest, x, y, rng)
        return smapped_plain(blocks, rest, x, y)

    return loss_fn


# ---------------------------------------------------------------------------
# Train / eval steps


def create_pipeline_train_state(key: jax.Array, cfg: TrainConfig, mesh: Mesh) -> dict:
    """Train state in the stage-stacked layout, initialized directly onto
    the mesh (each stage materializes only its own layers)."""
    model_cfg = cfg.resolved_model()
    _check_pipeline_cfg(model_cfg, mesh)
    tx, _ = make_optimizer(cfg)

    def init(k):
        state = create_train_state(k, cfg)
        params = stack_blocks(state["params"])
        return {
            "params": params,
            "opt_state": tx.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    abstract = jax.eval_shape(init, key)
    sh = pipeline_state_sharding(abstract, mesh)
    return jax.jit(init, out_shardings=sh)(key)


def make_pipeline_train_step(cfg: TrainConfig, mesh: Mesh, state_template: dict):
    """``step(state, batch, rng=None) -> (state, metrics)`` — same contract
    and metrics as the GSPMD step (parallel/dp_step.py), compiled over the
    pipeline mesh. ``batch['x']``/``['y']`` are ``(A, B, T)``: the
    grad-accumulation axis doubles as the pipeline microbatch stream."""
    model_cfg = cfg.resolved_model()
    n_stages = _check_pipeline_cfg(model_cfg, mesh)
    data_shards = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
    if cfg.micro_batch_size % data_shards:
        raise ValueError(
            f"micro_batch_size={cfg.micro_batch_size} not divisible by the "
            f"data*fsdp shard count {data_shards} (mesh {dict(mesh.shape)})"
        )
    if cfg.grad_acc_steps < n_stages:
        import warnings

        warnings.warn(
            f"grad_acc_steps={cfg.grad_acc_steps} < pipeline stages "
            f"{n_stages}: the GPipe bubble dominates; use at least "
            f"{n_stages} (ideally a few x) microbatches",
            stacklevel=2,
        )
    tx, schedule = make_optimizer(cfg)
    loss_f = make_pipeline_loss(model_cfg, mesh)

    def raw_step(state, batch, rng=None):
        loss, grads = jax.value_and_grad(loss_f)(
            state["params"], batch["x"], batch["y"], rng
        )
        updates, opt_state = tx.update(grads, state["opt_state"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        metrics = {
            "loss": loss,
            "learning_rate": schedule(state["step"]),
            "grad_norm": optax.global_norm(grads),
        }
        return new_state, metrics

    st_sh = pipeline_state_sharding(state_template, mesh)
    # T shards over the AUTO sequence axis (GSPMD-SP); the manual in_specs
    # only describe the data axes, the sequence sharding rides along
    b_sh = NamedSharding(mesh, P(None, _DATA_AXES, "sequence"))
    jitted = jax.jit(
        raw_step,
        in_shardings=(st_sh, {"x": b_sh, "y": b_sh}, None),
        out_shardings=(st_sh, None),
        donate_argnums=(0,),
    )

    def step(state: dict, batch: dict, rng=None):
        return jitted(state, batch, rng)

    return step


def make_pipeline_eval_step(cfg: TrainConfig, mesh: Mesh):
    """``eval_step(params, x, y) -> loss`` on stage-stacked params; ``x``
    is a single (B, T) batch, run through the pipeline as one microbatch
    (bubble fraction (P-1)/P — use :func:`make_pipeline_eval_many` for
    eval loops)."""
    model_cfg = cfg.resolved_model()
    loss_f = make_pipeline_loss(model_cfg, mesh)

    @jax.jit
    def eval_step(params: dict, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        return loss_f(params, x[None], y[None])

    return eval_step


def make_pipeline_eval_many(cfg: TrainConfig, mesh: Mesh):
    """``eval_many(params, xs, ys) -> scalar mean loss`` over a stacked
    (K, B, T) eval set, fed through the pipeline as ONE K-microbatch
    stream: the GPipe bubble amortizes to (P-1)/(K+P-1) instead of
    (P-1)/P at every one of estimate_loss's eval_iters calls (VERDICT r1
    item 7). The scalar mean over the stream equals the mean of per-batch
    losses (equal batch sizes)."""
    model_cfg = cfg.resolved_model()
    loss_f = make_pipeline_loss(model_cfg, mesh)

    @jax.jit
    def eval_many(params: dict, xs: jnp.ndarray, ys: jnp.ndarray) -> jnp.ndarray:
        return loss_f(params, xs, ys)

    return eval_many

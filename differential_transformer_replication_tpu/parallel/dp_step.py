"""The sharded (multi-chip) training step.

Two placements behind one ``make_sharded_train_step`` entry point:

1. **GSPMD** (the general path): ``jax.jit`` with explicit in/out
   shardings over a Mesh — the partitioner inserts the gradient psum
   over the ``data``/``fsdp`` axes and the tensor-parallel
   all-gathers/reduce-scatters implied by the param specs. This is the
   working replacement for the reference's imported-but-never-used
   DDP/NCCL stack (train.py:7-10, 88).

2. **Overlap-scheduled DP** (pure data-parallel meshes, on by default
   via ``TrainConfig.dp_overlap``): the same step body under
   ``shard_map``, with the gradient all-reduce issued PER LAYER-GROUP
   BUCKET from inside the backward pass. GSPMD emits ONE fused
   all-reduce after the whole backward — at the recipe scale that is
   ~378 MB of gradients fully exposed after the last FLOP. Here each
   bucket's params pass through a custom-vjp identity whose backward is
   ``lax.pmean`` over the data axis, so layer k's all-reduce is issued
   the moment layer k's cotangents exist and XLA's latency-hiding
   scheduler overlaps it with the backward compute of layers < k.
   Bucketing is ``TrainConfig.dp_bucket_layers`` consecutive blocks per
   collective (embeddings and the ln_f/lm_head tail ride their own
   buckets, issued last/first respectively). With gradient accumulation
   (``grad_acc_steps > 1``) the microbatch scan instead accumulates
   LOCAL grads and one whole-tree pmean runs after it — the in-backward
   bucket schedule would re-issue every collective per microbatch (A x
   the volume) with nothing left to overlap. Numerically it is the same
   mean gradient modulo float reduction order (parity-tested against
   the single-device step, accumulated and not), and it stays ONE
   jitted program with a donated state — the zero-recompile pin holds
   (tests/test_fused_ffn.py).

The step body is IDENTICAL to the single-device one (train/step.py);
only the placement differs. That is the point of the SPMD design: one
program, any mesh.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from differential_transformer_replication_tpu.config import TrainConfig
from differential_transformer_replication_tpu.parallel.sharding import (
    batch_sharding,
    state_sharding,
)
from differential_transformer_replication_tpu.train.step import (
    create_train_state,
    make_step_fn,
)
from differential_transformer_replication_tpu.utils import faults


def _attach_compile_counter(step, jitted, label: str):
    """Expose a compile-event counter on the step wrapper for the
    trainer's obs layer (``train_compile_events_total``).

    Primary source: the jit's private ``_cache_size`` (compile-cache
    entries; steady state must hold at 1). That attribute is not API —
    on jax versions where it is absent the trainer's counter would
    silently report NOTHING, so fall back to the backend-compile
    monitoring the RecompileSentinel rides (analysis/sanitizers.py:
    ``compile_count``, one event per real XLA backend compilation,
    process-wide). The semantics differ (cache entries vs cumulative
    compiles) but the property the pins watch — the count must stop
    growing at steady state — is the same. Which source is active is
    logged once at build so a drifted jax version is visible in the
    run log, not just as a changed metric baseline.
    """
    step.jitted = jitted  # for lowering: what the step compiles to
    cache_size = getattr(jitted, "_cache_size", None)
    if cache_size is not None:
        step._cache_size = cache_size
        step._compile_counter_source = "jit-cache"
    else:
        from differential_transformer_replication_tpu.analysis.sanitizers import (
            compile_count,
        )

        step._cache_size = compile_count
        step._compile_counter_source = "backend-compile-monitor"
    from differential_transformer_replication_tpu.parallel.multihost import (
        is_primary,
    )

    if is_primary():
        print(
            f"[dp_step] {label}: compile-event source = "
            f"{step._compile_counter_source}"
        )
    return step


# ---------------------------------------------------------------------------
# Overlap-scheduled pure-DP path
# ---------------------------------------------------------------------------


def overlap_eligible(cfg: TrainConfig) -> bool:
    """The bucketed-pmean path covers pure data parallelism only: fsdp
    shards the params themselves (replicated P() specs would be wrong)
    and tensor/sequence/pipeline need the partitioner's per-op
    collectives. Those meshes keep the GSPMD path."""
    m = cfg.mesh
    return (
        cfg.dp_overlap
        and m.data > 1
        and m.fsdp == 1
        and m.tensor == 1
        and m.sequence == 1
        and m.pipeline == 1
        # multi-process pods keep the GSPMD path: its collectives and
        # the checkpoint gather are proven cross-host (test_multihost_*);
        # the shard_map overlap path is validated single-process so far
        and jax.process_count() == 1
    )


def _bucket_sync(axis: str):
    """Identity-forward / pmean-backward pytree transform. Each CALL is
    one gradient bucket: autodiff attaches the pmean where the call
    sits in the forward, so in the backward it fires as soon as every
    cotangent in that bucket exists."""

    @jax.custom_vjp
    def sync(tree):
        return tree

    def fwd(tree):
        return tree, None

    def bwd(_, ct):
        return (
            jax.tree_util.tree_map(lambda g: jax.lax.pmean(g, axis), ct),
        )

    sync.defvjp(fwd, bwd)
    return sync


def make_param_sync(axis: str, bucket_layers: int):
    """``params -> params`` with one :func:`_bucket_sync` application per
    gradient bucket: the embedding table(s), every ``bucket_layers``
    consecutive transformer blocks, and the ln_f/lm_head tail. Backward
    runs tail -> blocks(L..1) -> embeddings, so the per-bucket pmeans
    stream in that order, each overlapping the remaining backward."""
    sync = _bucket_sync(axis)
    group = max(1, int(bucket_layers))

    def param_sync(params: dict) -> dict:
        blocks = params["blocks"]
        tail_keys = [k for k in ("ln_f", "lm_head") if k in params]
        embed_keys = [
            k for k in params if k != "blocks" and k not in tail_keys
        ]
        embed = sync({k: params[k] for k in embed_keys})
        tail = sync({k: params[k] for k in tail_keys})
        new_blocks = []
        for start in range(0, len(blocks), group):
            new_blocks.extend(sync(list(blocks[start:start + group])))
        return {**embed, **tail, "blocks": new_blocks}

    return param_sync


def _make_overlap_train_step(cfg: TrainConfig, mesh: Mesh):
    axis = "data"
    inner = make_step_fn(
        cfg,
        # mesh=None on purpose: inside shard_map every shard is a
        # single-device program — attention must take the plain
        # single-device dispatch, not the shard_map/ring wrappers
        mesh=None,
        param_sync=make_param_sync(axis, cfg.dp_bucket_layers),
        loss_sync=lambda l: jax.lax.pmean(l, axis),
        # grad_acc_steps > 1 syncs the ACCUMULATED grads once after the
        # microbatch scan instead of firing the bucketed pmeans inside
        # every microbatch's backward — with accumulation there is no
        # remaining backward to overlap after the scan anyway, and the
        # per-microbatch schedule moves A x the collective volume for a
        # numerically identical mean (train/step.py docstring)
        grad_sync=lambda g: jax.tree_util.tree_map(
            lambda t: jax.lax.pmean(t, axis), g
        ),
    )

    def raw(state, batch, rng=None):
        if rng is not None:
            # the dropout key rides in replicated (P() spec): fold the
            # shard index in so each data shard draws INDEPENDENT masks
            # for its slice of the batch, matching GSPMD semantics where
            # one global mask is sharded over the batch axis — without
            # this every shard reuses the same masks on its local
            # examples (correlated regularization across the data axis)
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        return inner(state, batch, rng)
    batch_specs = {
        # (A, B, T): microbatch axis replicated, batch sharded over data
        "x": P(None, axis, None),
        "y": P(None, axis, None),
    }
    if faults.nan_armed():
        # (A,) poison scales ride replicated, exactly like the GSPMD
        # path — armed faults never change the jit signature mid-run
        batch_specs["poison"] = P()

    sharded = jax.shard_map(
        raw,
        mesh=mesh,
        in_specs=(P(), batch_specs, P()),
        out_specs=(P(), P()),
        # the custom-vjp pmean confuses the replication checker on some
        # jax versions; replication here is by construction (params and
        # synced grads are identical on every shard)
        check_vma=False,
    )
    # Explicit in/out shardings pin the steady state to ONE cache entry:
    # without them the first call sees the init-time state sharding
    # (state_sharding's size-1-axis specs) while every later call sees
    # the output's replicated sharding — a silent retrace on step 2, the
    # exact pathology the zero-recompile pin forbids. The one-time
    # reshard of the init state is free (size-1 mesh axes ARE
    # replication; no bytes move).
    repl = NamedSharding(mesh, P())
    jitted = jax.jit(
        sharded,
        in_shardings=(
            repl,
            {k: NamedSharding(mesh, s) for k, s in batch_specs.items()},
            None,
        ),
        out_shardings=(repl, None),
        donate_argnums=(0,),
    )

    def step(state: dict, batch: dict, rng=None):
        # normalize the state onto the replicated sharding BEFORE the
        # call: an init-time or resume-time state carries
        # state_sharding's size-1-axis specs, which are physically
        # identical to P() but a DIFFERENT jit cache key — without this
        # the first post-init step silently adds a second cache entry
        # (the compile-event pin watches exactly that). device_put
        # short-circuits when the sharding already matches, so steady
        # state pays one cheap equality sweep, no transfer.
        state = jax.device_put(state, repl)
        return jitted(state, batch, rng)

    return _attach_compile_counter(
        step, jitted, f"overlap-dp step (data={cfg.mesh.data}, "
        f"bucket={cfg.dp_bucket_layers} layers)"
    )


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def make_sharded_train_step(cfg: TrainConfig, mesh: Mesh, state_template: dict):
    """Returns ``step(state, batch, rng) -> (state, metrics)`` compiled
    with the mesh's shardings. ``state_template`` (abstract or concrete)
    supplies the pytree structure for sharding inference. Pure-DP meshes
    take the overlap-scheduled shard_map path (module docstring) unless
    ``cfg.dp_overlap`` is off."""
    if overlap_eligible(cfg):
        return _make_overlap_train_step(cfg, mesh)
    # attention_impl='pallas' on a >1-device mesh routes through the
    # shard_map wrapper (parallel/shard_flash.py) — batch on data/fsdp,
    # heads on tensor — or the ring path when sequence > 1. GSPMD never
    # sees a bare pallas_call.
    st_sh = state_sharding(state_template, mesh)
    b_sh = batch_sharding(mesh)
    batch_shardings = {"x": b_sh, "y": b_sh}
    if faults.nan_armed():
        # fault-injection poison scales ride replicated next to the batch
        # (chaos tests only; absent in production, so the jit signature —
        # and the compiled program — is unchanged when disarmed)
        batch_shardings["poison"] = NamedSharding(mesh, P())

    jitted = jax.jit(
        make_step_fn(cfg, mesh=mesh),
        in_shardings=(st_sh, batch_shardings, None),
        out_shardings=(st_sh, None),
        donate_argnums=(0,),
    )

    def step(state: dict, batch: dict, rng=None):
        return jitted(state, batch, rng)

    # surface the compile-event counter through the wrapper so the
    # trainer's obs layer works on sharded runs too (jit-cache entries
    # when the private attribute exists, backend-compile monitoring
    # otherwise — see _attach_compile_counter)
    return _attach_compile_counter(step, jitted, "gspmd step")


def create_sharded_train_state(key: jax.Array, cfg: TrainConfig, mesh: Mesh) -> dict:
    """Initialize the train state directly onto the mesh: the init is
    jitted with the state sharding as out_shardings, so each device
    materializes only its own shards (no host-side full copy)."""
    abstract = jax.eval_shape(lambda k: create_train_state(k, cfg), key)
    sh = state_sharding(abstract, mesh)
    init = jax.jit(lambda k: create_train_state(k, cfg), out_shardings=sh)
    return init(key)

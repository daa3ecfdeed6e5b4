"""FSDP-style sharded training with a gradient comm-hook point.

The reference does not implement FSDP — it composes with PyTorch FSDP as a
hard dependency of its L4 algorithms and as the consumer of deferred init
(SURVEY §2.4).  This framework therefore provides the TPU-native host
capability itself: a ZeRO-style sharded train step built from
``shard_map`` + XLA collectives.

Design (idiomatic JAX, not a port):
  - Parameters live as *globally sharded* ``jax.Array``s with
    ``NamedSharding(P(shard_axis, ...))`` on their first divisible dim —
    exactly what ``materialize_module(sharding_rule=fsdp_shard_rule(mesh))``
    produces, making deferred-init → FSDP a zero-copy handoff (the north
    star; BASELINE.json).
  - The gradient part of the step runs in ``shard_map`` over the mesh:
    all-gather shards over ``shard_axis`` (ICI) → local fwd/bwd →
    ``psum_scatter`` gradients back into shards (the reduce-scatter of
    classic FSDP) → the **comm hook** decides cross-replica synchronization
    (all-reduce, GossipGraD ppermute gossip, SlowMo local-only, ...) —
    mirroring ``register_comm_hook`` semantics (reference
    gossip_grad.py:334-389).
  - The optimizer update happens *outside* ``shard_map`` on the sharded
    arrays; since optimizer math is elementwise, XLA keeps every optimizer
    state shard local to its parameter shard — ZeRO-1/2 optimizer-state
    sharding falls out of sharding propagation with zero code.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ..nn.remat import remat_scope
from ..obs.comm import record_collective as _record_comm, tree_bytes as _leaf_bytes
from jax import shard_map

from .comm_hooks import DefaultState, Hook, HookContext, allreduce_hook

__all__ = [
    "fsdp_partition_spec",
    "fsdp_shard_rule",
    "donated_carry_shardings",
    "optimizer_state_shardings",
    "ShardedTrainStep",
]


def donated_carry_shardings(*trees: Any) -> tuple:
    """Per-tree ``out_shardings`` mirroring each input's ACTUAL placement.

    The companion of :func:`optimizer_state_shardings` for donated-carry
    steps (TDX101): jit does not propagate input shardings into outputs,
    so a ``donate_argnums`` carry must pin its outputs to the layouts the
    inputs arrived with, or the carry silently decays to jit-chosen
    (usually replicated) placements on the first step.  Leaves without a
    concrete sharding (numpy inputs, abstract values) map to ``None`` —
    jit's free choice, exactly the prior behavior for them.
    """

    def leaf_sharding(x: Any):
        sh = getattr(x, "sharding", None)
        return sh if isinstance(sh, jax.sharding.Sharding) else None

    return tuple(
        jax.tree_util.tree_map(leaf_sharding, t) for t in trees
    )


def accumulate_grads(
    loss_fn: Callable[[Any, Any], jax.Array],
    params: Any,
    batch: Any,
    accum: int,
    split_fn: Callable[[Any, int, int], Any],
    has_aux: bool = False,
    aux_merge: Optional[Callable[[Any], Any]] = None,
):
    """Shared microbatch gradient accumulation: validate the batch's
    common leading dim, split it with ``split_fn(leaf, lead, accum)``
    (callers inject contiguous vs strided strategies), scan
    ``value_and_grad`` over the microbatches accumulating in f32, and
    return ``(mean_loss, grads_in_param_dtype)``.

    With ``has_aux`` the loss_fn returns ``(loss, aux)`` and the result
    becomes ``((mean_loss, aux), grads)``; under accumulation the
    per-microbatch auxes come back scan-stacked on a leading axis
    unless ``aux_merge`` folds them (the numerics taps pass
    ``obs.numerics.reduce_stacked_digests`` — aux is the only escape
    hatch for forward-pass observables under ``value_and_grad``)."""
    if accum == 1:
        return jax.value_and_grad(loss_fn, has_aux=has_aux)(params, batch)
    leads = {
        getattr(x, "shape", ())[:1] for x in jax.tree_util.tree_leaves(batch)
    }
    if len(leads) != 1 or leads == {()}:
        raise ValueError(
            "gradient accumulation requires every batch leaf to share one "
            f"batch-major leading dim; got leading dims {sorted(leads)}"
        )
    (lead,) = next(iter(leads))
    if lead % accum != 0:
        raise ValueError(
            f"batch leading dim {lead} not divisible by accum_steps={accum}"
        )
    micro = jax.tree_util.tree_map(
        lambda x: split_fn(x, lead, accum), batch
    )
    g0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )

    def body(carry, mb):
        loss_acc, g_acc = carry
        if has_aux:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb
            )
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, mb)
            aux = None
        g_acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), g_acc, grads
        )
        return (loss_acc + loss, g_acc), aux

    (loss_sum, g_sum), aux_stack = lax.scan(
        body, (jnp.zeros((), jnp.float32), g0), micro
    )
    grads = jax.tree_util.tree_map(
        lambda p, g: (g / accum).astype(p.dtype), params, g_sum
    )
    mean_loss = loss_sum / accum
    if has_aux:
        if aux_merge is not None:
            aux_stack = aux_merge(aux_stack)
        return (mean_loss, aux_stack), grads
    return mean_loss, grads


def contiguous_split(x, lead, accum):
    """(lead, ...) -> (accum, lead/accum, ...): right inside shard_map,
    where the leaf is already this device's local shard."""
    return x.reshape(accum, lead // accum, *x.shape[1:])


def strided_split(x, lead, accum):
    """Microbatch i takes rows [i::accum], so each keeps the full
    data-parallel extent of a dp-sharded global batch (a contiguous split
    would park every microbatch on one dp slice)."""
    return jnp.moveaxis(x.reshape(lead // accum, accum, *x.shape[1:]), 1, 0)


def optimizer_state_shardings(state_shape: Any, params: Any, mesh: Mesh) -> Any:
    """Shardings for an optimizer state pytree: subtrees structurally equal
    to ``params`` (optax's per-parameter slots) inherit the parameter
    shardings; everything else (step counters, ...) is replicated.

    Needed because jit's sharding propagation does NOT flow input shardings
    into ``zeros_like``-style outputs that never read the input values —
    without explicit out_shardings the whole optimizer state lands on one
    device regardless of how the parameters are sharded.

    Deprecation shim: this is now a projection of the declarative plan
    engine — ``ShardingPlan.optimizer_state_shardings`` (parallel/plan.py)
    derives the same slot inheritance from the plan's RULES (plus the
    ZeRO-2 augmentation), and new code should hold a plan rather than
    call this directly.  This entry point keeps working for trees placed
    by hand: slots inherit each parameter's ACTUAL sharding.
    """
    from .plan import derive_optimizer_state_shardings

    repl = NamedSharding(mesh, P())

    def sharding_of(_path: str, param_leaf: Any):
        return (
            param_leaf.sharding
            if isinstance(param_leaf, jax.Array)
            else repl
        )

    return derive_optimizer_state_shardings(
        state_shape, params, mesh, sharding_of
    )


def fsdp_partition_spec(
    shape: Sequence[int], mesh: Mesh, axis: str, min_shard_elems: int = 1024
) -> P:
    """Shard the first dim divisible by the axis size; else replicate.

    Tiny tensors (< min_shard_elems) stay replicated — sharding a 4-element
    bias across 32 chips costs more in collective latency than it saves.
    """
    n = mesh.shape[axis]
    size = int(np.prod(shape)) if shape else 0
    if size >= min_shard_elems:
        for d, s in enumerate(shape):
            if s % n == 0 and s >= n:
                spec = [None] * len(shape)
                spec[d] = axis
                return P(*spec)
    return P()


def fsdp_shard_rule(
    mesh: Mesh, axis: str = "fsdp", min_shard_elems: int = 1024
) -> Callable[[str, Any], NamedSharding]:
    """A ``materialize_module``-compatible sharding rule: parameters are
    *born* FSDP-sharded (deferred-init → sharded-materialize handoff)."""

    def rule(path: str, like: Any) -> NamedSharding:
        return NamedSharding(
            mesh, fsdp_partition_spec(like.shape, mesh, axis, min_shard_elems)
        )

    return rule


@dataclasses.dataclass
class ShardedTrainStep:
    """A jitted sharded train step with a gradient comm-hook point.

    Args:
      loss_fn: ``loss_fn(params, batch) -> scalar`` (pure).
      optimizer: an optax-style ``GradientTransformation``.
      mesh: the device mesh.
      shard_axis: mesh axis for parameter/optimizer sharding (ZeRO), or
        ``None`` for fully replicated parameters.
      replica_axes: data-parallel axes whose gradient synchronization the
        comm hook owns (the hook sees per-replica gradients and decides:
        all-reduce / gossip / local-only).
      comm_hook / hook_state: the hook pair, mirroring
        ``register_comm_hook(state, hook)``.
      batch_axes: mesh axes the leading batch dim is sharded over
        (default: replica_axes + shard_axis — every data-parallel device).
      divergent_replicas: set True for algorithms where replicas' parameters
        legitimately diverge between synchronizations (GossipGraD, SlowMo).
        Parameters then carry a leading per-replica dim sharded over the
        (single) replica axis, so each node owns its own divergent copy —
        the SPMD translation of the reference's per-rank parameter state.
        Use :meth:`stack_replicas` / :meth:`consensus` to enter/leave this
        layout.
    """

    loss_fn: Callable[[Any, Any], jax.Array]
    optimizer: Any
    mesh: Mesh
    shard_axis: Optional[str] = "fsdp"
    replica_axes: tuple[str, ...] = ()
    comm_hook: Hook = allreduce_hook
    hook_state: Optional[DefaultState] = None
    batch_axes: Optional[tuple[str, ...]] = None
    divergent_replicas: bool = False
    # full PartitionSpec for batch leaves (overrides batch_axes-on-dim0);
    # e.g. P('dp', 'sp') to shard tokens over batch AND sequence axes
    batch_spec: Optional[P] = None
    # microbatch gradient accumulation: each device splits its LOCAL batch
    # shard into accum_steps microbatches scanned sequentially (params are
    # all-gathered once per step, not per microbatch); gradients accumulate
    # in f32 and the comm hook runs once, on the accumulated gradient
    accum_steps: int = 1
    # the declarative plan this step's placements realize.  Defaults to
    # ShardingPlan.fsdp(mesh, shard_axis) for the plain (non-divergent)
    # layouts, whose specs are exactly param_spec's — one object the
    # Trainer can with_mesh() through an elastic reshard.  Divergent-
    # replica layouts (leading per-replica dim) stay plan-less: their
    # lead-dim specs are not expressible as path rules.
    plan: Optional[Any] = None
    # numerics observatory (obs/numerics.py): fuse per-layer activation,
    # per-param-group param/grad, and loss digests into the jitted step.
    # None -> TDX_NUMERICS env; digests ride the step's outputs (zero
    # extra dispatches) and land on self.last_digests, harvested by the
    # Trainer at its existing log-window sync.
    numerics: Optional[bool] = None

    def __post_init__(self) -> None:
        self.last_digests = None
        if self.hook_state is None:
            self.hook_state = DefaultState()
        if (
            self.plan is None
            and self.shard_axis is not None
            and not self.divergent_replicas
        ):
            from .plan import ShardingPlan

            self.plan = ShardingPlan.fsdp(self.mesh, self.shard_axis)
        if self.batch_axes is None:
            axes = list(self.replica_axes)
            if self.shard_axis is not None:
                axes.append(self.shard_axis)
            self.batch_axes = tuple(axes)
        if self.divergent_replicas and len(self.replica_axes) != 1:
            raise ValueError(
                "divergent_replicas requires exactly one replica axis"
            )
        self._jitted = None
        # what "auto" block checkpoints save (nn/remat.py), chosen by
        # _resolve_remat at the first concrete call; the policies the
        # traced blocks named; the checked program's projected peak and
        # the device budget it was held to
        self._remat_auto: Optional[str] = None
        self.remat_named: Optional[frozenset] = None
        self.step_projected_peak_bytes: Optional[int] = None
        self.hbm_budget_bytes: Optional[int] = None

    # -- sharding helpers --------------------------------------------------

    def param_spec(self, leaf: Any) -> P:
        shape = leaf.shape
        lead: tuple = ()
        if self.divergent_replicas:
            lead = (self.replica_axes[0],)
            shape = shape[1:]
        if self.shard_axis is None:
            return P(*lead) if lead else P()
        inner = fsdp_partition_spec(shape, self.mesh, self.shard_axis)
        return P(*lead, *inner)

    def param_sharding(self, tree: Any) -> Any:
        return jax.tree_util.tree_map(
            lambda l: NamedSharding(self.mesh, self.param_spec(l)), tree
        )

    def shard_params(self, params: Any) -> Any:
        """Place (or re-place) a parameter pytree into FSDP sharding."""
        return jax.device_put(params, self.param_sharding(params))

    def stack_replicas(self, params: Any) -> Any:
        """Broadcast params into the per-replica layout (leading replica
        dim, sharded over the replica axis) for divergent-replica hooks."""
        if not self.divergent_replicas:
            return params
        n = self.mesh.shape[self.replica_axes[0]]
        # bring inputs onto the mesh (replicated) so jit sees one device set
        params = jax.device_put(params, NamedSharding(self.mesh, P()))

        def stack(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), tree
            )

        stacked_shardings = jax.tree_util.tree_map(
            lambda l: NamedSharding(
                self.mesh, self.param_spec(jax.ShapeDtypeStruct((n, *l.shape), l.dtype))
            ),
            params,
        )
        return jax.jit(stack, out_shardings=stacked_shardings)(params)

    def consensus(self, params: Any) -> Any:
        """Average the per-replica copies back into a single set."""
        if not self.divergent_replicas:
            return params
        return jax.jit(
            lambda t: jax.tree_util.tree_map(lambda x: x.mean(axis=0), t)
        )(params)

    def init_optimizer(self, params: Any) -> Any:
        """Optimizer state placed to mirror parameter shardings (ZeRO)."""
        state_shape = jax.eval_shape(self.optimizer.init, params)
        shardings = optimizer_state_shardings(state_shape, params, self.mesh)
        return jax.jit(self.optimizer.init, out_shardings=shardings)(params)

    # -- the step ----------------------------------------------------------

    def _build(self, params: Any, opt_state: Any) -> None:
        mesh = self.mesh
        shard_axis = self.shard_axis
        all_axes = tuple(mesh.axis_names)
        batch_spec = (
            self.batch_spec if self.batch_spec is not None else P(self.batch_axes)
        )
        specs = jax.tree_util.tree_map(self.param_spec, params)
        flat_specs, spec_tree = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(x, P)
        )
        hook = self.comm_hook
        state = self.hook_state
        ctx_axes = self.replica_axes
        n_shard = mesh.shape[shard_axis] if shard_axis else 1
        loss_fn = self.loss_fn

        def gather_leaf(x, spec: P):
            if shard_axis is None:
                return x
            for d, ax in enumerate(spec):
                if ax == shard_axis:
                    # audit payload = the GATHERED (full-parameter) bytes
                    _record_comm(
                        "all_gather", shard_axis,
                        payload_bytes=_leaf_bytes(x) * n_shard,
                        axis_size=n_shard,
                    )
                    return lax.all_gather(x, shard_axis, axis=d, tiled=True)
            return x

        def scatter_grad_leaf(g, spec: P):
            if shard_axis is None:
                return g
            for d, ax in enumerate(spec):
                if ax == shard_axis:
                    # the classic FSDP gradient reduce-scatter: payload is
                    # the full gradient (== parameter) bytes — the number
                    # tests/test_comm_audit.py pins against param_bytes
                    _record_comm(
                        "reduce_scatter", shard_axis,
                        payload_bytes=_leaf_bytes(g),
                        axis_size=n_shard,
                    )
                    return (
                        lax.psum_scatter(
                            g, shard_axis, scatter_dimension=d, tiled=True
                        )
                        / n_shard
                    )
            _record_comm(
                "pmean", shard_axis,
                payload_bytes=_leaf_bytes(g), axis_size=n_shard,
            )
            return lax.pmean(g, shard_axis)

        def tree_with_specs(fn, tree):
            flat, td = jax.tree_util.tree_flatten(tree)
            return td.unflatten(
                fn(x, s) for x, s in zip(flat, flat_specs)
            )

        divergent = self.divergent_replicas
        # Data axes whose gradient contributions the trainer itself must
        # combine: every batch axis that is neither a replica axis (the comm
        # hook owns those) nor the shard axis (psum_scatter owns that).
        # Without this, e.g. divergent-gossip over ('node','local') batches
        # would silently drop all but one local device's data.
        data_axes: list[str] = []
        for entry in batch_spec:
            if entry is None:
                continue
            data_axes.extend(entry if isinstance(entry, tuple) else (entry,))
        grad_reduce_axes = tuple(
            ax for ax in data_axes if ax not in ctx_axes and ax != shard_axis
        )

        accum = int(self.accum_steps)
        if accum < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum}")

        from ..obs.numerics import (
            allreduce_digests,
            array_digest,
            numerics_enabled,
            numerics_tape,
            reduce_stacked_digests,
            tree_group_digest,
        )

        num_on = (
            self.numerics
            if self.numerics is not None
            else numerics_enabled()
        )
        # activation digests are per-device partials over the local batch
        # shard: psum over every batch-sharding axis makes the integer
        # fields the exact GLOBAL counts (each (row, token) counted once,
        # any mesh shape) — axes the batch is replicated over must not
        # double-count, so they are excluded
        digest_axes = tuple(dict.fromkeys(data_axes))

        def local_grad(p, batch):
            # inside shard_map the batch leaf is this device's local shard,
            # so a contiguous split is correct
            if num_on:

                def loss_aux(pp, mb):
                    with numerics_tape() as tape:
                        loss = loss_fn(pp, mb)
                    return loss, tape.digests()

                (loss, acts), grads = accumulate_grads(
                    loss_aux, p, batch, accum, contiguous_split,
                    has_aux=True, aux_merge=reduce_stacked_digests,
                )
                return loss, grads, acts
            loss, grads = accumulate_grads(
                loss_fn, p, batch, accum, contiguous_split
            )
            return loss, grads, {}

        def grad_part(p_shards, batch, hook_step):
            full = tree_with_specs(gather_leaf, p_shards)
            if divergent:
                # local view: drop the (size-1 per replica) leading dim
                local = jax.tree_util.tree_map(lambda x: x[0], full)
                loss, grads, acts = local_grad(local, batch)
                grads = jax.tree_util.tree_map(lambda g: g[None], grads)
            else:
                loss, grads, acts = local_grad(full, batch)
            acts = allreduce_digests(acts, digest_axes, mesh.shape)
            if grad_reduce_axes:
                for _ax in grad_reduce_axes:
                    _record_comm(
                        "pmean", _ax, grads, axis_size=mesh.shape[_ax]
                    )
                grads = jax.tree_util.tree_map(
                    lambda g: lax.pmean(g, grad_reduce_axes), grads
                )
            g_shards = tree_with_specs(scatter_grad_leaf, grads)
            ctx = HookContext(replica_axes=ctx_axes, step=hook_step)
            g_shards = hook(state, g_shards, ctx)
            for _ax in all_axes:
                _record_comm(
                    "pmean", _ax, loss, axis_size=mesh.shape[_ax]
                )
            loss = lax.pmean(loss, all_axes)
            return loss, g_shards, acts

        in_specs = (specs, batch_spec, P())
        # the digest dict's leaves are post-psum replicated across the mesh
        out_specs = (P(), specs, P())
        sm = shard_map(
            grad_part,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )

        optimizer = self.optimizer

        def step(params, opt_state, batch, hook_step):
            loss, grads, acts = sm(params, batch, hook_step)
            digs = None
            if num_on:
                # program-order tap set: params -> activations -> loss ->
                # grads, all fused into this one program (rule 1 of
                # obs/numerics.py — zero extra dispatches)
                digs = tree_group_digest(params, "params/")
                digs.update(
                    {f"act/{site}": d for site, d in acts.items()}
                )
                digs["loss"] = array_digest(loss)
                digs.update(tree_group_digest(grads, "grads/"))
            with jax.named_scope("optimizer"):  # metadata only
                updates, opt_state = optimizer.update(
                    grads, opt_state, params
                )
                params = jax.tree_util.tree_map(
                    lambda p, u: (p + u).astype(p.dtype), params, updates
                )
            if num_on:
                return params, opt_state, loss, digs
            return params, opt_state, loss

        # donated carries keep the layouts they arrived with — without
        # this the params/opt_state outputs decay to jit-chosen
        # placements (TDX101; the optimizer-state lesson applied to the
        # step itself)
        p_sh, o_sh = donated_carry_shardings(params, opt_state)
        self._out_sh = (
            (p_sh, o_sh, None, None) if num_on else (p_sh, o_sh, None)
        )
        self._step_fn = step
        # "auto" blocks recompute until _resolve_remat has looked
        self._set_jitted(self._jit_under("full"))
        del spec_tree

    def _jit_under(self, auto: str):
        """The step jitted with ``"auto"`` blocks resolved to ``auto``:
        the scope is set inside the traced function, one jitted callable
        per choice (jit does not key its cache on the scope)."""
        inner = self._step_fn

        def step(params, opt_state, batch, hook_step):
            with remat_scope(auto) as scope:
                out = inner(params, opt_state, batch, hook_step)
            self.remat_named = frozenset(scope.named)
            return out

        return jax.jit(step, donate_argnums=(0, 1), out_shardings=self._out_sh)

    def _set_jitted(self, jitted) -> None:
        from ..obs.recompile import track_jit_cache

        self._jitted = jitted
        track_jit_cache("sharded_train_step", jitted)

    def _resolve_remat(self, args: tuple) -> None:
        """Choose what "auto" blocks save, once, from concrete arguments.

        Where the backend reports an HBM budget, the step is lowered and
        compiled with the blocks keeping their matmul outputs, and that
        executable is kept (the first call finds it in jit's cache: one
        compile) when arguments + outputs - aliased + temps fit the
        budget.  Otherwise, and where there is no budget (the CPU mesh),
        the blocks recompute their forward in the backward."""
        from ..obs.memory import device_hbm_budget
        from ..utils import compat

        budget = device_hbm_budget()
        self.hbm_budget_bytes = budget
        self._remat_auto = "full"
        if budget is None:
            return
        saving = self._jit_under("dots")
        try:
            compiled = saving.lower(*args).compile()
        except jax.errors.JaxRuntimeError as e:
            # a program that cannot fit is refused by the chip's compiler
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return
        ma = compat.compiled_memory_analysis(compiled)
        if ma is not None:
            self.step_projected_peak_bytes = (
                ma.get("arg_bytes", 0) + ma.get("out_bytes", 0)
                - ma.get("alias_bytes", 0) + ma.get("temp_bytes", 0)
            )
        peak = self.step_projected_peak_bytes
        # a step with no "auto" block is the same program either way:
        # keep the one already compiled
        if "auto" not in self.remat_named or (
            peak is not None and peak <= budget
        ):
            self._remat_auto = "dots"
            self._set_jitted(saving)

    @property
    def remat_resolved(self) -> Optional[str]:
        """What the step's block checkpoints save: ``"dots"`` / ``"full"``
        where they asked for "auto", ``"explicit:<policy>"`` where they
        named one, ``"none"`` for a step with no block checkpoint; None
        before the step has been traced."""
        named = self.remat_named
        if named is None or self._remat_auto is None:
            return None
        if "auto" in named:
            return self._remat_auto
        if named:
            return "explicit:" + ",".join(sorted(named))
        return "none"

    def __call__(self, params: Any, opt_state: Any, batch: Any):
        """Run one step.  Returns (params, opt_state, loss).

        With numerics on, the step's fused digest dict (device arrays,
        NOT fetched — the harvester owns the sync boundary) is stashed
        on ``self.last_digests`` so the public 3-tuple stays stable.
        """
        if self._jitted is None:
            self._build(params, opt_state)
        hook_step = self.hook_state.step_args()
        if hook_step is None:
            hook_step = jnp.int32(0)
        args = (params, opt_state, batch, hook_step)
        # never resolved on tracers (a step traced inside an outer jit
        # runs the policy already resolved, or "full" before that)
        if self._remat_auto is None and not any(
            isinstance(x, jax.core.Tracer)
            for x in jax.tree_util.tree_leaves(args)
        ):
            self._resolve_remat(args)
        out = self._jitted(*args)
        self.hook_state.advance()
        if len(out) == 4:
            params, opt_state, loss, self.last_digests = out
            return params, opt_state, loss
        return out

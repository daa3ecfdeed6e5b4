"""Tensor parallelism via GSPMD sharding annotations.

The reference contains no TP at all (SURVEY §2.4 marks it absent); this is
part of the host capability set a TPU framework must own.  The TPU-native
recipe (the scaling-book approach) is *not* manual collective insertion:
pick a mesh, annotate parameter shardings (Megatron-style column/row
splits), and let XLA's SPMD partitioner insert the all-gathers /
reduce-scatters on ICI.

Two pieces:
  - pattern-based sharding rules (``tp_shard_rule``) usable directly as
    ``materialize_module(sharding_rule=...)`` — parameters are *born*
    TP-sharded (optionally 2D TP x FSDP);
  - ``GSPMDTrainStep``: a jitted train step driven purely by those
    annotations.  Comm hooks live on the ``shard_map`` path
    (``ShardedTrainStep``); this path is the compiler-scheduled one.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .fsdp import (
    accumulate_grads,
    donated_carry_shardings,
    optimizer_state_shardings,
    strided_split,
)
from .plan import ShardingPlan

__all__ = [
    "tp_shard_rule",
    "llama_tp_plan",
    "llama_tp_rule",
    "shard_params",
    "GSPMDTrainStep",
]


def tp_shard_rule(
    mesh: Mesh,
    patterns: Sequence[tuple[str, P]],
    *,
    default_axis: Optional[str] = None,
) -> Callable[[str, Any], NamedSharding]:
    """Build a ``sharding_rule(path, like) -> NamedSharding`` from
    ``(regex, PartitionSpec)`` pairs (first match wins).

    Unmatched parameters are replicated, or FSDP-sharded over
    ``default_axis`` when given.

    Deprecation shim: this is now a projection of the declarative plan
    engine — prefer holding the :class:`~.plan.ShardingPlan` itself
    (``ShardingPlan(mesh, rules=patterns, default_axis=...)``), which
    additionally derives optimizer-state/carry shardings, validates,
    and prices the layout.
    """
    return ShardingPlan(
        mesh, rules=tuple(patterns), default_axis=default_axis
    ).as_rule()


def llama_tp_plan(
    mesh: Mesh,
    tp_axis: str = "tp",
    fsdp_axis: Optional[str] = None,
    **plan_kwargs: Any,
) -> ShardingPlan:
    """Megatron-style TP :class:`~.plan.ShardingPlan` for
    :class:`~torchdistx_tpu.models.Llama`.

    Column-parallel (shard output features) for qkv and MLP up/gate;
    row-parallel (shard input features) for the attention output and MLP
    down projections — so each block needs exactly one reduce per
    sub-layer, which XLA inserts.  Embedding and head shard over vocab.
    With ``fsdp_axis``, the other matrix dim is additionally FSDP-sharded
    (2D TP x FSDP).  The plan also carries the serve KV pool's layout as
    the ``kv_cache`` pseudo-path rule (sharded over heads on ``tp_axis``
    — dim 2 of the stored (slots | pages, rows, heads * head_dim) pool,
    which splits into contiguous ``heads / tp`` groups).
    """
    f = fsdp_axis  # may be None -> replicated on that dim
    rules = (
        (r"\.(wq|wk|wv)\.weight$", P(tp_axis, f)),
        (r"\.wo\.weight$", P(f, tp_axis)),
        (r"\.(w_gate|w_up)\.weight$", P(tp_axis, f)),
        (r"\.w_down\.weight$", P(f, tp_axis)),
        (r"tok_emb\.weight$", P(tp_axis, f)),
        (r"lm_head\.weight$", P(tp_axis, f)),
        (r"^kv_cache$", P(None, None, tp_axis)),
    )
    return ShardingPlan(mesh, rules=rules, **plan_kwargs)


def llama_tp_rule(
    mesh: Mesh,
    tp_axis: str = "tp",
    fsdp_axis: Optional[str] = None,
) -> Callable[[str, Any], NamedSharding]:
    """Deprecation shim: :func:`llama_tp_plan`'s rule projection.  New
    code should pass the plan object around (``ServeEngine(plan=...)``,
    ``materialize_module(sharding_rule=plan.as_rule())``) instead of a
    bare rule callable."""
    return llama_tp_plan(mesh, tp_axis, fsdp_axis).as_rule()


def shard_params(
    params: dict, rule: Callable[[str, Any], NamedSharding]
) -> dict:
    """Apply a ``tp_shard_rule``-style rule to an already-materialized
    parameter dict: each leaf is ``device_put`` to ``rule(path, leaf)``
    unless it already carries an equivalent sharding (a no-op then — the
    check keeps re-entrant calls from issuing redundant transfers).

    This is the post-hoc sibling of being *born* sharded via
    ``materialize_module(sharding_rule=...)`` — the serving path uses it
    because inference engines usually receive finished weights rather
    than materialize them (``ServeEngine(mesh=, plan=)``).
    """
    out = {}
    for path, leaf in params.items():
        target = rule(path, leaf)
        sh = getattr(leaf, "sharding", None)
        if sh is not None and sh.is_equivalent_to(target, leaf.ndim):
            out[path] = leaf
        else:
            out[path] = jax.device_put(leaf, target)
    return out


@dataclasses.dataclass
class GSPMDTrainStep:
    """Compiler-partitioned train step: parameters keep their annotated
    shardings (TP / 2D TP x FSDP / anything expressible as NamedSharding),
    and XLA inserts all collectives.

    Use when no gradient comm hook is needed — for hooks (GossipGraD,
    SlowMo) use :class:`ShardedTrainStep`.

    With ``plan=`` the step is plan-driven: optimizer state is created
    under the plan's derived shardings and the donated carry cites
    ``plan.shardings_for`` (TDX101).  A ``zero2=True`` plan turns this
    into an automatic ZeRO-2 step (arXiv:2004.13336): the carry pins
    params replicated but optimizer slots dp-sharded, so XLA computes
    the elementwise update sharded and all-gathers the updated params —
    the step books that gather's ring closed form into the comm audit
    at every dispatch (GSPMD collectives are invisible to the Python
    tracer; plan == audit == counters).
    """

    loss_fn: Callable[[Any, Any], jax.Array]
    optimizer: Any
    mesh: Mesh
    batch_spec: P = P()
    # microbatch gradient accumulation: the global batch's leading dim is
    # split into accum_steps microbatches scanned sequentially, gradients
    # accumulated in f32 — the standard fit-a-bigger-batch lever
    accum_steps: int = 1
    plan: Optional[ShardingPlan] = None
    # numerics observatory (obs/numerics.py): fuse activation / param /
    # grad / loss digests into the jitted step (None -> TDX_NUMERICS).
    # Digests land on self.last_digests as device arrays; the public
    # 3-tuple return is unchanged.  On this compiler-partitioned path
    # the digests are reductions over GLOBAL arrays, so the integer
    # fields are exact whatever the mesh — XLA partitions an int sum
    # without changing its value.
    numerics: Optional[bool] = None

    def __post_init__(self) -> None:
        opt = self.optimizer
        loss_fn = self.loss_fn
        accum = int(self.accum_steps)
        if accum < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum}")

        from ..obs.numerics import (
            array_digest,
            numerics_enabled,
            numerics_tape,
            reduce_stacked_digests,
            tree_group_digest,
        )

        num_on = (
            self.numerics if self.numerics is not None else numerics_enabled()
        )
        self._numerics_on = num_on
        self.last_digests = None

        def step(params, opt_state, batch):
            # strided microbatches keep the full dp extent of the global
            # batch sharding (see strided_split)
            digs = None
            if num_on:

                def loss_aux(p, mb):
                    with numerics_tape() as tape:
                        loss = loss_fn(p, mb)
                    return loss, tape.digests()

                (loss, acts), grads = accumulate_grads(
                    loss_aux, params, batch, accum, strided_split,
                    has_aux=True, aux_merge=reduce_stacked_digests,
                )
                digs = tree_group_digest(params, "params/")
                digs.update({f"act/{s}": d for s, d in acts.items()})
                digs["loss"] = array_digest(loss)
                digs.update(tree_group_digest(grads, "grads/"))
            else:
                loss, grads = accumulate_grads(
                    loss_fn, params, batch, accum, strided_split
                )
            updates, opt_state = opt.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(
                lambda p, u: (p + u).astype(p.dtype), params, updates
            )
            if num_on:
                return params, opt_state, loss, digs
            return params, opt_state, loss

        self._step = step
        # built lazily at the first __call__, where the actual carry
        # placements are known (and rebuildable: elastic reshard resets
        # _jitted to None when the mesh changes under the step)
        self._jitted = None
        self._step_rows: tuple = ()
        self._warned_shardings: set = set()

    def _build(self, params: Any, opt_state: Any) -> None:
        # donated carries keep their arrival layouts (TDX101): GSPMD
        # propagation covers values the outputs READ, but pinning
        # out_shardings keeps fresh outputs (optimizer zeros, dtype
        # casts) from decaying to jit-chosen placements.  For ZeRO-2
        # these pins ARE the mechanism: sharded opt slots + replicated
        # params force XLA to compute the update sharded and gather.
        if self.plan is not None:
            p_sh, o_sh = self.plan.shardings_for(params, opt_state)
        else:
            p_sh, o_sh = donated_carry_shardings(params, opt_state)
        out_sh = (
            (p_sh, o_sh, None, None)
            if self._numerics_on
            else (p_sh, o_sh, None)
        )
        self._jitted = jax.jit(
            self._step,
            donate_argnums=(0, 1),
            out_shardings=out_sh,
        )
        # the ZeRO-2 gather's closed form, priced once from shape/dtype
        # metadata (stable across donation) and booked per dispatch
        self._step_rows = (
            self.plan.price_step(params)
            if self.plan is not None and self.plan.zero2
            else ()
        )
        from ..obs.recompile import track_jit_cache

        track_jit_cache("gspmd_train_step", self._jitted)

    def init_optimizer(self, params: Any) -> Any:
        state_shape = jax.eval_shape(self.optimizer.init, params)
        if self.plan is not None:
            shardings = self.plan.optimizer_state_shardings(
                state_shape, params
            )
        else:
            shardings = optimizer_state_shardings(
                state_shape, params, self.mesh
            )
        return jax.jit(self.optimizer.init, out_shardings=shardings)(params)

    def __call__(self, params: Any, opt_state: Any, batch: Any):
        target = NamedSharding(self.mesh, self.batch_spec)

        mesh_devices = set(self.mesh.devices.flat)

        def place(x: Any) -> Any:
            # keep batches the DataLoader already *distributed* on this mesh
            # (re-placing them to batch_spec could gather every step), but a
            # single-device array — e.g. a default device_put — must still
            # be spread to batch_spec
            if isinstance(x, jax.Array):
                if x.sharding.is_equivalent_to(target, x.ndim):
                    return x
                if (
                    len(x.sharding.device_set) > 1
                    and x.sharding.device_set <= mesh_devices
                ):
                    # accepted as pre-distributed — but a layout that
                    # differs from batch_spec makes XLA reshard/gather it
                    # EVERY step, so say so once per distinct layout
                    sig = (repr(x.sharding), x.shape)
                    if sig not in self._warned_shardings:
                        self._warned_shardings.add(sig)
                        import warnings

                        warnings.warn(
                            f"GSPMDTrainStep: batch leaf {x.shape} arrives "
                            f"with sharding {x.sharding}, not the step's "
                            f"batch_spec {self.batch_spec}; it is passed "
                            "through as-is, which can trigger a per-step "
                            "reshard inside the compiled step. Align the "
                            "DataLoader's sharding with batch_spec to "
                            "silence this.",
                            stacklevel=3,
                        )
                    return x
            return jax.device_put(x, target)

        batch = jax.tree_util.tree_map(place, batch)
        if self._jitted is None:
            self._build(params, opt_state)
        if self._step_rows:
            # analytic-at-dispatch booking (the serve-engine idiom):
            # XLA's ZeRO-2 updated-params all-gather never crosses the
            # Python tracer, so each dispatch books the plan's closed
            # form — a k-step comm audit equals k x price_step exactly
            from ..obs.comm import record_collective

            for r in self._step_rows:
                record_collective(
                    r["kind"],
                    r["axis"],
                    payload_bytes=r["payload_bytes"],
                    count=r["count"],
                    axis_size=r["axis_size"],
                )
        out = self._jitted(params, opt_state, batch)
        if len(out) == 4:
            params, opt_state, loss, self.last_digests = out
            return params, opt_state, loss
        return out

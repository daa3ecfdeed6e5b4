"""Pipeline parallelism: microbatch pipelining over a ``pp`` mesh axis.

Absent in the reference (SURVEY §2.4); built TPU-first: every stage runs
the same SPMD program (shard_map over ``pp``), stage weights live stacked
with a leading ``pp`` dim sharded over the axis, and activations hop to the
next stage with a single ``lax.ppermute`` per tick — a neighbor transfer on
ICI.

Two schedules:

- :func:`pipeline_apply` — forward-only GPipe loop (``n_micro + n_stages -
  1`` ticks).  Differentiable via ``jax.grad`` through the scan, but that
  autodiff backward keeps every microbatch's residuals live: O(n_micro)
  activation memory per stage.  Use it for inference or tiny pipelines.

- :func:`pipeline_train_step` — the real training schedule, a 1F1B-style
  interleaved forward/backward with a *manual* backward pipeline.  Each
  tick every stage executes one forward micro-op and one backward
  micro-op; microbatch ``i``'s backward starts at the last stage in the
  same tick its forward completes there, and gradients ride the reverse
  ``ppermute`` down the pipeline.  In-flight activations per stage are
  bounded by ``2*(n_stages-1-s)`` — O(pipeline depth), independent of
  ``n_micro`` — which is the property that lets microbatch counts scale
  until the ``2*(n_stages-1)/(n_micro + 2*(n_stages-1))`` bubble vanishes.
  Backward recomputes the stage forward from the stashed *input*
  (remat-style: one extra forward per microbatch per stage) instead of
  stashing autodiff residuals, keeping the stash one activation-sized
  buffer per slot.

SPMD lockstep means bubble ticks still execute ``stage_fn`` on zero
inputs with the results masked out via ``jnp.where`` *selects* (never
mask-multiplies: ``where`` discards garbage NaNs; ``0*NaN`` would not) —
that is inherent to single-program pipelining on a mesh axis and costs
only the bubble fraction.

Data parallelism composes: pass ``dp_axis`` and shard the microbatch
batch dim over it (``P(None, "dp", ...)``); per-stage parameter gradients
are ``pmean``-reduced over ``dp`` in-pipeline, and nothing about the
schedule changes.

Tensor parallelism composes through ``param_specs``: pass per-leaf
``PartitionSpec``s that shard stage-weight dims over a ``tp`` mesh axis
(Megatron column/row split) and carry the tp collectives inside
``stage_fn`` with ``collectives.copy_psum_grad`` where the replicated
activation enters the region and ``collectives.allreduce_linear`` after
the row-parallel matmul — NOT a plain ``lax.psum``, whose transpose
double-counts gradients by |tp| under ``check_vma=False`` (see
``collectives.allreduce_linear``).  The schedule is oblivious:
activations stay tp-replicated at stage boundaries, gradients come back
in the same tp-sharded layout as the params.  ``dryrun_multichip`` leg 7
and ``tests/test_pp.py`` exercise the full dp x tp x pp composition.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs.comm import record_collective as _record_comm, tree_bytes as _tree_bytes
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "stack_pipeline_stages",
    "split_microbatches",
    "pipeline_apply",
    "pipeline_train_step",
]


def stack_pipeline_stages(
    stage_params: Sequence[Any], mesh: Mesh, axis: str = "pp"
) -> Any:
    """Stack per-stage parameter pytrees (identical structure) into leaves
    with a leading stage dim sharded over ``axis``.

    Accepts materialized params from ``deferred_init`` +
    ``materialize_module`` per stage — the deferred-init → pipeline
    handoff (BASELINE.json's north-star pattern applied to PP).
    """
    n = mesh.shape[axis]
    if len(stage_params) != n:
        raise ValueError(
            f"{len(stage_params)} stages for a {n}-way {axis!r} axis"
        )
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *stage_params
    )
    shardings = jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, P(axis, *([None] * (l.ndim - 1)))),
        stacked,
    )
    return jax.device_put(stacked, shardings)


def split_microbatches(batch: Any, n_micro: int) -> Any:
    """Reshape every leaf ``(B, ...) -> (n_micro, B // n_micro, ...)``."""

    def split(x):
        if x.shape[0] % n_micro:
            raise ValueError(
                f"batch dim {x.shape[0]} not divisible by n_micro={n_micro}"
            )
        return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])

    return jax.tree_util.tree_map(split, batch)


def pipeline_apply(
    stage_params: Any,
    microbatches: jax.Array,
    *,
    mesh: Mesh,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    axis: str = "pp",
    dp_axis: Optional[str] = None,
    param_specs: Optional[Any] = None,
) -> jax.Array:
    """Run ``microbatches`` (N_micro, *mb_shape) through the pipeline
    (forward-only GPipe schedule).

    ``stage_params`` must be stacked/sharded by :func:`stack_pipeline_stages`
    (leading dim = stage).  ``stage_fn(params_of_stage, x) -> y`` applies one
    stage; activations must keep the microbatch shape.  Returns the
    (N_micro, *mb_shape) outputs of the final stage.  With ``dp_axis``,
    the microbatch *batch* dim (dim 1) is sharded over that axis.
    ``param_specs`` (a pytree of ``PartitionSpec`` matching
    ``stage_params``, leading entry = ``axis``) overrides the default
    pp-only sharding — the tensor-parallel composition hook (module
    docstring).
    """
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    ticks = n_micro + n_stages - 1

    def body(p_local, mb):
        # scan bodies trace once: the audit must record the schedule's
        # STATIC trip count (ticks ppermutes of one activation each),
        # not the single traced occurrence (obs/comm.py docstring)
        _act_bytes = _tree_bytes(mb) // mb.shape[0]
        _record_comm(
            "exchange", axis, payload_bytes=_act_bytes, count=ticks,
            axis_size=n_stages, senders=n_stages - 1,
        )
        _record_comm(
            "all_reduce", axis,
            payload_bytes=_act_bytes * n_micro, axis_size=n_stages,
        )
        p = jax.tree_util.tree_map(lambda a: a[0], p_local)
        idx = lax.axis_index(axis)
        is_first = idx == 0
        is_last = idx == n_stages - 1
        mb_shape = mb.shape[1:]
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            prev_out, outputs = carry
            recv = lax.ppermute(prev_out, axis, perm)
            feed = lax.dynamic_index_in_dim(
                mb, jnp.clip(t, 0, n_micro - 1), keepdims=False
            )
            inp = jnp.where(is_first, feed, recv)
            out = stage_fn(p, inp)
            w = t - (n_stages - 1)
            write = jnp.where(
                is_last & (w >= 0),
                jnp.ones((), bool),
                jnp.zeros((), bool),
            )
            updated = lax.dynamic_update_index_in_dim(
                outputs, out, jnp.clip(w, 0, n_micro - 1), 0
            )
            outputs = jnp.where(write, updated, outputs)
            return (out, outputs), None

        init = (
            jnp.zeros(mb_shape, mb.dtype),
            jnp.zeros((n_micro, *mb_shape), mb.dtype),
        )
        (_, outputs), _ = lax.scan(tick, init, jnp.arange(ticks))
        # results exist on the last stage only; replicate across the axis
        outputs = lax.psum(
            jnp.where(is_last, outputs, jnp.zeros_like(outputs)), axis
        )
        return outputs

    spec_params = param_specs if param_specs is not None else (
        jax.tree_util.tree_map(
            lambda l: P(axis, *([None] * (l.ndim - 1))), stage_params
        )
    )
    mb_spec = P(None, dp_axis) if dp_axis else P()
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(spec_params, mb_spec),
        out_specs=mb_spec,
        check_vma=False,
    )(stage_params, microbatches)


def pipeline_train_step(
    stage_params: Any,
    microbatches: jax.Array,
    targets: Any,
    *,
    mesh: Mesh,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, Any], jax.Array],
    axis: str = "pp",
    dp_axis: Optional[str] = None,
    param_specs: Optional[Any] = None,
) -> tuple[jax.Array, Any]:
    """One pipelined forward+backward: returns ``(loss, grads)``.

    1F1B-style interleaved schedule (module docstring).  Per tick ``t``
    every stage ``s`` runs:

    - *forward* of microbatch ``i_f = t - s`` (input from stage ``s-1``'s
      previous-tick output via ``ppermute``, or ``microbatches[i_f]`` at
      stage 0), stashing the input in a circular buffer of depth
      ``2*n_stages - 1``;
    - *backward* of microbatch ``i_b = t - 2*(n_stages-1) + s``: re-runs
      the stage forward from the stashed input under ``jax.vjp``, seeds
      the cotangent from ``loss_fn`` at the last stage (same tick as that
      microbatch's forward there) or from stage ``s+1``'s previous-tick
      gradient, accumulates parameter grads, and sends the input-gradient
      down the reverse ``ppermute``.

    Total ``n_micro + 2*(n_stages-1)`` ticks.

    ``loss_fn(y, tgt) -> scalar`` (mean over its microbatch) runs on every
    stage each tick (SPMD) with non-last-stage results discarded — fold
    only the lm-head/readout into it, not anything heavier.

    ``targets`` leading dims must match ``microbatches`` (n_micro, b).
    Returns ``loss`` (scalar, replicated) and ``grads`` in the same
    stacked/sharded layout as ``stage_params`` — feed them straight to an
    optimizer over the stacked params.  Gradients are averaged over
    microbatches (and over ``dp_axis`` when given, composing with data
    parallelism).
    """
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    ticks = n_micro + 2 * (n_stages - 1)
    stash_depth = 2 * n_stages - 1

    def body(p_local, mb, tgt):
        # static 1F1B schedule accounting (scan traces once — see
        # pipeline_apply): every tick runs one forward and one backward
        # ppermute of a microbatch activation, 2*ticks total, plus the
        # final loss psum and the dp reductions below.  Closed form
        # pinned in tests/test_comm_audit.py.
        _act_bytes = _tree_bytes(mb) // mb.shape[0]
        _record_comm(
            "exchange", axis, payload_bytes=_act_bytes, count=2 * ticks,
            axis_size=n_stages, senders=n_stages - 1,
        )
        _record_comm(
            "all_reduce", axis,
            payload_bytes=np.dtype(np.float32).itemsize,
            axis_size=n_stages,
        )
        p = jax.tree_util.tree_map(lambda a: a[0], p_local)
        s_idx = lax.axis_index(axis)
        is_first = s_idx == 0
        is_last = s_idx == n_stages - 1
        mb_shape = mb.shape[1:]
        perm_f = [(i, i + 1) for i in range(n_stages - 1)]
        perm_b = [(i + 1, i) for i in range(n_stages - 1)]

        def tick(carry, t):
            prev_f, prev_b, stash, gacc, lacc = carry
            recv_f = lax.ppermute(prev_f, axis, perm_f)
            recv_b = lax.ppermute(prev_b, axis, perm_b)

            # ---- forward micro-op: microbatch i_f = t - s -------------
            i_f = t - s_idx
            valid_f = (i_f >= 0) & (i_f < n_micro)
            feed = lax.dynamic_index_in_dim(
                mb, jnp.clip(i_f, 0, n_micro - 1), keepdims=False
            )
            x_in = jnp.where(is_first, feed, recv_f)
            y = stage_fn(p, x_in)
            stash_new = lax.dynamic_update_index_in_dim(
                stash, x_in, i_f % stash_depth, 0
            )
            stash = jnp.where(valid_f, stash_new, stash)

            # ---- backward micro-op: i_b = t - 2*(S-1) + s -------------
            # (stash read AFTER the forward write: at the last stage
            # i_b == i_f, consuming the input stashed this very tick)
            i_b = t - 2 * (n_stages - 1) + s_idx
            valid_b = (i_b >= 0) & (i_b < n_micro)
            x_b = lax.dynamic_index_in_dim(
                stash, i_b % stash_depth, keepdims=False
            )
            y_b, vjp = jax.vjp(stage_fn, p, x_b)
            tgt_b = jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(
                    a, jnp.clip(i_b, 0, n_micro - 1), keepdims=False
                ),
                tgt,
            )
            loss_b, g_y = jax.value_and_grad(
                lambda yy: loss_fn(yy, tgt_b)
            )(y_b)
            g_in = jnp.where(is_last, g_y, recv_b)
            dp, dx = vjp(g_in)
            gacc = jax.tree_util.tree_map(
                lambda a, d: jnp.where(valid_b, a + d, a), gacc, dp
            )
            lacc = lacc + jnp.where(
                valid_b & is_last, loss_b.astype(jnp.float32), 0.0
            )
            # zero invalid sends so bubble-tick garbage never propagates
            prev_f = jnp.where(valid_f, y, jnp.zeros_like(y))
            prev_b = jnp.where(valid_b, dx, jnp.zeros_like(dx))
            return (prev_f, prev_b, stash, gacc, lacc), None

        init = (
            jnp.zeros(mb_shape, mb.dtype),
            jnp.zeros(mb_shape, mb.dtype),
            jnp.zeros((stash_depth, *mb_shape), mb.dtype),
            jax.tree_util.tree_map(jnp.zeros_like, p),
            jnp.zeros((), jnp.float32),
        )
        (_, _, _, gacc, lacc), _ = lax.scan(
            tick, init, jnp.arange(ticks)
        )

        loss = lax.psum(lacc, axis) / n_micro  # nonzero on last stage only
        gacc = jax.tree_util.tree_map(lambda g: g / n_micro, gacc)
        if dp_axis is not None:
            _record_comm(
                "pmean", dp_axis, gacc, axis_size=mesh.shape[dp_axis]
            )
            _record_comm(
                "pmean", dp_axis, loss, axis_size=mesh.shape[dp_axis]
            )
            loss = lax.pmean(loss, dp_axis)
            gacc = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, dp_axis), gacc
            )
        # re-add the unit stage dim so outputs mirror stage_params' layout
        gacc = jax.tree_util.tree_map(lambda g: g[None], gacc)
        return loss, gacc

    spec_params = param_specs if param_specs is not None else (
        jax.tree_util.tree_map(
            lambda l: P(axis, *([None] * (l.ndim - 1))), stage_params
        )
    )
    mb_spec = P(None, dp_axis) if dp_axis else P()
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(spec_params, mb_spec, mb_spec),
        out_specs=(P(), spec_params),
        check_vma=False,
    )(stage_params, microbatches, targets)

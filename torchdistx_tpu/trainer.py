"""High-level trainer tying the pieces together: deferred init -> sharded
materialize -> train loop with comm hooks, metrics, and checkpointing.

The reference is explicitly *not* a trainer (SURVEY "What torchdistx is
NOT") — it plugs into torch trainers.  This framework owns the host side,
so it ships the loop: prefetching data, jitted steps, tokens/sec metrics,
and periodic checkpoint/resume.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, Callable, Iterable, Optional

import jax

from .obs.blackbox import resolve_record
from .obs.comm import CommProfile, comm_audit
from .obs.flight import get_flight_recorder
from .obs.trace import get_tracer
from .utils.checkpoint import restore_checkpoint, save_checkpoint

__all__ = ["Trainer", "batch_digest"]


def batch_digest(batch: Any) -> str:
    """Identity digest of one training batch, host-side only: numpy
    leaves hash by bytes (shape/dtype included), already-on-device
    leaves by shape/dtype/type — NEVER fetched, so digesting a batch
    costs zero device syncs.  Two fits fed bit-identical host batches
    produce identical digests; a shuffled/corrupted pipeline names the
    first differing step."""
    h = hashlib.sha256()
    import numpy as np

    for leaf in jax.tree_util.tree_leaves(batch):
        if isinstance(leaf, np.ndarray):
            h.update(str((leaf.shape, str(leaf.dtype))).encode())
            h.update(np.ascontiguousarray(leaf).tobytes())
        elif isinstance(leaf, (bool, int, float, str, bytes)):
            h.update(repr(leaf).encode())
        else:
            h.update(
                str(
                    (
                        type(leaf).__name__,
                        getattr(leaf, "shape", None),
                        str(getattr(leaf, "dtype", "")),
                    )
                ).encode()
            )
    return h.hexdigest()


class Trainer:
    """Drive a train step (ShardedTrainStep / GSPMDTrainStep / any callable
    ``step(params, opt_state, batch) -> (params, opt_state, loss)``).

    Args:
      step: the step callable.
      params / opt_state: initial state (``opt_state=None`` uses
        ``step.init_optimizer(params)`` when available).
      tokens_per_batch: if given, logs tokens/sec.
      checkpoint_dir / checkpoint_every: periodic checkpointing.
      log_every / log_fn: metric emission (default: one JSON line to
        stdout).
    """

    def __init__(
        self,
        step: Callable[..., Any],
        params: Any,
        opt_state: Any = None,
        *,
        tokens_per_batch: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1000,
        log_every: int = 50,
        log_fn: Optional[Callable[[dict], None]] = None,
        failure_detector: Optional[Any] = None,
        on_failure: str = "raise",
        flight: Optional[Any] = None,
        flops_per_token: Optional[float] = None,
        peak_flops: Optional[float] = None,
        cost_card: bool = True,
        stall_timeout_s: Optional[float] = None,
        record: Any = None,
    ) -> None:
        self.step = step
        self.params = params
        if opt_state is None and hasattr(step, "init_optimizer"):
            opt_state = step.init_optimizer(params)
        self.opt_state = opt_state
        self.tokens_per_batch = tokens_per_batch
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        self.log_every = log_every
        self.log_fn = log_fn or (lambda m: print(json.dumps(m), flush=True))
        # failure handling (utils.failure): losses are checked at log
        # boundaries (where they are realized anyway — zero extra syncs);
        # on_failure: "raise" | "continue" (log-only) | "restore" (roll
        # back to the latest health-gated checkpoint) | "reshard"
        # (device_loss: shrink the mesh and migrate live state onto the
        # survivors — parallel/reshard.py; other kinds roll back).
        # For suppressing the poisoned update ITSELF, wrap the optimizer
        # with utils.failure.guard_nonfinite_updates.
        self.failure_detector = failure_detector
        self.on_failure = on_failure
        self.global_step = 0
        self._history: list[float] = []
        self._last_checkpoint: Optional[str] = None
        # flight recorder (obs.flight): ring-records at log boundaries /
        # checkpoints / failures, dumped atomically when the run breaks —
        # defaults to the process-wide recorder (TDX_FLIGHT_DIR sink)
        self.flight = flight if flight is not None else get_flight_recorder()
        self.last_flight_dump: Optional[str] = None
        # session black box (obs.blackbox): the train-side step-window
        # analog of the serve recorder.  Every step records its batch
        # identity digest + the rng counter — with the per-step rng/comm
        # digests already on the flight ring, a failed window is fully
        # re-drivable.  TDX_SESSION_RECORD=0 makes this a no-op.
        self.recorder = resolve_record(record)
        self._bb_on = bool(getattr(self.recorder, "enabled", False))
        if self._bb_on:
            self.recorder.record(
                "trainer",
                step_type=type(step).__name__,
                tokens_per_batch=tokens_per_batch,
                start_step=self.global_step,
                rng_counter=self._rng_counter(),
            )
            if self.recorder.path:
                # crash/flight dumps name the black box they pair with
                self.flight.session_path = self.recorder.path
        # collective-traffic audit: the FIRST call of the step program
        # traces under this profile (obs.comm — trace-time accounting),
        # so after one step it holds the per-step analytic comm plan
        self.comm_profile = CommProfile()
        # MFU: tokens/sec * flops/token / peak; only reported when the
        # caller supplies the model's flops_per_token and a peak is known
        # (given here, or on record for the running device kind in
        # utils.benchmarks.PEAK_BF16_FLOPS)
        self.flops_per_token = flops_per_token
        self.peak_flops = peak_flops
        # goodput accounting (productive vs compile/checkpoint/rollback
        # wall time), all host-measured at the same boundaries that
        # already block on the device
        self._t_productive = 0.0
        self._t_compile = 0.0
        self._t_checkpoint = 0.0
        self._t_rollback = 0.0
        self._t_reshard = 0.0  # elastic migration time (disjoint from rollback)
        # only the FIRST fit()'s first step carries the jit compile; a
        # later fit on the same (warm) step program must not book its
        # first window as compile overhead or goodput reads low
        self._warmed = False
        # live telemetry the Prometheus collector projects
        # (metrics_collector); loss/steps_per_sec update at log
        # boundaries — where they are realized anyway, zero extra syncs
        self.metrics: dict = {
            "steps_total": 0,
            "tokens_total": 0,
            "checkpoints_total": 0,
            "failures_total": 0,
            "loss": None,
            "steps_per_sec": None,
            "tokens_per_sec": None,
            "mfu": None,
            "mfu_xla": None,
            "flop_attribution": None,
            "goodput": None,
        }
        # cost observatory (obs.cost): the step program's CostCard,
        # captured once at the warmup boundary (one extra compile,
        # booked as compile overhead).  mfu_xla then reports per-window
        # MFU from XLA-COUNTED step FLOPs alongside the analytic `mfu`,
        # and flop_attribution is their ratio (the cost-model
        # validation check) — per-span numbers, not one end-of-run one.
        from .obs.cost import force_disabled as _cost_force_disabled

        self._want_cost_card = bool(cost_card) and not _cost_force_disabled()
        self.cost_card = None
        # numerics observatory (obs.numerics): when the step fuses
        # digests (ShardedTrainStep/GSPMDTrainStep numerics=... /
        # TDX_NUMERICS), they are harvested HERE, at the log boundary's
        # existing block_until_ready — the arrays are already resident,
        # so the device_get is a copy, not a new sync.  The book feeds
        # nonfinite provenance into failure/rollback flight records,
        # Perfetto counter tracks, and numerics_collector().
        from .obs.numerics import NumericsBook

        self.numerics_book = NumericsBook()
        # dispatch-stall watchdog (obs.watchdog): armed around every
        # step dispatch and log-boundary device sync — a wedged step
        # dumps the flight ring naming "trainer/step" + its cost card
        self.watchdog = None
        if stall_timeout_s is not None:
            from .obs.cost import default_book
            from .obs.watchdog import DispatchWatchdog

            self.watchdog = DispatchWatchdog(
                stall_timeout_s, flight=self.flight, book=default_book()
            )

    # -- checkpoint --------------------------------------------------------

    def save(self, path: Optional[str] = None) -> str:
        path = path or os.path.join(
            self.checkpoint_dir or ".", f"step_{self.global_step}"
        )
        t0 = time.time()
        with get_tracer().span(
            "trainer/checkpoint", cat="trainer", step=self.global_step
        ):
            save_checkpoint(
                path,
                {
                    "params": self.params,
                    "opt_state": self.opt_state,
                    "global_step": self.global_step,
                },
            )
        self._last_checkpoint = path
        self._t_checkpoint += time.time() - t0
        self.metrics["checkpoints_total"] += 1
        self.flight.record(
            "checkpoint", step=self.global_step, path=path,
            seconds=round(time.time() - t0, 3),
        )
        return path

    def restore(self, path: str) -> None:
        """Restore params/opt_state/step STREAMED into the shardings the
        current (template) state carries — each array lands directly in its
        mesh layout, with no replicated host copy in between (the sharded
        ``map_location`` analog)."""
        template = {
            "params": self.params,
            "opt_state": self.opt_state,
            "global_step": 0,
        }
        # like= rebuilds the optimizer NamedTuples around the
        # already-placed leaves (orbax returns plain nests)
        out = restore_checkpoint(
            path, like=template, shardings_from=template
        )
        self.params = out["params"]
        self.opt_state = out["opt_state"]
        self.global_step = int(out["global_step"])

    # -- elastic resharding ------------------------------------------------

    @staticmethod
    def _shrunk_mesh(mesh, n_lost: int):
        """The surviving mesh after losing the LAST ``n_lost`` devices of
        ``mesh``'s flat device order (the injection contract — a real
        loss would pass the survivor mesh to :meth:`reshard` directly).
        The shrink factor is absorbed by the outermost axis that divides
        it, so ('dp','fsdp')=(2,4) losing a replica becomes (1,4) and a
        flat fsdp=8 mesh becomes fsdp=4."""
        import numpy as np
        from jax.sharding import Mesh

        from .utils.failure import StepFailure

        devices = list(np.asarray(mesh.devices).flat)
        n_surv = len(devices) - int(n_lost)
        if n_surv < 1 or len(devices) % n_surv != 0:
            raise StepFailure(
                "device_loss",
                f"cannot shrink a {len(devices)}-device mesh to "
                f"{n_surv} survivors (need a divisor)",
            )
        factor = len(devices) // n_surv
        shape = {ax: int(mesh.shape[ax]) for ax in mesh.axis_names}
        for ax in shape:
            if shape[ax] % factor == 0:
                shape[ax] //= factor
                break
        else:
            raise StepFailure(
                "device_loss",
                f"no mesh axis of {dict(mesh.shape)} divides the shrink "
                f"factor {factor}",
            )
        arr = np.asarray(devices[:n_surv]).reshape(tuple(shape.values()))
        return Mesh(arr, tuple(shape))

    def reshard(self, failure: Any = None, *, mesh: Any = None) -> str:
        """Elastic recovery: move params + optimizer state onto a shrunk
        mesh and re-jit the step with the new shardings (ROADMAP item 3;
        the ``on_failure="reshard"`` leg of the failure policy).

        The target ``mesh`` defaults to :meth:`_shrunk_mesh` of the
        step's current mesh by ``failure.n_lost`` devices.  State moves
        via :func:`~torchdistx_tpu.parallel.reshard.reshard` when the
        survivors still hold a full copy of every leaf, else via the
        checkpoint bounce (save on A, ``restore_checkpoint`` straight
        into the B shardings).  Either way the migration's collective
        footprint is booked into ``self.comm_profile`` (the closed-form
        arXiv:2112.01075 pricing), its wall time into the ``_t_reshard``
        goodput bucket, and the flight recorder gets
        ``reshard_start``/``reshard_done`` naming both mesh shapes.
        Returns the migration mode used: ``"live"`` or ``"checkpoint"``.
        """
        import copy
        import dataclasses

        from .obs.comm import comm_audit as _audit
        from .parallel.fsdp import optimizer_state_shardings
        from .parallel.reshard import (
            can_reshard_live,
            reshard as _reshard,
            reshard_via_checkpoint,
        )
        from .utils.failure import StepFailure

        old_mesh = getattr(self.step, "mesh", None)
        old_plan = getattr(self.step, "plan", None)
        if old_mesh is None or (
            old_plan is None and not hasattr(self.step, "param_sharding")
        ):
            raise StepFailure(
                getattr(failure, "kind", "device_loss"),
                f"{failure} (and the step carries no mesh/plan to reshard)",
            )
        if mesh is None:
            mesh = self._shrunk_mesh(
                old_mesh, getattr(failure, "n_lost", None) or 1
            )
        mesh_from = {ax: int(old_mesh.shape[ax]) for ax in old_mesh.axis_names}
        mesh_to = {ax: int(mesh.shape[ax]) for ax in mesh.axis_names}
        t0 = time.time()
        self.flight.record(
            "reshard_start",
            step=self.global_step,
            mesh_from=mesh_from,
            mesh_to=mesh_to,
        )
        # fresh step object on the new mesh: _jitted resets, so the next
        # call re-builds (and re-jits) with the new out_shardings.  A
        # plan-carrying step keeps ONE source of sharding truth: the
        # same rules over the shrunk mesh (plan.with_mesh), from which
        # both param and optimizer-slot targets re-derive below.
        new_plan = old_plan.with_mesh(mesh) if old_plan is not None else None
        if dataclasses.is_dataclass(self.step):
            replace_kw = {"mesh": mesh}
            if new_plan is not None and any(
                f.name == "plan" for f in dataclasses.fields(self.step)
            ):
                replace_kw["plan"] = new_plan
            new_step = dataclasses.replace(self.step, **replace_kw)
        else:
            new_step = copy.copy(self.step)
            new_step.mesh = mesh
            if hasattr(new_step, "plan"):
                new_step.plan = new_plan
            if hasattr(new_step, "_jitted"):
                new_step._jitted = None
        if new_plan is not None:
            params_sh = new_plan.param_shardings(self.params)

            def opt_shardings(opt_state, params):
                return new_plan.optimizer_state_shardings(opt_state, params)

        else:
            params_sh = new_step.param_sharding(self.params)

            def opt_shardings(opt_state, params):
                return optimizer_state_shardings(opt_state, params, mesh)

        live = can_reshard_live(
            {"params": self.params, "opt_state": self.opt_state}, mesh
        )
        migration = CommProfile()
        with _audit(self.comm_profile), _audit(migration):
            if live:
                self.params = _reshard(self.params, params_sh)
                opt_sh = opt_shardings(self.opt_state, self.params)
                self.opt_state = _reshard(self.opt_state, opt_sh)
            else:
                base = os.path.join(
                    self.checkpoint_dir or ".",
                    f"reshard_{self.global_step}",
                )
                self.params = reshard_via_checkpoint(
                    self.params, base + "_params", params_sh
                )
                opt_sh = opt_shardings(self.opt_state, self.params)
                self.opt_state = reshard_via_checkpoint(
                    self.opt_state, base + "_opt", opt_sh
                )
        self.step = new_step
        dt = time.time() - t0
        self._t_reshard += dt
        mode = "live" if live else "checkpoint"
        self.flight.record(
            "reshard_done",
            step=self.global_step,
            mesh_from=mesh_from,
            mesh_to=mesh_to,
            mode=mode,
            wire_bytes=int(migration.wire_bytes()),
            seconds=round(dt, 3),
        )
        return mode

    # -- loop --------------------------------------------------------------

    def fit(
        self,
        batches: Iterable[Any],
        num_steps: Optional[int] = None,
    ) -> dict:
        """Run up to ``num_steps`` (or the iterable's length).  Returns final
        metrics.

        Telemetry contract: every log boundary, checkpoint, and failure
        lands in the flight recorder; an exception (including a
        ``StepFailure`` escaping under ``on_failure="raise"``) dumps the
        ring to JSONL before propagating, and a HANDLED NaN/deadline
        failure dumps too — the rollback evidence must exist even when
        the run survives (``self.last_flight_dump``).
        """
        self.flight.record(
            "fit_start", step=self.global_step, num_steps=num_steps,
            rng_counter=self._rng_counter(),
        )
        try:
            return self._fit(batches, num_steps)
        except BaseException as e:
            self.flight.record(
                "exception", step=self.global_step,
                error=f"{type(e).__name__}: {e}"[:300],
                last_checkpoint=self._last_checkpoint,
            )
            self._safe_dump(f"exception:{type(e).__name__}")
            raise

    def _safe_dump(self, reason: str) -> Optional[str]:
        """Write the crash dump without letting telemetry I/O (full or
        read-only TDX_FLIGHT_DIR) turn a survivable incident — or the
        original exception — into a telemetry crash."""
        try:
            self.last_flight_dump = self.flight.dump(reason=reason)
        except Exception:
            pass
        return self.last_flight_dump

    @staticmethod
    def _rng_counter() -> int:
        from .utils.rng import _state

        return int(_state.counter)

    def _watch(self, name: str):
        """Stall-watchdog guard for one device-blocking region (no-op
        context without a watchdog)."""
        import contextlib

        if self.watchdog is None:
            return contextlib.nullcontext()
        return self.watchdog.arm(name)

    def _capture_cost_card(self, batch) -> None:
        """Capture the step program's CostCard at the warmup boundary
        (obs.cost: the one lower/compile/cost_analysis dance, booked as
        compile overhead by the caller).  Best-effort: a step that
        cannot be re-lowered (exotic callables) just leaves
        ``cost_card`` None — the probe must never fail training."""
        if not self._want_cost_card or self.cost_card is not None:
            return
        self._want_cost_card = False  # one attempt, success or not
        try:
            import warnings

            from .obs.cost import compute_cost_card, default_book

            analytic = (
                self.flops_per_token * self.tokens_per_batch
                if self.flops_per_token and self.tokens_per_batch
                else None
            )
            with warnings.catch_warnings():
                # a step wrapper's inner jit may carry donate_argnums,
                # which the outer lowering jit ignores with a warning
                warnings.simplefilter("ignore")
                self.cost_card = compute_cost_card(
                    self.step,
                    self.params,
                    self.opt_state,
                    batch,
                    name="trainer/step",
                    analytic_flops=analytic,
                    book=default_book(),
                )
        except Exception:
            self.cost_card = None

    def _harvest_numerics(self) -> None:
        """Fold the step's fused digests (if any) into the numerics book.

        Called only at log boundaries, immediately after the existing
        ``block_until_ready(loss)`` — the digest arrays rode the same
        program as the loss, so they are already materialized and the
        ``device_get`` here is a host copy, never a new device sync or
        dispatch (the ISSUE 19 zero-sync contract)."""
        digs = getattr(self.step, "last_digests", None)
        if digs is None:
            return
        try:
            self.numerics_book.update_tree(
                jax.device_get(digs), step=self.global_step
            )
            self.numerics_book.emit_counter_tracks(get_tracer())
        except Exception:
            # telemetry must never kill the loop (same discipline as
            # _safe_dump); a malformed digest just goes unharvested
            pass

    def _update_derived_metrics(self) -> None:
        """goodput / tokens-per-sec / mfu gauges from the accumulated
        wall-time split; cheap, host-only."""
        sps = self.metrics["steps_per_sec"]
        peak = self.peak_flops
        if peak is None:
            # the running chip's peak, or no utilization at all: a device
            # kind without a peak on record (the CPU mesh) reports none
            from .utils.benchmarks import PEAK_BF16_FLOPS

            peak = PEAK_BF16_FLOPS.get(jax.devices()[0].device_kind)
        if sps and self.tokens_per_batch:
            tps = sps * self.tokens_per_batch
            self.metrics["tokens_per_sec"] = tps
            if self.flops_per_token and peak:
                self.metrics["mfu"] = tps * self.flops_per_token / peak
        card = self.cost_card
        if card is not None and card.flops and sps and peak:
            # the XLA-counted sibling of `mfu`: per-window measured
            # throughput against what the compiler actually built, not
            # the paper formula — and their ratio as the cost-model
            # attribution check (obs.cost.CostCard.flop_attribution)
            self.metrics["mfu_xla"] = sps * card.flops / peak
            self.metrics["flop_attribution"] = card.flop_attribution
        overhead = (
            self._t_compile + self._t_checkpoint + self._t_rollback
            + self._t_reshard
        )
        if self._t_productive + overhead > 0:
            self.metrics["goodput"] = self._t_productive / (
                self._t_productive + overhead
            )

    def _fit(
        self,
        batches: Iterable[Any],
        num_steps: Optional[int] = None,
    ) -> dict:
        t_window = time.time()
        window_steps = 0
        warmup_pending = not self._warmed  # first-ever step carries compile
        t_warm0 = time.time()
        loss = None  # device array; only realized at log boundaries / return
        it = iter(batches)
        while True:
            # check the budget BEFORE drawing a batch, so a bounded fit
            # neither consumes nor transfers a batch it will not train on
            if num_steps is not None and self.global_step >= num_steps:
                break
            try:
                # where an input-bound run waits (or a feed that holds the
                # host a fixed number of steps ahead of the device)
                with get_tracer().span("trainer/next_batch", cat="trainer"):
                    batch = next(it)
            except StopIteration:
                break
            if self._bb_on:
                # batch identity + rng counter per step: the recording
                # half of bit-exact window replay (flight's per-step
                # rng/comm digests are the verification half)
                self._last_batch_digest = batch_digest(batch)
                self.recorder.record(
                    "train_step",
                    step=self.global_step,
                    rng_counter=self._rng_counter(),
                    batch=self._last_batch_digest,
                )
            # a span per step (obs.trace: a step annotation in any
            # profile, a host event when the tracer is enabled); the
            # dispatch is async, so the span measures host-side submit
            # time, not device step time — device time shows under
            # trainer/sync at the log boundaries' block_until_ready
            # the comm audit only sees Python-level collectives at TRACE
            # time, so this is free after the first (compiling) call and
            # self.comm_profile ends up holding the per-step comm plan
            with get_tracer().span(
                "trainer/step", cat="trainer", step_num=self.global_step
            ), comm_audit(self.comm_profile), self._watch("trainer/step"):
                self.params, self.opt_state, loss = self.step(
                    self.params, self.opt_state, batch
                )
            self.global_step += 1
            window_steps += 1
            self.metrics["steps_total"] += 1
            if self.tokens_per_batch:
                self.metrics["tokens_total"] += self.tokens_per_batch

            if warmup_pending:
                # exclude the first step's jit compile from throughput
                # windows: wait for it, then restart the clock
                with get_tracer().span(
                    "trainer/sync", cat="trainer"
                ), self._watch("trainer/warmup_sync"):
                    jax.block_until_ready(loss)
                # the cost observatory's card (one extra compile) rides
                # the same warmup boundary, booked as compile overhead
                self._capture_cost_card(batch)
                self._t_compile += time.time() - t_warm0
                self.flight.record(
                    "warmup",
                    step=self.global_step,
                    seconds=round(time.time() - t_warm0, 3),
                    comm=self.comm_profile.digest(),
                )
                t_window = time.time()
                window_steps = 0
                warmup_pending = False
                self._warmed = True

            # window_steps == 0 right after the warmup reset (log_every=1):
            # skip that boundary instead of logging 0.0 steps/sec
            if self.global_step % self.log_every == 0 and window_steps > 0:
                with get_tracer().span(
                    "trainer/sync", cat="trainer"
                ), self._watch("trainer/step_sync"):
                    jax.block_until_ready(loss)
                dt = time.time() - t_window
                last_loss = float(loss)
                self._harvest_numerics()
                if self.failure_detector is not None:
                    from .utils.failure import StepFailure, apply_failure_policy

                    try:
                        if hasattr(self.failure_detector, "check_devices"):
                            self.failure_detector.check_devices(
                                self.global_step
                            )
                        self.failure_detector.check_loss(
                            self.global_step, last_loss
                        )
                        self.failure_detector.check_window(
                            self.global_step, dt, window_steps
                        )
                    except StepFailure as failure:
                        self.metrics["failures_total"] += 1
                        get_tracer().instant(
                            "trainer/failure",
                            cat="trainer",
                            kind=failure.kind,
                            step=self.global_step,
                        )
                        failed_step = self.global_step  # before any rollback
                        self.flight.record(
                            "failure",
                            step=failed_step,
                            failure_kind=failure.kind,
                            loss=last_loss,
                            last_checkpoint=self._last_checkpoint,
                            # numerics provenance: the EARLIEST tap site
                            # (program order) whose nonfinite count went
                            # positive — names the layer a NaN was born
                            # in, not just the loss that surfaced it
                            nonfinite_site=(
                                self.numerics_book.first_nonfinite_site()
                            ),
                        )
                        t_rb = time.time()
                        rs0 = self._t_reshard
                        # "raise" propagates: _fit's wrapper records the
                        # exception and dumps the ring before re-raising
                        action = apply_failure_policy(
                            self, failure, self.on_failure
                        )
                        # reshard() books its own time into _t_reshard;
                        # keep the goodput buckets disjoint
                        self._t_rollback += max(
                            0.0,
                            time.time() - t_rb - (self._t_reshard - rs0),
                        )
                        self.flight.record(
                            "rollback",
                            step=failed_step,
                            action=action,
                            restored_step=self.global_step,
                            checkpoint=self._last_checkpoint,
                            seconds=round(time.time() - t_rb, 3),
                            nonfinite_site=(
                                self.numerics_book.first_nonfinite_site()
                            ),
                        )
                        # the dump IS the incident artifact: write it even
                        # though the run continues (ISSUE 5 crash-path
                        # contract — the last entries show the rollback)
                        self._safe_dump(f"failure:{failure.kind}")
                        self.log_fn(
                            {
                                "step": failed_step,
                                "failure": failure.kind,
                                "action": action,
                                "resumed_from": self.global_step,
                            }
                        )
                        t_window = time.time()
                        window_steps = 0
                        continue
                metrics = {
                    "step": self.global_step,
                    "loss": round(last_loss, 6),
                    "steps_per_sec": round(window_steps / dt, 3),
                }
                self.metrics["loss"] = last_loss
                self.metrics["steps_per_sec"] = window_steps / dt
                self._t_productive += dt
                self._update_derived_metrics()
                if self.tokens_per_batch:
                    metrics["tokens_per_sec"] = round(
                        self.tokens_per_batch * window_steps / dt, 1
                    )
                self._history.append(last_loss)
                self.flight.record(
                    "step",
                    step=self.global_step,
                    loss=last_loss,
                    steps_per_sec=round(window_steps / dt, 3),
                    window_s=round(dt, 4),
                    rng_counter=self._rng_counter(),
                    comm=self.comm_profile.digest(),
                    last_checkpoint=self._last_checkpoint,
                    batch=getattr(self, "_last_batch_digest", None),
                )
                self.log_fn(metrics)
                t_window = time.time()
                window_steps = 0

            if (
                self.checkpoint_dir
                and self.global_step % self.checkpoint_every == 0
            ):
                # health-gate: never let poisoned state become the rollback
                # target of on_failure="restore"
                healthy = True
                if self.failure_detector is not None and loss is not None:
                    jax.block_until_ready(loss)
                    import math as _math

                    if not _math.isfinite(float(loss)):
                        healthy = False
                        self.log_fn(
                            {
                                "step": self.global_step,
                                "checkpoint": "skipped_nonfinite_loss",
                            }
                        )
                if healthy:
                    self.save()

        self._update_derived_metrics()
        self.flight.record(
            "fit_end",
            step=self.global_step,
            loss=float(loss) if loss is not None else None,
            goodput=self.metrics["goodput"],
            rng_counter=self._rng_counter(),
        )
        return {
            "step": self.global_step,
            "loss": float(loss) if loss is not None else float("nan"),
            "goodput": self.metrics["goodput"],
        }

    # -- observability -----------------------------------------------------

    def metrics_collector(self, prefix: str = "tdx_train"):
        """An ``obs.metrics`` collector over this trainer's live metrics
        (``registry.register_collector(t.metrics_collector(), obj=t)``):
        ``*_total`` counters for steps/tokens/checkpoints/failures,
        ``loss`` / ``steps_per_sec`` / ``tokens_per_sec`` / ``mfu`` /
        ``goodput`` / ``global_step`` gauges from the latest log
        boundary, and — when a :class:`~torchdistx_tpu.utils.failure.
        FailureDetector` is attached — its live degradation counters
        (``consecutive_nonfinite``, per-kind ``failure_events_total``)
        so a run that is *about* to die is scrapeable before it does."""
        import weakref

        from .obs.metrics import MetricFamily

        ref = weakref.ref(self)  # don't pin the trainer in a registry

        def collect():
            self = ref()
            if self is None:
                return []
            m = self.metrics
            fams = []
            for name in (
                "steps_total",
                "tokens_total",
                "checkpoints_total",
                "failures_total",
            ):
                fams.append(
                    MetricFamily(f"{prefix}_{name}", "counter").add(m[name])
                )
            fams.append(
                MetricFamily(f"{prefix}_global_step", "gauge").add(
                    self.global_step
                )
            )
            for name in (
                "loss",
                "steps_per_sec",
                "tokens_per_sec",
                "mfu",
                "mfu_xla",
                "flop_attribution",
                "goodput",
            ):
                if m[name] is not None:
                    fams.append(
                        MetricFamily(f"{prefix}_{name}", "gauge").add(
                            m[name]
                        )
                    )
            book = self.numerics_book
            if book is not None and book.harvests:
                fams.extend(book.collector(prefix=f"{prefix}_numerics")())
            det = self.failure_detector
            if det is not None:
                fams.append(
                    MetricFamily(
                        f"{prefix}_consecutive_nonfinite", "gauge"
                    ).add(det.consecutive_nonfinite)
                )
                ev = MetricFamily(
                    f"{prefix}_failure_events_total",
                    "counter",
                    "detector-observed failure events by kind (incl. "
                    "tolerated ones that have not tripped the policy)",
                )
                counts = det.counts_by_kind()
                for kind in sorted(counts):
                    ev.add(counts[kind], kind=kind)
                if not counts:
                    ev.add(0.0)
                fams.append(ev)
            return fams

        return collect

"""Driver kind ``train_steps``: ``deferred_init`` -> ``materialize_module``
under ``fsdp_shard_rule`` on a mesh of the cell's chips -> ``Trainer.fit``
over ``ShardedTrainStep``, a fresh batch every step from a host feed.

One object -- the trainer with its compiled step and its state -- is
built in set-up, driven from the seed through its first steps (whose
losses, first gradient and parameter change the reference follows), then
handed to the window.  The feed is the window's own: it stops at the
deadline and keeps the host at most two steps ahead of the device, so
the window ends where the clock says."""

from __future__ import annotations

import collections
import gc
import statistics
import time

import numpy as np

from harness import check, reference, traffic


class StepProbe:
    """Passes a step through untouched and keeps each loss (a device
    scalar, not fetched): the benchmark's span around the step."""

    def __init__(self, step):
        self._step = step
        self.losses = collections.deque(maxlen=64)

    def __getattr__(self, name):
        return getattr(self._step, name)

    def __call__(self, params, opt_state, batch):
        import jax

        out = self._step(params, opt_state, batch)
        if not isinstance(out[2], jax.core.Tracer):  # the cost card traces us
            self.losses.append(out[2])
        return out


class Driver:
    kind = "train_steps"

    def __init__(self, ctx):
        self.ctx = ctx
        self.mix = ctx.cell.traffic
        self.cfg = ctx.cell.config
        # everything that depends on the architecture comes from the
        # configuration's family, never from a module named here
        self.family = ctx.family("reference.TrainReference",
                                 "reference.sample_leaves",
                                 "counts.train_flops_per_token")
        self.arch = self.family.reference.Arch.from_config(self.cfg)
        self.plan = self.family.reference.leaf_plan(self.arch)
        self.batch = int(self.mix["batch"])
        self.seq = int(self.mix["seq"])
        self.first_steps = 3
        self.stamps = []
        self.deadline = None

    # -- the feed: the window's own -----------------------------------------

    def _feed(self):
        import jax

        while True:
            losses = self.probe.losses
            if len(losses) >= 2:
                jax.block_until_ready(losses[-2])
            now = time.monotonic()
            if self.deadline is not None and now >= self.deadline:
                return
            self.ctx.tick(now)
            self.stamps.append(now)
            yield traffic.train_batch(self.arch.vocab_size, self.batch,
                                      self.seq, self.ctx.seed, self.fed)
            self.fed += 1

    # -- set-up ---------------------------------------------------------------

    def setup(self):
        ctx = self.ctx
        with ctx.span("import"):
            import jax
            import torchdistx_tpu as tdx
            from torchdistx_tpu.nn import functional
            from torchdistx_tpu.nn.module import functional_call
            from torchdistx_tpu.optimizers import anyprecision_adamw
            from torchdistx_tpu.parallel import (ShardedTrainStep, create_mesh,
                                                 fsdp_shard_rule)
            from torchdistx_tpu.trainer import Trainer
        mesh = create_mesh({"fsdp": ctx.cell.chips},
                           devices=jax.devices()[: ctx.cell.chips])
        with ctx.span("materialize"):
            tdx.manual_seed(reference.seed31(ctx.seed))
            model = tdx.deferred_init(self.family.constructor(self.cfg))
            tdx.materialize_module(model, sharding_rule=fsdp_shard_rule(mesh))
            params = dict(model.named_parameters())
            jax.block_until_ready(list(params.values()))
        opt = self.mix["optimizer"]
        if opt["name"] != "anyprecision_adamw":
            raise ValueError(f"optimizer {opt['name']!r} is not known here")
        self.adamw = reference.AdamW(lr=float(opt["lr"]))

        def loss_fn(p, b):
            tokens, labels = b
            return functional.cross_entropy(
                functional_call(model, p, (tokens,)), labels)

        step = ShardedTrainStep(loss_fn, anyprecision_adamw(self.adamw.lr),
                                mesh, shard_axis="fsdp")
        self.probe = StepProbe(step)
        self.fed = 0
        with ctx.span("build_trainer"):
            self.trainer = Trainer(
                self.probe, params, tokens_per_batch=self.batch * self.seq,
                log_fn=ctx.log)
        feed = self._feed()
        self.feed = feed
        tr = self.trainer
        with ctx.span("weights_check"):
            self.weights_differ = reference.weights_differ(
                self.arch, self.plan, ctx.seed, tr.params)
        # the first steps, through the window's own call and feed
        self.prog_loss, self.prog_grad, self.prog_change = [], {}, {}
        for k in range(1, self.first_steps + 1):
            with ctx.span("first_step" if k == 1 else "steps_2_3"):
                tr.fit(feed, num_steps=k)
                self.prog_loss.append(self.probe.losses[-1])
            with ctx.span("first_steps_readings"):
                if k == 1:
                    # the first gradient as the optimizer got it: its
                    # first moment after one step is (1 - b1) times it
                    m = dict(_find_state(tr.opt_state).exp_avg)
                    self.prog_grad = reference.tree_norms(m)
                    self.prog_grad_sample = {
                        n: np.asarray(m[n], np.float32) / (1.0 - self.adamw.b1)
                        for n in self.family.reference.sample_leaves(self.arch)}
                    del m
        with ctx.span("first_steps_readings"):
            self.prog_change = reference.change_norm_against_seed(
                self.arch, self.plan, ctx.seed, tr.params)
            self.prog_loss = [float(x) for x in self.prog_loss]
            self.prog_grad = {n: float(v) / (1.0 - self.adamw.b1)
                              for n, v in self.prog_grad.items()}
            self.prog_change = {n: float(v) for n, v in self.prog_change.items()}
        with ctx.span("warm_up"):
            # past the donated-carry recompile: until a step compiles nothing
            for _ in range(4):
                before = ctx.compiles.total
                tr.fit(feed, num_steps=tr.global_step + 1)
                jax.block_until_ready(tr.params)
                if ctx.compiles.total == before:
                    break

    # -- the measured window ------------------------------------------------

    def window(self, seconds: float):
        import jax

        tr = self.trainer
        self.stamps.clear()
        start_step = tr.global_step
        t0 = time.monotonic()
        self.deadline = t0 + seconds
        self.ctx.window_opened(t0)
        tr.fit(self.feed)
        jax.block_until_ready(tr.params)
        t1 = time.monotonic()
        steps = tr.global_step - start_step
        self.window_s = t1 - t0
        self.steps = steps
        self.tokens = steps * self.batch * self.seq
        gaps = np.diff(self.stamps)
        self.ctx.counters.update({
            "train.steps": steps,
            "train.tokens": self.tokens,
            "train.window_s": self.window_s,
            "train.step_s_p50": float(statistics.median(gaps)) if len(gaps) else None,
            "train.flops_per_token": self.family.counts.train_flops_per_token(
                self.cfg, self.seq),
            "train.batch": self.batch, "train.seq": self.seq,
        })
        return {
            "attempted": steps, "failed": 0,
            "end_to_end": {"train_tokens_per_s": self.tokens / self.window_s},
        }

    def free(self):
        self.trainer = self.probe = self.feed = None
        gc.collect()

    # -- correct ----------------------------------------------------------------

    def reference_readings(self, precision="f32", rows=None):
        """The reference over the first steps: losses, first gradient
        norms, change norms.  (Also what the control and the planted
        faults are read with.)"""
        ref = self.family.reference.TrainReference(
            self.arch, self.ctx.seed, self.adamw, precision=precision, rows=rows)
        ref.keep = self.family.reference.sample_leaves(self.arch)
        losses, grad = [], {}
        for k in range(self.first_steps):
            tokens, labels = traffic.train_batch(
                self.arch.vocab_size, self.batch, self.seq, self.ctx.seed, k)
            loss, norms = ref.step(tokens, labels)
            losses.append(loss)
            if k == 0:
                grad = norms
        change = ref.change_norms()
        return ([float(x) for x in losses],
                {n: float(v) for n, v in grad.items()},
                {n: float(v) for n, v in change.items()},
                dict(ref.kept))

    def compare(self, verdict, losses, grad, change, sample, ref):
        ref_loss, ref_grad, ref_change, ref_sample = ref
        lim = self.ctx.cell.limits
        for k in range(self.first_steps):
            verdict.show(f"loss_gap_step{k + 1}",
                         check.rel_gap(losses[k], ref_loss[k]),
                         f"{losses[k]:.6f} vs {ref_loss[k]:.6f}")
        gap, leaf = check.worst_leaf_gap(grad, ref_grad)
        verdict.add("grad_norm_gap", gap, lim["grad_norm_gap"], leaf)
        diffs = {n: float(reference.diff_rel(sample[n], ref_sample[n]))
                 for n in ref_sample}
        leaf = max(diffs, key=diffs.get)
        verdict.add("grad_diff", diffs[leaf], lim["grad_diff"], leaf)
        skip = check.small_gradient_leaves(ref_grad)
        gap, leaf = check.worst_leaf_gap(change, ref_change, skip)
        verdict.add("change_norm_gap", gap, lim["change_norm_gap"], leaf)

    def check(self, verdict):
        verdict.add("weights_differ", self.weights_differ, 0,
                    "leaves not bit for bit what the seed's rule makes")
        ref = self.reference_readings()
        self.compare(verdict, self.prog_loss, self.prog_grad,
                     self.prog_change, self.prog_grad_sample, ref)


def _find_state(opt_state):
    """The AnyPrecisionAdamW state inside whatever wraps it."""
    import jax

    found = [x for x in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "exp_avg")) if hasattr(x, "exp_avg")]
    if len(found) != 1:
        raise RuntimeError("no single optimizer state with exp_avg found")
    return found[0]

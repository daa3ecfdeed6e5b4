"""``correct`` has to come out false where it should.

Each case skips the harness's look for a chip (``--rehearsal``: the tiny
cell on the CPU) and drives the rest of a run through ``run.main``.

- the timed path broken underneath, once for each fault a cell can have:
  a step that returns its state unchanged; half of the batch left out, the
  mean taken over the rest; a token altered where it is produced (the
  exchange between chips exists in no cell yet);
- the control: the reference computed in int8 and put in the program's
  place reads over the limit of at least one compared number.

The limits here are the rehearsal cells' own, set from CPU readings at
that size (``proof/*_readings.py --rehearsal``); the benchmark's cells
carry limits read on the chip at their size.
"""

import numpy as np
import pytest

import run
from harness import check, loader


def test_sound_train_run_is_correct(drive):
    result = drive("tiny.train")
    assert result["correct"] is True, result["compared"]
    assert list(result)[-1] == "compared"
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"


def test_state_left_unchanged_is_not_correct(drive, monkeypatch):
    from torchdistx_tpu.parallel.fsdp import ShardedTrainStep

    real = ShardedTrainStep.__call__

    def broken(self, params, opt_state, batch):
        import jax
        import jax.numpy as jnp

        copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
        _, _, loss = real(self, copy(params), copy(opt_state), batch)
        return params, opt_state, loss  # the state as it came

    monkeypatch.setattr(ShardedTrainStep, "__call__", broken)
    result = drive("tiny.train")
    assert result["correct"] is False
    c = result["compared"]
    assert c["change_norm_gap"]["value"] == pytest.approx(1.0, abs=1e-6)
    assert c["grad_norm_gap"]["value"] > c["grad_norm_gap"]["limit"]


def test_half_of_the_batch_left_out_is_not_correct(drive, monkeypatch):
    from torchdistx_tpu.parallel.fsdp import ShardedTrainStep

    real = ShardedTrainStep.__call__

    def broken(self, params, opt_state, batch):
        half = tuple(np.concatenate([b[: len(b) // 2]] * 2) for b in batch)
        return real(self, params, opt_state, half)

    monkeypatch.setattr(ShardedTrainStep, "__call__", broken)
    result = drive("tiny.train")
    assert result["correct"] is False
    c = result["compared"]
    assert c["grad_norm_gap"]["value"] > 10 * c["grad_norm_gap"]["limit"]


def test_sound_serve_run_is_correct(drive):
    result = drive("tiny.batch4")
    assert result["correct"] is True, result["compared"]
    assert result["counts"]["serve.requests_finished"] > 0


def test_altered_token_is_not_correct(drive, monkeypatch):
    from torchdistx_tpu.serve.engine import ServeEngine

    real = ServeEngine._record_first

    def broken(self, req, tok, now):
        return real(self, req, (int(tok) + 1) % 512, now)

    monkeypatch.setattr(ServeEngine, "_record_first", broken)
    result = drive("tiny.batch4")
    assert result["correct"] is False
    c = result["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


def _driver(workload, seed):
    cell = loader.load_cell(workload, rehearsal=True)
    ctx = run.Context(cell, seed, 1.0, False)
    return loader.load_driver(cell.driver_kind).Driver(ctx)


@pytest.mark.parametrize("seed", [3, 2**31 + 4, 5])
def test_train_control_is_not_correct(seed):
    """The int8 reference in the program's place, against the float32
    reference, on three seeds."""
    from harness import reference

    drv = _driver("tiny.train", seed)
    drv.adamw = reference.AdamW(lr=float(drv.mix["optimizer"]["lr"]))
    ref = drv.reference_readings()
    control = drv.reference_readings(precision="int8")
    verdict = check.Verdict()
    drv.compare(verdict, *control, ref)
    assert verdict.correct is False
    over = {n for n, v, lim, _ in verdict.rows if v > lim}
    assert {"grad_diff", "grad_norm_gap"} <= over


@pytest.mark.parametrize("seed", [3, 2**31 + 4, 5])
def test_serve_control_is_not_correct(seed):
    """A short window of the float32 toy, then at each position of the
    served prompts and tokens the token that the reference in the next
    precision down (bfloat16 for float32) puts first: the served tokens
    stay within the limits, the control's do not."""
    from harness import reference

    drv = _driver("tiny.batch4-f32", seed)
    drv.setup()
    drv.window(2.0)
    drv.after_window()
    drv.free()
    seqs, lens = drv.sample()
    family_ref = drv.family.reference
    ref = family_ref.ServeReference(drv.arch, seed, "f32")
    control = family_ref.ServeReference(drv.arch, seed, "bf16")
    served, ctl = reference.served_gaps(ref, seqs, lens, control)
    lim = drv.ctx.cell.limits
    assert drv.weights_differ == 0
    assert max(served["max"]) <= lim["logit_gap"]
    assert sum(served["sum"]) / sum(served["tokens"]) <= lim["logit_gap_mean"]
    # the control has to fail one of the cell's numbers, not each
    assert (max(ctl["max"]) > lim["logit_gap"]
            or sum(ctl["sum"]) / sum(ctl["tokens"]) > lim["logit_gap_mean"])

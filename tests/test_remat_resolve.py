"""``ShardedTrainStep`` chooses what "auto" block checkpoints keep from
the compiled step's own memory (``nn/remat.py``).

Where the backend reports an HBM budget (``obs.memory.device_hbm_budget``,
monkeypatched here: the CPU reports none), the step compiles the program
whose blocks keep their matmul outputs and keeps it when arguments +
outputs - aliased + temps fit; else the blocks recompute their forward.
An explicit policy is never overridden, and the kept program is compiled
once, counted the way the benchmark counts compiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

import torchdistx_tpu as tdx
from torchdistx_tpu import obs
from torchdistx_tpu.models import Llama
from torchdistx_tpu.nn import functional, functional_call
from torchdistx_tpu.optimizers import anyprecision_adamw
from torchdistx_tpu.parallel import ShardedTrainStep, create_mesh
from torchdistx_tpu.trainer import Trainer

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
BIG = 1 << 40


def _budget(monkeypatch, value):
    import torchdistx_tpu.obs.memory as memory

    monkeypatch.setattr(memory, "device_hbm_budget", lambda: value)


def _step(**model_kw):
    tdx.manual_seed(0)
    model = tdx.deferred_init(
        Llama.from_name, "tiny", n_layers=1, max_seq_len=32, use_flash=False,
        **{"remat": True, **model_kw},
    )
    tdx.materialize_module(model)
    mesh = create_mesh({"fsdp": 1}, devices=jax.devices()[:1])

    def loss_fn(p, b):
        tokens, labels = b
        return functional.cross_entropy(
            functional_call(model, p, (tokens,)), labels
        )

    step = ShardedTrainStep(
        loss_fn, anyprecision_adamw(1e-3), mesh, shard_axis="fsdp"
    )
    params = step.shard_params(dict(model.named_parameters()))
    opt_state = step.init_optimizer(params)
    toks = np.random.RandomState(0).randint(0, 256, (2, 32)).astype(np.int32)
    return step, params, opt_state, (toks, np.roll(toks, -1, axis=1))


class _StepCompiles:
    """Backend compiles of the step program (``fun_name`` "jit(step)"), from
    the same ``jax.monitoring`` event the benchmark's counter reads."""

    def __init__(self):
        self.n = 0
        self.on = True
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, seconds, fun_name=None, **_kw):
        if self.on and event == COMPILE_EVENT and fun_name == "jit(step)":
            self.n += 1


def _peak_under(monkeypatch, budget, **model_kw):
    return _stepped(monkeypatch, budget, **model_kw)[0]


def _stepped(monkeypatch, budget, **model_kw):
    _budget(monkeypatch, budget)
    step, params, opt_state, batch = _step(**model_kw)
    params, opt_state, _ = step(params, opt_state, batch)
    return step, params, opt_state, batch


def _program(stepped):
    """The lowered text of the program the step runs."""
    step, params, opt_state, batch = stepped
    return step._jitted.lower(
        params, opt_state, batch, jnp.int32(0)
    ).as_text()


def test_budget_above_the_saving_peak_keeps_the_dots(monkeypatch):
    step = _peak_under(monkeypatch, BIG)
    assert step.remat_resolved == "dots"
    assert step.hbm_budget_bytes == BIG
    assert 0 < step.step_projected_peak_bytes <= BIG


def test_budget_below_the_saving_peak_recomputes(monkeypatch):
    peak = _peak_under(monkeypatch, BIG).step_projected_peak_bytes
    # the boundary: the peak itself fits, one byte less does not
    assert _peak_under(monkeypatch, peak).remat_resolved == "dots"
    step = _peak_under(monkeypatch, peak - 1)
    assert step.remat_resolved == "full"
    # what was checked is the saving program, whatever was chosen
    assert step.step_projected_peak_bytes == peak
    assert step.hbm_budget_bytes == peak - 1


def test_no_budget_recomputes_and_compiles_nothing_extra(monkeypatch):
    counter = _StepCompiles()
    step = _peak_under(monkeypatch, None)
    counter.on = False
    assert step.remat_resolved == "full"
    assert step.step_projected_peak_bytes is None
    assert step.hbm_budget_bytes is None
    assert counter.n == 1


@pytest.mark.parametrize(
    "policy,budget", [("full", BIG), ("dots", 1)], ids=["full", "dots"]
)
def test_explicit_policy_is_never_overridden(monkeypatch, policy, budget):
    stepped = _stepped(monkeypatch, budget, remat_policy=policy)
    assert stepped[0].remat_resolved == f"explicit:{policy}"
    # the program is the one named: the one "auto" resolves to where
    # the choice goes the other way from what this budget would make it
    auto = _stepped(monkeypatch, 1 if policy == "full" else BIG)
    other = _stepped(monkeypatch, budget)
    assert auto[0].remat_resolved == policy != other[0].remat_resolved
    assert _program(stepped) == _program(auto) != _program(other)


def test_a_step_without_checkpoints_reads_none(monkeypatch):
    assert _peak_under(monkeypatch, BIG, remat=False).remat_resolved == "none"


@pytest.mark.parametrize("fits", [True, False], ids=["fits", "falls_back"])
def test_one_compile_when_the_saving_program_fits(monkeypatch, fits):
    peak = _peak_under(monkeypatch, BIG).step_projected_peak_bytes
    _budget(monkeypatch, peak if fits else peak - 1)
    step, params, opt_state, batch = _step()
    counter = _StepCompiles()
    params, opt_state, _ = step(params, opt_state, batch)
    first = counter.n
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    counter.on = False
    assert step.remat_resolved == ("dots" if fits else "full")
    # the fallback compiles the recomputing program after the check
    assert first == (1 if fits else 2)
    assert counter.n == first


@pytest.mark.parametrize("refusal", ["RESOURCE_EXHAUSTED: out of HBM", "INTERNAL: other"])
def test_a_compiler_refusal_of_the_saving_program(monkeypatch, refusal):
    # the chip's compiler refuses a program that cannot fit its memory:
    # that is the fallback; any other refusal is the caller's to see
    _budget(monkeypatch, BIG)
    step, params, opt_state, batch = _step()

    def refuse(self, *a, **k):
        raise jax.errors.JaxRuntimeError(refusal)

    monkeypatch.setattr(jax.stages.Lowered, "compile", refuse)
    if refusal.startswith("INTERNAL"):
        with pytest.raises(jax.errors.JaxRuntimeError, match="INTERNAL"):
            step(params, opt_state, batch)
        return
    step(params, opt_state, batch)
    assert step.remat_resolved == "full"
    assert step.step_projected_peak_bytes is None


def test_no_resolution_on_tracers(monkeypatch):
    _budget(monkeypatch, BIG)
    step, params, opt_state, batch = _step()
    # an outer jit tracing the step (the cost card, a probe) runs the
    # recomputing program and leaves the choice to the first real call
    jax.eval_shape(lambda p, o: step(p, o, batch)[2], params, opt_state)
    assert step.remat_resolved is None
    assert step.hbm_budget_bytes is None
    step(params, opt_state, batch)
    assert step.remat_resolved == "dots"


@pytest.mark.parametrize("budget", [BIG, 1], ids=["fits", "does_not_fit"])
def test_trainer_collector_reads_the_choice(monkeypatch, budget):
    _budget(monkeypatch, budget)
    step, params, opt_state, batch = _step()
    logs = []
    tr = Trainer(step, params, opt_state, log_every=1, log_fn=logs.append,
                 cost_card=False)
    tr.fit([batch] * 2, num_steps=2)
    want = "dots" if budget == BIG else "full"
    reg = obs.MetricsRegistry()
    reg.register_collector(tr.metrics_collector(), obj=tr)
    samples = obs.parse_prometheus(reg.render())["samples"]
    assert samples[("tdx_train_remat_saves_dots", (("resolved", want),))] == (
        1.0 if want == "dots" else 0.0
    )
    assert samples[("tdx_train_step_projected_peak_bytes", ())] == float(
        step.step_projected_peak_bytes
    )
    assert samples[("tdx_train_hbm_budget_bytes", ())] == float(budget)
    # logged once, at set-up
    remat_logs = [r for r in logs if "remat" in r]
    assert remat_logs == [{
        "step": 1, "remat": want,
        "step_projected_peak_bytes": step.step_projected_peak_bytes,
        "hbm_budget_bytes": budget,
    }]


def test_no_budget_logs_nothing_and_reads_full(monkeypatch):
    _budget(monkeypatch, None)
    step, params, opt_state, batch = _step()
    logs = []
    tr = Trainer(step, params, opt_state, log_every=1, log_fn=logs.append,
                 cost_card=False)
    tr.fit([batch] * 2, num_steps=2)
    assert not [r for r in logs if "remat" in r]
    reg = obs.MetricsRegistry()
    reg.register_collector(tr.metrics_collector(), obj=tr)
    samples = obs.parse_prometheus(reg.render())["samples"]
    assert samples[("tdx_train_remat_saves_dots", (("resolved", "full"),))] == 0.0
    assert ("tdx_train_hbm_budget_bytes", ()) not in samples


def test_the_choice_leaves_the_loss_and_update_alone(monkeypatch):
    out = {}
    for budget in (BIG, 1):
        _budget(monkeypatch, budget)
        step, params, opt_state, batch = _step()
        params, _, loss = step(params, opt_state, batch)
        out[step.remat_resolved] = (float(loss), params)
    (l_dots, p_dots), (l_full, p_full) = out["dots"], out["full"]
    np.testing.assert_allclose(l_dots, l_full, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p_dots),
                    jax.tree_util.tree_leaves(p_full)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

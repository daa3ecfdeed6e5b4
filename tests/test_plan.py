"""Declarative sharding plans (parallel/plan.py).

Five pinned behaviours from the issue:
  1. rule resolution — first-match-wins precedence, no-match fallback
     (replicated, or FSDP over default_axis);
  2. plan-vs-manual bit-identity: materializing under the plan's rule
     and deriving optimizer shardings from it must reproduce the
     pre-plan manual wiring EXACTLY (placements and bits) for fsdp,
     tp=2, and dp x tp layouts on the 8-device CPU mesh;
  3. ZeRO-2: a dp-replicated model trained with plan-sharded optimizer
     state is BITWISE identical to the replicated-optimizer oracle
     (elementwise update math), while optimizer bytes/device drop to
     1/dp;
  4. closed-form wire pins: the ZeRO-2 updated-params all-gather books
     exactly ``(n-1)/n * participating_bytes`` per step into the comm
     audit, equal to ``plan.price_step`` (plan == audit == counters);
  5. loud failure: a plan overshooting a named per-device budget raises
     PlanError naming the budget at plan time, on both the
     shape-only (capacity_plan) and materialized (sharding_report)
     validation paths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import torchdistx_tpu as tdx
from torchdistx_tpu.models import Llama
from torchdistx_tpu.nn import functional, functional_call
from torchdistx_tpu.obs.comm import comm_audit
from torchdistx_tpu.parallel import (
    GSPMDTrainStep,
    PlanError,
    ShardingPlan,
    create_mesh,
    fsdp_partition_spec,
    llama_tp_plan,
    optimizer_state_shardings,
)
from torchdistx_tpu.parallel.fsdp import fsdp_shard_rule

GIB = 1024**3


def _llama_params(seed, sharding_rule=None):
    tdx.manual_seed(seed)
    model = tdx.deferred_init(Llama.from_name, "tiny")
    if sharding_rule is None:
        tdx.materialize_module(model)
    else:
        tdx.materialize_module(model, sharding_rule=sharding_rule)
    return model, dict(model.named_parameters())


def _loss_fn(model):
    def loss_fn(p, batch):
        tokens, labels = batch
        logits = functional_call(model, p, (tokens,))
        return functional.cross_entropy(logits, labels)

    return loss_fn


def _data(vocab=256, b=8, s=16, seed=0):
    # globally unique tokens: the ZeRO-2 bitwise-vs-oracle assertions
    # are about the elementwise update math being exactly shardable —
    # duplicate tokens would additionally test embedding scatter-add
    # summation order, which the partitioner is free to reassociate
    rs = np.random.RandomState(seed)
    tokens = rs.permutation(vocab)[: b * s].reshape(b, s).astype(np.int32)
    labels = rs.randint(0, vocab, (b, s)).astype(np.int32)
    return tokens, labels


class TestRuleResolution:
    def test_first_match_wins(self, mesh8):
        plan = ShardingPlan(
            mesh8,
            rules=(
                (r"\.weight$", P("fsdp", None)),
                (r"attn\..*\.weight$", P(None, "fsdp")),
            ),
        )
        # both patterns match; the FIRST rule is the plan's answer
        assert plan.spec_for("blocks.0.attn.wq.weight", (64, 64)) == P(
            "fsdp", None
        )
        # re.search, not fullmatch: substrings anywhere in the path hit
        assert plan.spec_for("deep.nesting.attn.weight", (64, 64)) == P(
            "fsdp", None
        )

    def test_no_match_falls_back_to_replicated(self, mesh8):
        plan = ShardingPlan(mesh8, rules=((r"\.weight$", P("fsdp", None)),))
        assert plan.spec_for("something.bias", (64,)) == P()
        assert plan.maybe_spec_for("something.bias", (64,)) is None

    def test_no_match_with_default_axis_fsdp_shards(self, mesh8):
        plan = ShardingPlan(mesh8, default_axis="fsdp")
        assert plan.spec_for("h", (4096, 64)) == fsdp_partition_spec(
            (4096, 64), mesh8, "fsdp", 1024
        )
        # below min_shard_elems the fallback replicates...
        assert plan.spec_for("tiny.bias", (8,)) == P()
        # ...but an EXPLICIT rule applies even to tiny tensors
        ruled = ShardingPlan(
            mesh8, rules=((r"bias$", P("fsdp")),), default_axis="fsdp"
        )
        assert ruled.spec_for("tiny.bias", (8,)) == P("fsdp")

    def test_unknown_axes_fail_loudly(self, mesh8):
        with pytest.raises(PlanError, match="default_axis"):
            ShardingPlan(mesh8, default_axis="nope")
        with pytest.raises(PlanError, match="references axis"):
            ShardingPlan(mesh8, rules=((r".", P("tp")),))
        with pytest.raises(PlanError, match="requires dp_axis"):
            ShardingPlan(mesh8, zero2=True)

    def test_with_mesh_carries_rules(self, mesh8):
        from jax.sharding import Mesh

        plan = ShardingPlan(
            mesh8, rules=((r"w", P("fsdp")),), default_axis="fsdp"
        )
        small = Mesh(np.array(jax.devices()[:4]).reshape(4), ("fsdp",))
        moved = plan.with_mesh(small)
        assert moved.rules == plan.rules
        assert moved.spec_for("w", (8, 8)) == P("fsdp")
        assert int(moved.mesh.shape["fsdp"]) == 4

    def test_with_mesh_rejects_missing_axis_eagerly(self, mesh8, mesh2x4):
        plan = ShardingPlan(mesh8, default_axis="fsdp")
        with pytest.raises(PlanError):
            plan.with_mesh(mesh2x4)


class TestPlanVsManual:
    """The plan must reproduce the manual wiring it subsumes, bit for
    bit: same placements, same materialized values, same derived
    optimizer shardings."""

    def _assert_same_shardings(self, a, b):
        fa = jax.tree_util.tree_leaves(
            a, is_leaf=lambda x: isinstance(x, NamedSharding)
        )
        fb = jax.tree_util.tree_leaves(
            b, is_leaf=lambda x: isinstance(x, NamedSharding)
        )
        assert len(fa) == len(fb)
        for sa, sb in zip(fa, fb):
            assert sa.spec == sb.spec, (sa, sb)

    def _check(self, mesh, plan, manual_rule):
        _, manual = _llama_params(0, manual_rule)
        _, planned = _llama_params(0, plan.as_rule())
        for k in manual:
            assert planned[k].sharding.spec == manual[k].sharding.spec, k
            np.testing.assert_array_equal(
                np.asarray(planned[k]), np.asarray(manual[k]), err_msg=k
            )
        tx = optax.adam(1e-3)
        state_shape = jax.eval_shape(tx.init, planned)
        self._assert_same_shardings(
            plan.optimizer_state_shardings(state_shape, planned),
            optimizer_state_shardings(state_shape, manual, mesh),
        )

    def test_fsdp(self, mesh8):
        self._check(
            mesh8,
            ShardingPlan.fsdp(mesh8),
            fsdp_shard_rule(mesh8, axis="fsdp"),
        )

    def test_tp2(self):
        from torchdistx_tpu.parallel.tp import llama_tp_rule

        mesh = create_mesh({"dp": 4, "tp": 2})
        self._check(
            mesh, llama_tp_plan(mesh, "tp"), llama_tp_rule(mesh, "tp")
        )

    def test_dp_x_tp_2d(self):
        from torchdistx_tpu.parallel.tp import llama_tp_rule

        mesh = create_mesh({"fsdp": 4, "tp": 2})
        self._check(
            mesh,
            llama_tp_plan(mesh, "tp", fsdp_axis="fsdp"),
            llama_tp_rule(mesh, "tp", fsdp_axis="fsdp"),
        )


class TestZero2:
    """Automatic ZeRO-2 weight-update sharding (arXiv:2004.13336): the
    plan replicates params over dp but shards optimizer slots + the
    update anyway, all-gathering updated params — bitwise identical to
    the replicated oracle, at 1/dp optimizer memory."""

    def _setup(self):
        mesh = create_mesh({"dp": 8})
        plan = ShardingPlan(mesh, dp_axis="dp", zero2=True, min_shard_elems=1)
        model, params = _llama_params(0, plan.as_rule())
        return mesh, plan, model, params

    def _opt_bytes_per_device(self, state):
        total = 0
        for leaf in jax.tree_util.tree_leaves(state):
            if not isinstance(leaf, jax.Array):
                continue
            shard = leaf.sharding.shard_shape(leaf.shape)
            total += int(np.prod(shard, dtype=np.int64)) * leaf.dtype.itemsize
        return total

    def test_ten_steps_bitwise_vs_replicated_oracle(self):
        mesh, plan, model, params = self._setup()
        loss_fn = _loss_fn(model)
        # momentum SGD: param-shaped slots, no scalar count leaf — the
        # 1/dp assertion below is exact.  Learning rate and momentum are
        # powers of two ON PURPOSE: the slot update is multiply-adds
        # (``m*trace + g``, ``p - lr*u``), and the compiler may contract
        # one into an FMA in the sharded program and not in the
        # replicated one (jax 0.9.0's CPU backend does, at 1e-1/0.9: a
        # 1-ulp, 4e-9 drift from step 2 on, while plain SGD stays
        # bitwise — the gradient reduction order is the same).  With an
        # exact multiply both forms round once to the same bits, so what
        # this pins is what is truly invariant: identical gradients,
        # reduction order, slot values and gathered params.
        tx = optax.sgd(0.125, momentum=0.5)
        batch = _data()

        step = GSPMDTrainStep(loss_fn, tx, mesh, batch_spec=P("dp"), plan=plan)
        state = step.init_optimizer(params)
        # plan-derived slots are dp-sharded even though params replicate
        sharded = [
            l for l in jax.tree_util.tree_leaves(state)
            if isinstance(l, jax.Array) and "dp" in str(l.sharding.spec)
        ]
        assert sharded, "no dp-sharded optimizer slot found"
        # optimizer bytes/device == 1/dp of the replicated footprint
        slot_total = sum(
            int(np.prod(l.shape, dtype=np.int64)) * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(state)
            if isinstance(l, jax.Array)
        )
        assert self._opt_bytes_per_device(state) * 8 == slot_total

        # oracle: identical step, replicated optimizer state (no plan)
        _, oparams = _llama_params(0, plan.as_rule())
        ostep = GSPMDTrainStep(loss_fn, tx, mesh, batch_spec=P("dp"))
        ostate = ostep.init_optimizer(oparams)

        for _ in range(10):
            params, state, loss = step(params, state, batch)
            oparams, ostate, oloss = ostep(oparams, ostate, batch)
        jax.block_until_ready((params, oparams))
        for k in oparams:
            np.testing.assert_array_equal(
                np.asarray(params[k]), np.asarray(oparams[k]), err_msg=k
            )
        np.testing.assert_array_equal(np.asarray(loss), np.asarray(oloss))
        # params stayed replicated (the plan's own placement for them)
        assert all(
            not str(v.sharding.spec).count("dp") for v in params.values()
        )

    def test_wire_pins_match_comm_audit_exactly(self):
        mesh, plan, model, params = self._setup()
        loss_fn = _loss_fn(model)
        tx = optax.sgd(1e-1, momentum=0.9)
        batch = _data()
        step = GSPMDTrainStep(loss_fn, tx, mesh, batch_spec=P("dp"), plan=plan)
        state = step.init_optimizer(params)

        param_bytes = sum(
            int(np.prod(v.shape, dtype=np.int64)) * v.dtype.itemsize
            for v in params.values()
        )
        # every tiny-Llama param has an 8-divisible dim, so with
        # min_shard_elems=1 ALL param bytes participate
        assert plan.zero2_participating_bytes(params) == param_bytes

        rows = plan.price_step(params)
        assert [r["kind"] for r in rows] == ["all_gather"]
        (row,) = rows
        assert row["axis"] == "dp"
        assert row["payload_bytes"] == param_bytes
        assert row["wire_bytes"] == param_bytes * 7 // 8  # (n-1)/n closed form

        k = 4
        with comm_audit() as prof:
            for _ in range(k):
                params, state, _ = step(params, state, batch)
        assert prof.ops("all_gather", "dp") == k
        assert prof.payload_bytes("all_gather", "dp") == k * param_bytes
        assert int(round(prof.wire_bytes("all_gather", "dp"))) == (
            k * plan.step_wire_bytes(params, "all_gather")
        )
        assert plan.step_wire_bytes(params) == param_bytes * 7 // 8

    def test_non_zero2_plan_prices_no_gather(self, mesh8):
        plan = ShardingPlan.replicated(mesh8)
        _, params = _llama_params(0)
        assert plan.price_step(params) == []
        assert plan.zero2_participating_bytes(params) == 0


class TestValidate:
    def test_budget_overshoot_fails_loudly_closed_form(self, mesh8):
        # 5B f32 params fully replicated: 20 GB/device > 16 GiB, priced
        # from ShapeDtypeStructs alone — nothing is allocated
        params = {
            "giant.weight": jax.ShapeDtypeStruct((50_000, 100_000), jnp.float32)
        }
        plan = ShardingPlan.replicated(mesh8)
        with pytest.raises(PlanError) as ei:
            plan.validate(
                params,
                budget_bytes_per_device=16 * GIB,
                budget_name="v5e HBM",
            )
        msg = str(ei.value)
        assert "v5e HBM" in msg  # the budget is NAMED
        assert str(16 * GIB) in msg  # ...with numbers
        assert "20000000000" in msg

    def test_sharded_plan_fits_same_budget(self, mesh8):
        params = {
            "giant.weight": jax.ShapeDtypeStruct((50_000, 100_000), jnp.float32)
        }
        doc = ShardingPlan.fsdp(mesh8).validate(
            params, budget_bytes_per_device=16 * GIB
        )
        assert doc["fits"] is True
        assert doc["components"]["params"] == 20_000_000_000 // 8

    def test_optimizer_state_counted_in_capacity(self, mesh8):
        params = {
            "giant.weight": jax.ShapeDtypeStruct((50_000, 100_000), jnp.float32)
        }
        state = jax.eval_shape(optax.adam(1e-3).init, params)
        plan = ShardingPlan.fsdp(mesh8)
        doc = plan.validate(params, optimizer_state=state)
        # adam: mu + nu sharded like the param (2x params per device),
        # plus the replicated 4-byte int32 step counter
        assert doc["components"]["optimizer_state"] == (
            2 * doc["components"]["params"] + 4
        )

    def test_materialized_mismatch_fails_loudly(self, mesh8):
        # params placed REPLICATED while the plan demands fsdp sharding
        x = jax.device_put(
            jnp.zeros((4096, 64)), NamedSharding(mesh8, P())
        )
        plan = ShardingPlan.fsdp(mesh8)
        with pytest.raises(PlanError, match="sharding_mismatch"):
            plan.validate({"w": x})

    def test_materialized_conforming_passes(self, mesh8):
        plan = ShardingPlan.fsdp(mesh8)
        x = jax.device_put(
            jnp.zeros((4096, 64)),
            NamedSharding(mesh8, plan.spec_for("w", (4096, 64))),
        )
        report = plan.validate({"w": x})
        assert report["flags"] == []


class TestServeEnginePlan:
    def test_plan_drives_params_and_kv_pool(self):
        from torchdistx_tpu.models import LlamaConfig
        from torchdistx_tpu.serve.engine import ServeEngine

        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("tp",))
        cfg = LlamaConfig(
            vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
            max_seq_len=64,
        )
        tdx.manual_seed(0)
        model = Llama(cfg)
        eng = ServeEngine(model, num_slots=2, max_len=32, mesh=mesh)
        # default plan is llama_tp_plan; params and the KV pool both
        # follow it — the kv_cache pseudo-path rule IS the pool layout
        assert isinstance(eng.plan, ShardingPlan)
        assert eng.params["blocks.0.attn.wq.weight"].sharding.spec == P(
            "tp", None
        )
        assert eng._kv_sharding.spec == eng.plan.maybe_spec_for(
            "kv_cache", ()
        )
        h = eng.submit([1, 2, 3], max_new_tokens=4)
        while not h.done():
            eng.step()
        assert len(h.result().tokens) == 4


class TestReshardToPlan:
    def test_transition_prices_then_books_identically(self, mesh8):
        from torchdistx_tpu.parallel import (
            plan_transition_wire_bytes,
            reshard_to_plan,
        )

        src = ShardingPlan.fsdp(mesh8)
        _, params = _llama_params(0, src.as_rule())
        tx = optax.sgd(1e-1, momentum=0.9)
        state = jax.jit(
            tx.init,
            out_shardings=src.optimizer_state_shardings(
                jax.eval_shape(tx.init, params), params
            ),
        )(params)

        target = ShardingPlan.replicated(mesh8)
        expected = plan_transition_wire_bytes(
            params, target, optimizer_state=state
        )
        assert expected > 0  # unsharding moves (g-1)/g of sharded bytes
        with comm_audit() as prof:
            new_params, new_state = reshard_to_plan(
                params, target, optimizer_state=state
            )
        assert int(round(prof.wire_bytes("all_gather"))) == expected
        for v in new_params.values():
            assert v.sharding.spec == P()
        for l in jax.tree_util.tree_leaves(new_state):
            if isinstance(l, jax.Array):
                assert l.sharding.spec == P()

"""Training-side observability (ISSUE 5) — the pinned invariants:

- **Analytic collective pins** (obs.comm): trace-time byte/op counts on
  the 8-device CPU mesh match the closed-form expectations for four
  legs — FSDP (gradient reduce-scatter payload == sharded parameter
  bytes, wire == (n-1)/n of it), TP (one forward all-reduce + one
  backward psum per Megatron layer), PP (1F1B exchanges ==
  2*(M + 2*(S-1))), GossipGraD (node-axis exchange of the full gradient
  bytes, one per traced branch).  A cached program's second call records
  NOTHING — the profile is per compiled program.
- **Sharding audit** (obs.memory): a deliberately replicated large
  parameter is flagged; replication the intended rule asked for is not;
  an optimizer state initialized without ``optimizer_state_shardings``
  is flagged against its sharded parameter.
- **Crash path** (obs.flight): an injected-NaN ``fit()`` writes a
  schema-valid flight dump whose last entries show the rollback
  (restored step + checkpoint path), and the streaming sink is readable
  BEFORE close (per-event flush — the ``kill -9`` contract).
- **Runtime gauges**: the default registry exposes flight depth and
  ``tdx_jit_cache_size{fn=...}`` with zero wiring.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import torchdistx_tpu as tdx
from torchdistx_tpu import nn
from torchdistx_tpu.nn import functional_call
from torchdistx_tpu.obs import comm_audit, sharding_report
from torchdistx_tpu.obs.comm import (
    CommProfile,
    record_collective,
    validate_comm_profile,
)
from torchdistx_tpu.obs.flight import FlightRecorder, validate_flight_jsonl
from torchdistx_tpu.parallel import (
    ShardedTrainStep,
    collectives,
    create_mesh,
    fsdp_shard_rule,
    optimizer_state_shardings,
)
from jax import shard_map
from torchdistx_tpu.trainer import Trainer
from torchdistx_tpu.utils.failure import FailureDetector

F32 = 4  # bytes


class MLP(nn.Module):
    def __init__(self, d=16, h=64):
        super().__init__()
        self.fc1 = nn.Linear(d, h)
        self.fc2 = nn.Linear(h, d)

    def forward(self, x):
        return self.fc2(jax.nn.relu(self.fc1(x)))


def _materialized_mlp():
    tdx.manual_seed(0)
    m = tdx.deferred_init(MLP)
    tdx.materialize_module(m)
    return m


def _mse_step(model, mesh, **kw):
    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((functional_call(model, p, (x,)) - y) ** 2)

    return ShardedTrainStep(loss_fn, optax.sgd(1e-2), mesh, **kw)


class TestCommAuditFSDP:
    """FSDP gradient sync bytes == parameter bytes (the ISSUE 5 pin)."""

    def test_closed_form_bytes_and_caching(self):
        n = 8
        mesh = create_mesh({"fsdp": n})
        model = _materialized_mlp()
        step = _mse_step(model, mesh, shard_axis="fsdp")
        params = step.shard_params(dict(model.named_parameters()))
        opt = step.init_optimizer(params)
        x = np.zeros((8, 16), np.float32)

        with comm_audit() as prof:
            params, opt, _ = step(params, opt, (x, x))

        # fc1/fc2 weights (1024 elems each) shard; biases (64/16) stay
        # replicated below min_shard_elems
        sharded_bytes = (64 * 16 + 16 * 64) * F32
        bias_bytes = (64 + 16) * F32
        assert prof.ops("all_gather", "fsdp") == 2
        assert prof.ops("reduce_scatter", "fsdp") == 2
        assert prof.payload_bytes("all_gather", "fsdp") == sharded_bytes
        assert prof.payload_bytes("reduce_scatter", "fsdp") == sharded_bytes
        # ring wire bytes: (n-1)/n of the payload, exactly
        assert prof.wire_bytes("reduce_scatter", "fsdp") == (
            sharded_bytes * (n - 1) / n
        )
        assert prof.wire_bytes("all_gather", "fsdp") == (
            sharded_bytes * (n - 1) / n
        )
        # replicated-leaf grads pmean (2 biases) + the loss pmean
        assert prof.ops("pmean", "fsdp") == 3
        assert prof.payload_bytes("pmean", "fsdp") == bias_bytes + F32

        # cached program: the second call must record NOTHING
        with comm_audit() as prof2:
            step(params, opt, (x, x))
        assert not prof2

    def test_profile_json_schema(self):
        prof = CommProfile()
        with comm_audit(prof):
            record_collective(
                "all_reduce", "dp", payload_bytes=1024, axis_size=4
            )
        doc = prof.to_json()
        assert validate_comm_profile(doc) == []
        assert doc["bytes_by_axis"] == {"dp": 1536}  # 2*(3/4)*1024
        # corrupt it -> the validator must say so
        doc["entries"][0]["ops"] = "three"
        assert validate_comm_profile(doc)
        assert validate_comm_profile({"schema": "nope"})

    def test_nested_audits_both_record(self):
        outer, inner = CommProfile(), CommProfile()
        with comm_audit(outer):
            with comm_audit(inner):
                record_collective(
                    "all_reduce", "dp", payload_bytes=8, axis_size=2
                )
        assert outer.ops() == inner.ops() == 1


class TestCommAuditTP:
    """Megatron f/g collectives: one fwd all-reduce + one bwd psum per
    layer, activation-sized."""

    def test_per_layer_allreduce_counts(self):
        n, d, h, b = 8, 16, 64, 4
        n_layers = 3
        mesh = create_mesh({"tp": n})
        rs = np.random.RandomState(0)
        ws = {
            f"w1_{i}": jnp.asarray(rs.randn(d, h).astype(np.float32))
            for i in range(n_layers)
        } | {
            f"w2_{i}": jnp.asarray(rs.randn(h, d).astype(np.float32))
            for i in range(n_layers)
        }
        x = jnp.asarray(rs.randn(b, d).astype(np.float32))

        def loss_fn(p, x):
            h_act = x
            for i in range(n_layers):
                xin = collectives.copy_psum_grad(h_act, "tp")
                mid = jax.nn.relu(xin @ p[f"w1_{i}"])
                h_act = collectives.allreduce_linear(
                    mid @ p[f"w2_{i}"], "tp"
                )
            return jnp.sum(h_act)

        def body(p, x):
            # differentiate wrt the input too (as an embedding below the
            # first TP layer would): every layer's input cotangent is
            # live, so every f-backward psum traces
            return jax.grad(loss_fn, argnums=(0, 1))(p, x)[0]

        specs = {
            f"w1_{i}": P(None, "tp") for i in range(n_layers)
        } | {f"w2_{i}": P("tp", None) for i in range(n_layers)}
        f = jax.jit(
            shard_map(
                body,
                mesh=mesh,
                in_specs=(specs, P()),
                out_specs=specs,
                check_vma=False,
            )
        )
        with comm_audit() as prof:
            jax.block_until_ready(f(ws, x))

        act_bytes = b * d * F32
        # forward: exactly one activation all-reduce per layer
        assert prof.ops("allreduce_linear", "tp") == n_layers
        assert prof.payload_bytes("allreduce_linear", "tp") == (
            n_layers * act_bytes
        )
        assert prof.wire_bytes("allreduce_linear", "tp") == (
            n_layers * act_bytes * 2 * (n - 1) / n
        )
        # backward: one psum per layer where the activation entered (f's
        # custom VJP), zero-wire identity for g's backward
        assert prof.ops("copy_psum_grad_bwd", "tp") == n_layers
        assert prof.payload_bytes("copy_psum_grad_bwd", "tp") == (
            n_layers * act_bytes
        )
        assert prof.ops("allreduce_linear_bwd", "tp") == n_layers
        assert prof.wire_bytes("allreduce_linear_bwd", "tp") == 0

    def test_dead_input_cotangent_is_pruned(self):
        """grad wrt params only: the FIRST layer's f-backward psum has a
        dead cotangent (nothing upstream is differentiated) and JAX
        prunes it — the audit must show n_layers-1, not n_layers, or the
        analytic model overstates backward traffic."""
        n, d, b, n_layers = 8, 16, 4, 3
        mesh = create_mesh({"tp": n})
        rs = np.random.RandomState(0)
        ws = {
            f"w_{i}": jnp.asarray(rs.randn(d, d).astype(np.float32))
            for i in range(n_layers)
        }

        def loss_fn(p, x):
            h_act = x
            for i in range(n_layers):
                xin = collectives.copy_psum_grad(h_act, "tp")
                h_act = collectives.allreduce_linear(
                    xin @ p[f"w_{i}"], "tp"
                )
            return jnp.sum(h_act)

        f = jax.jit(
            shard_map(
                lambda p, x: jax.grad(loss_fn)(p, x),
                mesh=mesh,
                in_specs=({k: P() for k in ws}, P()),
                out_specs={k: P() for k in ws},
                check_vma=False,
            )
        )
        with comm_audit() as prof:
            jax.block_until_ready(
                f(ws, jnp.asarray(rs.randn(b, d).astype(np.float32)))
            )
        assert prof.ops("copy_psum_grad_bwd", "tp") == n_layers - 1


class TestCommAuditPP:
    """1F1B schedule: exchange ops == 2*(M + 2*(S-1)) of one microbatch
    activation each (scan trip counts recorded statically)."""

    def test_1f1b_exchange_closed_form(self):
        from torchdistx_tpu.parallel.pp import (
            pipeline_train_step,
            split_microbatches,
            stack_pipeline_stages,
        )

        S = 4
        mesh = create_mesh({"pp": S}, devices=jax.devices()[:S])
        d, b, n_micro = 8, 2, 6
        rs = np.random.RandomState(1)
        stages = [
            {"w": jnp.asarray(rs.randn(d, d).astype(np.float32) * 0.1)}
            for _ in range(S)
        ]
        stacked = stack_pipeline_stages(stages, mesh, axis="pp")
        mb = split_microbatches(
            jnp.asarray(rs.randn(n_micro * b, d).astype(np.float32)),
            n_micro,
        )
        tgt = jnp.zeros_like(mb)

        def stage_fn(p, x):
            return x + jnp.tanh(x @ p["w"])

        with comm_audit() as prof:
            loss, grads = pipeline_train_step(
                stacked, mb, tgt,
                mesh=mesh, stage_fn=stage_fn,
                loss_fn=lambda y, t: jnp.mean((y - t) ** 2),
                axis="pp",
            )
            jax.block_until_ready(loss)

        ticks = n_micro + 2 * (S - 1)
        act_bytes = b * d * F32
        assert prof.ops("exchange", "pp") == 2 * ticks
        assert prof.payload_bytes("exchange", "pp") == (
            2 * ticks * act_bytes
        )
        # each lockstep ppermute drives S-1 of the S ring links
        assert prof.wire_bytes("exchange", "pp") == pytest.approx(
            2 * ticks * act_bytes * (S - 1) / S
        )
        # the loss replication psum
        assert prof.ops("all_reduce", "pp") == 1


class TestCommAuditGossip:
    """GossipGraD: intra-node all-mean of the full gradient once per
    step, one node-axis exchange per traced schedule branch."""

    def test_gossip_bytes(self, mesh2x4):
        from torchdistx_tpu.parallel import (
            GossipGraDState,
            gossip_grad_hook,
        )

        tdx.manual_seed(3)
        model = _materialized_mlp()
        gparams = dict(model.named_parameters())
        state = GossipGraDState(2, node_axis="node", local_axis="local")
        n_branches = len(state.branch_table()[0])
        step = _mse_step(
            model, mesh2x4,
            shard_axis=None,
            replica_axes=("node",),
            comm_hook=gossip_grad_hook,
            hook_state=state,
            divergent_replicas=True,
            batch_axes=("node", "local"),
        )
        p = step.stack_replicas(gparams)
        s = step.init_optimizer(p)
        x = np.zeros((8, 16), np.float32)
        y = np.zeros((8, 16), np.float32)
        with comm_audit() as prof:
            p, s, _ = step(p, s, (x, y))

        # per-replica gradient bytes: the hook sees the (1, ...) stacked
        # local view — same element count as the parameters themselves
        grad_bytes = sum(
            int(np.prod(v.shape)) * F32 for v in gparams.values()
        )
        # local-axis combine: the hook owns only replica_axes=("node",),
        # so the trainer's grad_reduce_axes pmean carries the local-axis
        # gradient traffic (+ the scalar loss-replication pmean)
        assert prof.ops("pmean", "local") == 2
        assert prof.payload_bytes("pmean", "local") == grad_bytes + F32
        # every lax.switch branch traces: one exchange per branch, each
        # of the full gradient (a conservative upper bound by design —
        # exactly n_branches at trace time)
        assert prof.ops("exchange", "node") == n_branches
        assert prof.payload_bytes("exchange", "node") == (
            n_branches * grad_bytes
        )


class TestCommAuditRing:
    """Sequence-parallel attention traffic is booked (the TDX103 fix):
    ring passes record n ppermute ops per rotating tensor (the length-n
    scan executes every rotation, INCLUDING the final home-coming hop —
    the audit books what runs, not the textbook n-1), Ulysses records
    its four all-to-alls.  Payloads are exact per-device block bytes."""

    def _qkv(self, b, s, h, d, seed=0):
        rs = np.random.RandomState(seed)
        return tuple(
            jnp.asarray(rs.randn(b, s, h, d), jnp.float32) for _ in range(3)
        )

    def test_jnp_ring_forward_closed_form(self, mesh8):
        from torchdistx_tpu.ops.attention import ring_attention

        n = 8
        b, s, h, d = 2, 64, 4, 16
        q, k, v = self._qkv(b, s, h, d)
        fn = jax.jit(
            shard_map(
                lambda q_, k_, v_: ring_attention(
                    q_, k_, v_, axis="fsdp", causal=True
                ),
                mesh=mesh8,
                in_specs=(P(None, "fsdp"),) * 3,
                out_specs=P(None, "fsdp"),
                check_vma=False,
            )
        )
        with comm_audit() as prof:
            fn(q, k, v)
        # rotating carry: K block, V block, 4-byte block index
        blk = b * (s // n) * h * d * F32
        ring_bytes = n * (2 * blk + 4)
        assert prof.ops("ppermute", "fsdp") == 3 * n
        assert prof.payload_bytes("ppermute", "fsdp") == ring_bytes
        # full-rotation ring hop: every device sends, wire ratio 1.0
        assert prof.wire_bytes("ppermute", "fsdp") == ring_bytes
        assert validate_comm_profile(prof.to_json()) == []

        # cached program: the second call must record NOTHING
        with comm_audit() as prof2:
            fn(q, k, v)
        assert not prof2

    def test_flash_ring_backward_books_five_tensors(self, mesh8):
        from torchdistx_tpu.ops.attention import ring_flash_attention

        n = 8
        b, s, h, d = 1, 64, 4, 8
        q, k, v = self._qkv(b, s, h, d, seed=1)
        ring = shard_map(
            lambda q_, k_, v_: ring_flash_attention(
                q_, k_, v_, axis="fsdp", causal=True, block_q=8, block_k=8
            ),
            mesh=mesh8,
            in_specs=(P(None, "fsdp"),) * 3,
            out_specs=P(None, "fsdp"),
            check_vma=False,
        )
        grad_fn = jax.jit(
            jax.grad(
                lambda q_, k_, v_: jnp.sum(jnp.sin(ring(q_, k_, v_))),
                argnums=(0, 1, 2),
            )
        )
        with comm_audit() as prof:
            grad_fn(q, k, v)
        kv = b * (s // n) * h * d * F32
        # forward ring: K, V, index; backward ring: K, V, their f32
        # gradient accumulators, index — five rotating tensors
        fwd_bytes = n * (2 * kv + 4)
        bwd_bytes = n * (4 * kv + 4)
        assert prof.ops("ppermute", "fsdp") == (3 + 5) * n
        assert prof.payload_bytes("ppermute", "fsdp") == fwd_bytes + bwd_bytes
        assert prof.wire_bytes("ppermute", "fsdp") == fwd_bytes + bwd_bytes

    def test_ulysses_all_to_all_closed_form(self, mesh8):
        from torchdistx_tpu.ops.attention import ulysses_attention

        n = 8
        b, s, h, d = 2, 64, 8, 16
        q, k, v = self._qkv(b, s, h, d, seed=2)
        fn = jax.jit(
            shard_map(
                lambda q_, k_, v_: ulysses_attention(
                    q_, k_, v_, axis="fsdp", causal=True, use_flash=False
                ),
                mesh=mesh8,
                in_specs=(P(None, "fsdp"),) * 3,
                out_specs=P(None, "fsdp"),
                check_vma=False,
            )
        )
        with comm_audit() as prof:
            fn(q, k, v)
        # q/k/v reshard out, attention output reshards back: four
        # all-to-alls of one per-device tensor each
        t = b * (s // n) * h * d * F32
        assert prof.ops("all_to_all", "fsdp") == 4
        assert prof.payload_bytes("all_to_all", "fsdp") == 4 * t
        # each device keeps its own slice: (n-1)/n of the payload on wire
        assert prof.wire_bytes("all_to_all", "fsdp") == 4 * t * (n - 1) / n


class TestShardingAudit:
    def test_flags_deliberate_replication(self, mesh8):
        big = jax.device_put(
            jnp.zeros((64, 64), jnp.float32), NamedSharding(mesh8, P())
        )
        sharded = jax.device_put(
            jnp.zeros((64, 64), jnp.float32),
            NamedSharding(mesh8, P("fsdp", None)),
        )
        small = jax.device_put(
            jnp.zeros((8,), jnp.float32), NamedSharding(mesh8, P())
        )
        rep = sharding_report(
            {"big": big, "sharded": sharded, "small": small}
        )
        kinds = {(f["kind"], f["path"]) for f in rep["flags"]}
        assert ("accidental_replication", "big") in kinds
        assert all(p != "sharded" for _, p in kinds)
        assert all(p != "small" for _, p in kinds)  # under min_shard_elems
        assert rep["total_bytes"] == (64 * 64 * 2 + 8) * F32
        # per-device: one full copy of big + 1/8 of sharded + small
        assert rep["bytes_per_device"] == (
            64 * 64 * F32 + 64 * 64 * F32 // 8 + 8 * F32
        )

    def test_planned_replication_not_flagged(self, mesh8):
        big = jax.device_put(
            jnp.zeros((64, 64), jnp.float32), NamedSharding(mesh8, P())
        )
        rep = sharding_report(
            {"big": big},
            intended_rule=lambda path, a: NamedSharding(mesh8, P()),
        )
        assert rep["flags"] == []
        # ... but an intended-vs-actual mismatch IS flagged
        rep2 = sharding_report(
            {"big": big},
            intended_rule=lambda path, a: NamedSharding(
                mesh8, P("fsdp", None)
            ),
        )
        assert [f["kind"] for f in rep2["flags"]] == ["sharding_mismatch"]

    def test_flags_unsharded_optimizer_state(self, mesh8):
        model = _materialized_mlp()
        params = {
            k: jax.device_put(
                v,
                NamedSharding(
                    mesh8,
                    P("fsdp", None) if v.ndim == 2 else P(),
                ),
            )
            for k, v in dict(model.named_parameters()).items()
        }
        opt = optax.adam(1e-3)
        # WITHOUT optimizer_state_shardings: moments land replicated
        bad_state = jax.jit(opt.init)(
            jax.device_put(
                {k: np.asarray(v) for k, v in params.items()},
                NamedSharding(mesh8, P()),
            )
        )
        rep = sharding_report(params, optimizer_state=bad_state)
        bad = [
            f for f in rep["flags"]
            if f["kind"] == "unsharded_optimizer_state"
        ]
        # adam keeps mu and nu per sharded weight -> 2 slots x 2 weights
        assert len(bad) == 4
        assert all("optimizer_state_shardings" in f["detail"] for f in bad)

        # WITH the proper out_shardings: clean report
        shardings = optimizer_state_shardings(
            jax.eval_shape(opt.init, params), params, mesh8
        )
        good_state = jax.jit(opt.init, out_shardings=shardings)(params)
        rep2 = sharding_report(params, optimizer_state=good_state)
        assert [
            f for f in rep2["flags"]
            if f["kind"] == "unsharded_optimizer_state"
        ] == []


class TestFlightRecorder:
    def test_ring_bound_and_dump_header(self, tmp_path):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            rec.record("tick", i=i)
        assert rec.depth == 4 and rec.recorded_total == 10
        path = rec.dump(str(tmp_path / "d.jsonl"), reason="test")
        assert validate_flight_jsonl(path) == []
        lines = [json.loads(x) for x in open(path)]
        assert lines[0]["kind"] == "flight_header"
        assert lines[0]["reason"] == "test"
        assert lines[0]["dropped"] == 6
        assert [e["i"] for e in lines[1:]] == [6, 7, 8, 9]

    def test_stream_flushes_per_event(self, tmp_path):
        # kill -9 semantics: every record must be ON DISK before close
        path = str(tmp_path / "stream.jsonl")
        rec = FlightRecorder(path=path)
        rec.record("a", x=1)
        rec.record("b", y=2)
        with open(path) as f:  # recorder still open — no close, no flush call
            lines = [json.loads(ln) for ln in f.read().splitlines()]
        assert [e["kind"] for e in lines] == ["a", "b"]
        assert validate_flight_jsonl(path) == []
        rec.close_stream()

    def test_validator_rejects_garbage(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text('{"kind": "ok", "t": 1.0}\nnot json\n{"t": 2.0}\n')
        errs = validate_flight_jsonl(str(p))
        assert len(errs) == 2  # bad line + missing kind


def _fit_nan_rollback(tmp_path, on_failure="restore"):
    """Shared crash-path scaffold: 4 clean steps (checkpoint at 2/4),
    then a poisoned parameter."""
    mesh = create_mesh({"fsdp": 8})
    model = _materialized_mlp()
    step = _mse_step(model, mesh, shard_axis="fsdp")
    params = step.shard_params(dict(model.named_parameters()))
    opt = step.init_optimizer(params)
    rs = np.random.RandomState(0)
    batches = [
        (b, b) for b in (rs.randn(8, 16).astype(np.float32)
                         for _ in range(8))
    ]
    rec = FlightRecorder(dump_dir=str(tmp_path))
    trainer = Trainer(
        step, params, opt,
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2,
        log_every=1, log_fn=lambda m: None,
        failure_detector=FailureDetector(nan_tolerance=0),
        on_failure=on_failure, flight=rec,
    )
    trainer.fit(batches[:4])
    poisoned = dict(trainer.params)
    k0 = next(iter(poisoned))
    poisoned[k0] = poisoned[k0] * jnp.float32(np.nan)
    trainer.params = poisoned
    return trainer, batches


class TestCrashPath:
    def test_nan_rollback_writes_flight_dump(self, tmp_path):
        trainer, batches = _fit_nan_rollback(tmp_path)
        res = trainer.fit(batches[4:])
        assert np.isfinite(res["loss"])  # rollback recovered the run

        dump = trainer.last_flight_dump
        assert dump and os.path.dirname(dump) == str(tmp_path)
        assert validate_flight_jsonl(dump) == []
        recs = [json.loads(ln) for ln in open(dump)]
        # the LAST entries show the incident: failure then rollback
        assert [r["kind"] for r in recs[-2:]] == ["failure", "rollback"]
        rb = recs[-1]
        assert rb["action"] == "restored"
        assert rb["restored_step"] == 4
        assert rb["checkpoint"].endswith("step_4")
        assert recs[-2]["failure_kind"] == "nonfinite"
        # step records carry the telemetry fields the ISSUE names
        step_rec = next(r for r in recs if r["kind"] == "step")
        for field in ("loss", "rng_counter", "comm", "steps_per_sec"):
            assert field in step_rec
        assert validate_comm_profile(
            trainer.comm_profile.to_json()
        ) == []

    def test_raise_policy_dumps_on_exception(self, tmp_path):
        trainer, batches = _fit_nan_rollback(tmp_path, on_failure="raise")
        with pytest.raises(Exception):
            trainer.fit(batches[4:])
        dump = trainer.last_flight_dump
        assert dump and validate_flight_jsonl(dump) == []
        recs = [json.loads(ln) for ln in open(dump)]
        assert recs[-1]["kind"] == "exception"
        assert "StepFailure" in recs[-1]["error"]

    def test_detector_counters_scrapeable(self, tmp_path):
        from torchdistx_tpu.obs.metrics import (
            MetricsRegistry,
            parse_prometheus,
        )

        trainer, batches = _fit_nan_rollback(tmp_path)
        trainer.fit(batches[4:])
        reg = MetricsRegistry()
        reg.register_collector(trainer.metrics_collector(), obj=trainer)
        parsed = parse_prometheus(reg.render())
        s = parsed["samples"]
        assert s[("tdx_train_failures_total", ())] == 1
        assert s[
            ("tdx_train_failure_events_total", (("kind", "nonfinite"),))
        ] == 1
        assert s[("tdx_train_consecutive_nonfinite", ())] == 0  # reset
        assert 0 < s[("tdx_train_goodput", ())] <= 1


class TestRuntimeGauges:
    def test_default_registry_serves_flight_and_jit_gauges(self):
        from torchdistx_tpu.obs.metrics import (
            default_registry,
            parse_prometheus,
        )
        from torchdistx_tpu.obs.recompile import track_jit_cache

        jitted = jax.jit(lambda x: x + 1)
        jitted(jnp.zeros(4))
        track_jit_cache("audit_test_fn", jitted)
        parsed = parse_prometheus(default_registry().render())
        s = parsed["samples"]
        assert ("tdx_flight_depth", ()) in s
        assert ("tdx_flight_capacity", ()) in s
        key = ("tdx_jit_cache_size", (("fn", "audit_test_fn"),))
        assert s[key] >= 1

    def test_trainer_mfu_gauge(self, tmp_path):
        mesh = create_mesh({"fsdp": 8})
        model = _materialized_mlp()
        step = _mse_step(model, mesh, shard_axis="fsdp")
        params = step.shard_params(dict(model.named_parameters()))
        opt = step.init_optimizer(params)
        batches = [(np.zeros((8, 16), np.float32),) * 2 for _ in range(4)]
        trainer = Trainer(
            step, params, opt, log_every=1, log_fn=lambda m: None,
            tokens_per_batch=128, flops_per_token=1000.0,
            peak_flops=1e9,
            flight=FlightRecorder(dump_dir=str(tmp_path)),
        )
        trainer.fit(batches)
        assert trainer.metrics["tokens_per_sec"] > 0
        assert trainer.metrics["mfu"] == pytest.approx(
            trainer.metrics["tokens_per_sec"] * 1000.0 / 1e9
        )
        assert 0 < trainer.metrics["goodput"] <= 1

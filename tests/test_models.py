"""Model families: construction (eager + deferred), forward shapes, jit,
parameter counts, ring attention equivalence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import torchdistx_tpu as tdx
from torchdistx_tpu.models import GPT2, Llama, T5, resnet18, resnet50
from torchdistx_tpu.nn import functional_call
from torchdistx_tpu.ops.attention import multihead_attention, ring_attention


class TestLlama:
    def test_deferred_then_forward(self):
        tdx.manual_seed(0)
        m = tdx.deferred_init(Llama.from_name, "tiny")
        assert tdx.is_deferred(m)
        tdx.materialize_module(m)
        tokens = jnp.zeros((2, 16), jnp.int32)
        logits = m(tokens)
        assert logits.shape == (2, 16, 256)

    def test_jit_forward(self):
        tdx.manual_seed(0)
        m = Llama.from_name("tiny")
        params = dict(m.named_parameters())
        tokens = jnp.zeros((2, 16), jnp.int32)
        f = jax.jit(lambda p, t: functional_call(m, p, (t,)))
        np.testing.assert_allclose(
            np.asarray(f(params, tokens)), np.asarray(m(tokens)), rtol=2e-5, atol=1e-5
        )

    def test_7b_param_count_under_fake_mode(self):
        # the north-star model is constructible with zero storage
        with tdx.fake_mode():
            m = Llama.from_name("llama2_7b")
        n = m.num_params()
        assert 6.5e9 < n < 7.5e9  # ~6.74B

    def test_gqa_heads(self):
        tdx.manual_seed(0)
        m = Llama.from_name("tiny", n_kv_heads=2)
        logits = m(jnp.zeros((1, 8), jnp.int32))
        assert logits.shape == (1, 8, 256)


class TestGPT2:
    def test_deferred_and_shapes(self):
        tdx.manual_seed(1)
        m = tdx.deferred_init(GPT2.from_name, "tiny")
        tdx.materialize_module(m)
        logits = m(jnp.zeros((2, 12), jnp.int32))
        assert logits.shape == (2, 12, 256)

    def test_gpt2_large_param_count(self):
        with tdx.fake_mode():
            m = GPT2.from_name("gpt2_large")
        # GPT-2 large ~774M params (tied head)
        assert 7.0e8 < m.num_params() < 8.5e8


class TestResNet:
    def test_resnet18_forward(self):
        tdx.manual_seed(2)
        m = tdx.deferred_init(resnet18, num_classes=10)
        tdx.materialize_module(m)
        m.eval()
        out = m(jnp.ones((2, 3, 32, 32)))
        assert out.shape == (2, 10)

    def test_resnet50_param_count(self):
        with tdx.fake_mode():
            m = resnet50()
        # torchvision resnet50 = 25.557M params
        assert 25.0e6 < m.num_params() < 26.2e6


class TestT5:
    def test_deferred_and_shapes(self):
        tdx.manual_seed(3)
        m = tdx.deferred_init(T5.from_name, "tiny")
        tdx.materialize_module(m)
        logits = m(jnp.zeros((2, 10), jnp.int32), jnp.zeros((2, 6), jnp.int32))
        assert logits.shape == (2, 6, 256)

    def test_t5_3b_param_count(self):
        with tdx.fake_mode():
            m = T5.from_name("t5_3b")
        assert 2.6e9 < m.num_params() < 3.2e9


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, mesh8, causal):
        rs = np.random.RandomState(0)
        b, s, h, d = 2, 64, 4, 16
        q = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)

        full = multihead_attention(q, k, v, causal=causal)

        ring = shard_map(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, axis="fsdp", causal=causal),
            mesh=mesh8,
            in_specs=(P(None, "fsdp"), P(None, "fsdp"), P(None, "fsdp")),
            out_specs=P(None, "fsdp"),
            check_vma=False,
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(full), rtol=2e-4, atol=2e-5)

    def test_gqa_ring(self, mesh8):
        rs = np.random.RandomState(1)
        b, s, hq, hkv, d = 1, 32, 8, 2, 8
        q = jnp.asarray(rs.randn(b, s, hq, d), jnp.float32)
        k = jnp.asarray(rs.randn(b, s, hkv, d), jnp.float32)
        v = jnp.asarray(rs.randn(b, s, hkv, d), jnp.float32)
        full = multihead_attention(q, k, v, causal=True)
        ring = shard_map(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, axis="fsdp", causal=True),
            mesh=mesh8,
            in_specs=(P(None, "fsdp"), P(None, "fsdp"), P(None, "fsdp")),
            out_specs=P(None, "fsdp"),
            check_vma=False,
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(full), rtol=2e-4, atol=2e-5)


class TestT5Flash:
    def test_flash_self_attention_matches_einsum(self):
        from torchdistx_tpu.models import T5

        tdx.manual_seed(31)
        m = tdx.deferred_init(T5.from_name, "tiny")
        tdx.materialize_module(m)
        params = dict(m.named_parameters())
        enc = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (2, 24)), jnp.int32
        )
        dec = jnp.asarray(
            np.random.RandomState(1).randint(0, 256, (2, 16)), jnp.int32
        )
        base = functional_call(m, params, (enc, dec))
        for blk in list(m.enc_blocks) + list(m.dec_blocks):
            blk.self_attn.cfg = dataclasses.replace(
                blk.self_attn.cfg, use_flash=True
            )
        flash = functional_call(m, params, (enc, dec))
        np.testing.assert_allclose(
            np.asarray(base), np.asarray(flash), rtol=3e-5, atol=3e-5
        )


class TestRingAttentionBias:
    @pytest.mark.parametrize("causal", [True, False])
    def test_ring_with_bias_matches_full(self, mesh8, causal):
        """Bias sharded by query rows (H, sq_local, S_global): ring must
        equal full attention with the same global bias — the T5-under-SP
        long-context path."""
        rs = np.random.RandomState(2)
        b, s, h, d = 1, 64, 4, 16
        q = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        bias = jnp.asarray(rs.randn(h, s, s) * 0.5, jnp.float32)

        # reference: full attention + bias (unscaled-compatible path)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        logits = logits / np.sqrt(d) + bias[None]
        if causal:
            mask = jnp.tril(jnp.ones((s, s), bool))
            logits = jnp.where(mask, logits, -jnp.inf)
        full = jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1).astype(q.dtype), v
        )

        ring = shard_map(
            lambda q_, k_, v_, b_: ring_attention(
                q_, k_, v_, axis="fsdp", causal=causal, bias=b_
            ),
            mesh=mesh8,
            in_specs=(
                P(None, "fsdp"),
                P(None, "fsdp"),
                P(None, "fsdp"),
                P(None, "fsdp", None),  # bias rows follow the query shard
            ),
            out_specs=P(None, "fsdp"),
            check_vma=False,
        )(q, k, v, bias)
        np.testing.assert_allclose(
            np.asarray(ring), np.asarray(full), rtol=2e-4, atol=2e-5
        )


class TestT5SequenceParallel:
    """T5 with sp_axis: the whole encoder-decoder forward inside
    shard_map (sequence sharded) must equal the unsharded model — the
    rel-pos bias rides per-device row slices through the ring paths and
    cross-attention rings over the encoder's key shards."""

    @pytest.mark.parametrize("use_flash", [False, True])
    @pytest.mark.slow
    def test_sp_forward_matches_unsharded(self, use_flash):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from torchdistx_tpu.models import T5
        from torchdistx_tpu.nn import functional_call
        from torchdistx_tpu.parallel import create_mesh

        mesh = create_mesh({"sp": 8})
        tdx.manual_seed(11)
        plain = tdx.deferred_init(T5.from_name, "tiny", use_flash=use_flash)
        tdx.materialize_module(plain)
        params = dict(plain.named_parameters())
        sp = T5.from_name("tiny", use_flash=use_flash, sp_axis="sp")
        sp.load_state_dict(params)
        from jax.sharding import NamedSharding

        params = jax.device_put(params, NamedSharding(mesh, P()))

        rs = np.random.RandomState(7)
        # UNEQUAL enc/dec lengths: cross-attention rings q shards of 4
        # over encoder key shards of 8 — the sq != skv ring path
        src = jnp.asarray(rs.randint(0, 256, (2, 64)), jnp.int32)
        tgt = jnp.asarray(rs.randint(0, 256, (2, 32)), jnp.int32)

        ref = plain(src, tgt)
        out = shard_map(
            lambda p, s, t: functional_call(sp, p, (s, t)),
            mesh=mesh,
            in_specs=(P(), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )(params, src, tgt)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
        )

    @pytest.mark.slow
    def test_sp_gradients_match_unsharded(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from torchdistx_tpu.models import T5
        from torchdistx_tpu.nn import functional, functional_call
        from torchdistx_tpu.parallel import create_mesh

        mesh = create_mesh({"sp": 8})
        tdx.manual_seed(12)
        plain = tdx.deferred_init(T5.from_name, "tiny")
        tdx.materialize_module(plain)
        params = dict(plain.named_parameters())
        sp = T5.from_name("tiny", sp_axis="sp")
        sp.load_state_dict(params)
        from jax.sharding import NamedSharding

        sp_params = jax.device_put(params, NamedSharding(mesh, P()))

        rs = np.random.RandomState(8)
        src = jnp.asarray(rs.randint(0, 256, (1, 64)), jnp.int32)
        tgt = jnp.asarray(rs.randint(0, 256, (1, 64)), jnp.int32)

        def loss_plain(p):
            return functional.cross_entropy(
                functional_call(plain, p, (src, tgt)), tgt
            )

        def loss_sp(p):
            def inner(p, s, t):
                logits = functional_call(sp, p, (s, t))
                return jax.lax.pmean(
                    functional.cross_entropy(logits, t), "sp"
                )

            return shard_map(
                inner,
                mesh=mesh,
                in_specs=(P(), P(None, "sp"), P(None, "sp")),
                out_specs=P(),
                check_vma=False,
            )(p, src, tgt)

        gp = jax.grad(loss_plain)(params)
        gs = jax.grad(loss_sp)(sp_params)
        # rel-bias table must receive the ring-accumulated dbias
        key = next(k for k in gp if "rel_bias" in k)
        np.testing.assert_allclose(
            np.asarray(gs[key]), np.asarray(gp[key]),
            rtol=3e-4, atol=3e-5, err_msg=key,
        )
        for k in gp:
            np.testing.assert_allclose(
                np.asarray(gs[k]), np.asarray(gp[k]),
                rtol=5e-4, atol=5e-5, err_msg=k,
            )


class TestSequenceParallelFamilies:
    """SP must hold across model families, not just Llama: GPT-2
    (learned positions offset per shard) and Mixtral (MoE FFN under the
    ring) — forward parity vs the unsharded model on the sp mesh."""

    @staticmethod
    def _sp_forward(model_sp, params, mesh, *args):
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P

        from torchdistx_tpu.nn import functional_call

        params = jax.device_put(params, NamedSharding(mesh, P()))
        specs = tuple(P(None, "sp") for _ in args)
        return shard_map(
            lambda p, *a: functional_call(model_sp, p, a),
            mesh=mesh,
            in_specs=(P(),) + specs,
            out_specs=P(None, "sp"),
            check_vma=False,
        )(params, *args)

    @pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
    @pytest.mark.slow
    def test_gpt2_sp_matches_unsharded(self, sp_mode):
        from torchdistx_tpu.models import GPT2
        from torchdistx_tpu.parallel import create_mesh

        mesh = create_mesh({"sp": 8})
        # ulysses reshards heads over the axis: use 8 heads for 8 devices
        kw = {"n_heads": 8} if sp_mode == "ulysses" else {}
        tdx.manual_seed(13)
        plain = tdx.deferred_init(GPT2.from_name, "tiny", **kw)
        tdx.materialize_module(plain)
        params = dict(plain.named_parameters())
        sp = GPT2.from_name("tiny", sp_axis="sp", sp_mode=sp_mode, **kw)
        sp.load_state_dict(params)

        toks = jnp.asarray(
            np.random.RandomState(9).randint(0, 256, (2, 64)), jnp.int32
        )
        ref = plain(toks)
        out = self._sp_forward(sp, params, mesh, toks)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
        )

    @pytest.mark.slow
    def test_mixtral_sp_matches_unsharded(self):
        from torchdistx_tpu.models import Mixtral
        from torchdistx_tpu.parallel import create_mesh

        mesh = create_mesh({"sp": 8})
        tdx.manual_seed(14)
        plain = tdx.deferred_init(Mixtral.from_name, "tiny")
        tdx.materialize_module(plain)
        params = dict(plain.named_parameters())
        sp = Mixtral.from_name("tiny", sp_axis="sp")
        sp.load_state_dict(params)

        toks = jnp.asarray(
            np.random.RandomState(10).randint(0, 256, (2, 64)), jnp.int32
        )
        ref = plain(toks)
        out = self._sp_forward(sp, params, mesh, toks)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-4
        )


def test_mistral_7b_preset():
    # Mistral-7B = Llama arch + GQA(8 kv heads) + 4096 sliding window;
    # param count must match the published 7.24B
    from torchdistx_tpu.models import Llama

    with tdx.fake_mode():
        m = Llama.from_name("mistral_7b")
    assert m.num_params() == 7241732096
    assert m.cfg.sliding_window == 4096 and m.cfg.n_kv_heads == 8


def test_llama3_8b_preset():
    # Llama-3-8B: GQA(8 kv), 128256 vocab, theta 5e5 — published 8.03B
    from torchdistx_tpu.models import Llama

    with tdx.fake_mode():
        m = Llama.from_name("llama3_8b")
    assert m.num_params() == 8030261248
    assert m.cfg.n_kv_heads == 8 and m.cfg.rope_theta == 500000.0


class TestRematPolicy:
    def test_grads_identical_across_policies(self):
        # remat changes WHAT is saved, never the math: loss and grads must
        # match bitwise-closely across off/full/dots
        import torchdistx_tpu as tdx
        from torchdistx_tpu.models import Llama
        from torchdistx_tpu.nn import functional, functional_call

        results = {}
        for policy, remat in [(None, False), ("full", True), ("dots", True)]:
            tdx.manual_seed(0)
            kw = dict(max_seq_len=32, remat=remat, use_flash=False)
            if policy:
                kw["remat_policy"] = policy
            m = tdx.deferred_init(Llama.from_name, "tiny", **kw)
            tdx.materialize_module(m)
            p = dict(m.named_parameters())
            toks = jnp.asarray(
                np.random.RandomState(0).randint(0, 64, (2, 32)), jnp.int32
            )

            def loss(p):
                return functional.cross_entropy(
                    functional_call(m, p, (toks,)), toks
                )

            l, g = jax.value_and_grad(loss)(p)
            results[policy or "off"] = (float(l), g)

        l0, g0 = results["off"]
        for k in ("full", "dots"):
            l1, g1 = results[k]
            np.testing.assert_allclose(l1, l0, rtol=1e-6)
            for a, b in zip(
                jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g0)
            ):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    atol=1e-5,
                )

    def test_unknown_policy_rejected_at_construction(self):
        from torchdistx_tpu.models import Llama

        with pytest.raises(ValueError, match="remat_policy"):
            Llama.from_name("tiny", remat_policy="typo")

    def test_mixtral_honors_policy(self):
        # the MoE training path threads the same policy (and the same
        # grads-invariance) as the inherited Llama paths
        import torchdistx_tpu as tdx
        from torchdistx_tpu.models import Mixtral
        from torchdistx_tpu.nn import functional, functional_call

        results = {}
        for policy in ("full", "dots"):
            tdx.manual_seed(0)
            m = tdx.deferred_init(
                Mixtral.from_name, "tiny", remat=True, remat_policy=policy,
                use_flash=False,
            )
            tdx.materialize_module(m)
            p = dict(m.named_parameters())
            toks = jnp.asarray(
                np.random.RandomState(0).randint(0, 64, (2, 16)), jnp.int32
            )

            def loss(p):
                logits, aux = functional_call(
                    m, p, (toks,), method="forward_with_aux"
                )
                return functional.cross_entropy(logits, toks) + 0.01 * aux

            l, g = jax.value_and_grad(loss)(p)
            results[policy] = (float(l), g)
        np.testing.assert_allclose(
            results["dots"][0], results["full"][0], rtol=1e-6
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(results["dots"][1]),
            jax.tree_util.tree_leaves(results["full"][1]),
        ):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=1e-5,
            )

"""Pallas flash attention: exact agreement with the reference attention (on
CPU via pallas interpret mode; compiled-kernel agreement is exercised on
real TPU hardware by bench/verification runs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchdistx_tpu as tdx
from torchdistx_tpu.models import Llama
from torchdistx_tpu.ops.attention import multihead_attention
from torchdistx_tpu.ops.flash_attention import flash_attention


@pytest.mark.parametrize(
    "b,s,hq,hkv,causal",
    [
        (2, 128, 4, 4, True),
        (1, 128, 8, 2, True),  # GQA
        (2, 64, 4, 4, False),
    ],
)
def test_matches_reference(b, s, hq, hkv, causal):
    rs = np.random.RandomState(0)
    d = 32
    q = jnp.asarray(rs.randn(b, s, hq, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, s, hkv, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, s, hkv, d), jnp.float32)
    ref = multihead_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_causal_cross_attention_end_aligned():
    # Sq < Skv (cached decode shape): query i must see keys up to
    # skv - sq + i, matching multihead_attention's end-aligned tril
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(1, 4, 2, 16), jnp.float32)
    k = jnp.asarray(rs.randn(1, 64, 2, 16), jnp.float32)
    v = jnp.asarray(rs.randn(1, 64, 2, 16), jnp.float32)
    ref = multihead_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=4, block_k=16)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_odd_lengths_auto_block():
    # block sizes reduce to dividing values; odd lengths just work
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(1, 100, 4, 32), jnp.float32)
    ref = multihead_attention(q, q, q, causal=True)
    out = flash_attention(q, q, q, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_causal_sq_gt_skv_rejected():
    q = jnp.zeros((1, 8, 2, 16))
    k = jnp.zeros((1, 4, 2, 16))
    with pytest.raises(ValueError, match="Sq"):
        flash_attention(q, k, k, causal=True)


def test_gqa_head_mismatch_error():
    q = jnp.zeros((1, 64, 6, 32))
    k = jnp.zeros((1, 64, 4, 32))
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k, k)


@pytest.mark.parametrize(
    "hq,hkv,sq,skv,causal",
    [
        (2, 2, 64, 64, True),
        (8, 2, 64, 64, True),  # GQA dK/dV group reduction
        (2, 2, 32, 64, True),  # Sq < Skv: end-aligned diag_offset masking
        (2, 2, 64, 64, False),  # non-causal (cross-attention shapes)
    ],
)
def test_gradients_match_reference(hq, hkv, sq, skv, causal):
    # flash fwd + pallas FA2 bwd must give the reference's gradients
    # across every masking regime the backward kernels implement
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(1, sq, hq, 16), jnp.float32)
    k = jnp.asarray(rs.randn(1, skv, hkv, 16), jnp.float32)
    v = jnp.asarray(rs.randn(1, skv, hkv, 16), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=32) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(multihead_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


def test_llama_use_flash_trains():
    import optax

    import torchdistx_tpu as tdx2
    from torchdistx_tpu.nn import functional, functional_call

    tdx2.manual_seed(0)
    m = Llama.from_name("tiny", use_flash=True)
    params = dict(m.named_parameters())
    tokens = jnp.zeros((2, 32), jnp.int32)

    def loss_fn(p):
        logits = functional_call(m, p, (tokens,))
        return functional.cross_entropy(logits, tokens)

    tx = optax.sgd(1e-2)
    s = tx.init(params)
    l0 = float(loss_fn(params))
    for _ in range(3):
        g = jax.grad(loss_fn)(params)
        u, s = tx.update(g, s, params)
        params = jax.tree_util.tree_map(lambda a, b: a + b, params, u)
    assert float(loss_fn(params)) < l0


def test_llama_use_flash_matches_default():
    tdx.manual_seed(0)
    a = Llama.from_name("tiny")
    tdx.manual_seed(0)
    b = Llama.from_name("tiny", use_flash=True)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 64)))
    # odd length: flash path must handle non-256-multiple sequences
    odd = jnp.asarray(np.random.RandomState(2).randint(0, 256, (1, 33)))
    assert b(odd).shape == (1, 33, 256)
    np.testing.assert_allclose(
        np.asarray(a(tokens)), np.asarray(b(tokens)), rtol=2e-4, atol=2e-4
    )


class TestBias:
    """Additive logit bias (T5 relative-position bias) on the flash path."""

    @staticmethod
    def _inputs(b=2, s=32, h=4, d=16, key=0):
        ks = jax.random.split(jax.random.PRNGKey(key), 4)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
        bias = jax.random.normal(ks[3], (h, s, s), jnp.float32)
        return q, k, v, bias

    @staticmethod
    def _reference(q, k, v, bias, causal):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        logits = logits / np.sqrt(q.shape[-1]) + bias[None]
        if causal:
            s = q.shape[1]
            mask = jnp.tril(jnp.ones((s, s), bool))
            logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_reference(self, causal):
        q, k, v, bias = self._inputs()
        out = flash_attention(
            q, k, v, bias=bias, causal=causal, block_q=8, block_k=8
        )
        ref = self._reference(q, k, v, bias, causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_gradients_including_dbias(self):
        q, k, v, bias = self._inputs(s=16)

        def flash_loss(q, k, v, b):
            return jnp.sum(
                flash_attention(
                    q, k, v, bias=b, causal=True, block_q=8, block_k=8
                ).astype(jnp.float32) ** 2
            )

        def ref_loss(q, k, v, b):
            return jnp.sum(
                self._reference(q, k, v, b, True).astype(jnp.float32) ** 2
            )

        gf = jax.grad(flash_loss, (0, 1, 2, 3))(q, k, v, bias)
        gr = jax.grad(ref_loss, (0, 1, 2, 3))(q, k, v, bias)
        for name, a, b in zip("qkvB", gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-5,
                err_msg=f"d{name}",
            )

    def test_bad_bias_shape_raises(self):
        q, k, v, bias = self._inputs()
        with pytest.raises(ValueError, match="bias shape"):
            flash_attention(q, k, v, bias=bias[:, :8], causal=False)

    def test_gradients_biased_gqa(self):
        # bias + grouped-query heads: the dbias kernel's per-query-head
        # K/V index map (bb * hkv + h // n_rep) must hold under n_rep > 1
        ks = jax.random.split(jax.random.PRNGKey(5), 4)
        b, s, hq, hkv, d = 2, 16, 4, 2, 8
        q = jax.random.normal(ks[0], (b, s, hq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
        bias = jax.random.normal(ks[3], (hq, s, s), jnp.float32)

        def ref(q, k, v, bias, causal=True):
            kr = jnp.repeat(k, hq // hkv, axis=2)
            vr = jnp.repeat(v, hq // hkv, axis=2)
            return self._reference(q, kr, vr, bias, causal)

        def flash_loss(q, k, v, b_):
            return jnp.sum(
                flash_attention(
                    q, k, v, bias=b_, causal=True, block_q=8, block_k=8
                ).astype(jnp.float32) ** 2
            )

        def ref_loss(q, k, v, b_):
            return jnp.sum(ref(q, k, v, b_).astype(jnp.float32) ** 2)

        gf = jax.grad(flash_loss, (0, 1, 2, 3))(q, k, v, bias)
        gr = jax.grad(ref_loss, (0, 1, 2, 3))(q, k, v, bias)
        for name, a, b_ in zip("qkvB", gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-5,
                err_msg=f"d{name}",
            )

    def test_gradients_biased_cross_shape(self):
        # Sq < Skv (decode / cross-attention): the end-aligned diag_offset
        # must mask dbias identically to the forward
        ks = jax.random.split(jax.random.PRNGKey(6), 4)
        b, sq, skv, h, d = 2, 8, 16, 2, 8
        q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, skv, h, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, skv, h, d), jnp.float32)
        bias = jax.random.normal(ks[3], (h, sq, skv), jnp.float32)

        def ref(q, k, v, bias):
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
            logits = logits / np.sqrt(d) + bias[None]
            rows = (skv - sq) + jnp.arange(sq)[:, None]
            cols = jnp.arange(skv)[None, :]
            logits = jnp.where(cols <= rows, logits, -jnp.inf)
            p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        def flash_loss(q, k, v, b_):
            return jnp.sum(
                flash_attention(
                    q, k, v, bias=b_, causal=True, block_q=8, block_k=8
                ).astype(jnp.float32) ** 2
            )

        def ref_loss(q, k, v, b_):
            return jnp.sum(ref(q, k, v, b_).astype(jnp.float32) ** 2)

        gf = jax.grad(flash_loss, (0, 1, 2, 3))(q, k, v, bias)
        gr = jax.grad(ref_loss, (0, 1, 2, 3))(q, k, v, bias)
        for name, a, b_ in zip("qkvB", gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-5,
                err_msg=f"d{name}",
            )

    def test_kernel_grads_match_chunked_reference(self):
        # the retired chunked-recompute backward stays as an independent
        # implementation; kernels must agree with it on the biased path
        from torchdistx_tpu.ops.flash_attention import _flash_bwd_chunked

        q, k, v, bias = self._inputs(s=16)
        g = jax.random.normal(
            jax.random.PRNGKey(9), q.shape, jnp.float32
        )

        def flash_fn(q, k, v, b_):
            return flash_attention(
                q, k, v, bias=b_, causal=True, block_q=8, block_k=8
            )

        _, vjp = jax.vjp(flash_fn, q, k, v, bias)
        dq, dk, dv, db = vjp(g)
        dq_c, dk_c, dv_c, db_c = _flash_bwd_chunked(
            q, k, v, bias, g, True, None, 8
        )
        for name, a, b_ in zip(
            ("dq", "dk", "dv", "dbias"),
            (dq, dk, dv, db),
            (dq_c, dk_c, dv_c, db_c),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-5,
                err_msg=name,
            )


class TestRingFlash:
    """Flash-backed ring attention: exact agreement with full attention
    (forward AND whole-ring custom-VJP gradients) on the sp mesh."""

    @staticmethod
    def _mesh_and_inputs(b, s, hq, hkv, d, key=0):
        from torchdistx_tpu.parallel import create_mesh

        mesh = create_mesh({"sp": 8})
        rng = np.random.RandomState(key)
        q = jnp.asarray(rng.randn(b, s, hq, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
        return mesh, q, k, v

    @staticmethod
    def _ring(mesh, causal):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from torchdistx_tpu.ops.attention import ring_flash_attention

        return shard_map(
            lambda q, k, v: ring_flash_attention(
                q, k, v, axis="sp", causal=causal, block_q=8, block_k=8
            ),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )

    @pytest.mark.parametrize(
        "hq,hkv,causal",
        [(4, 4, True), (8, 2, True), (4, 4, False)],  # incl. GQA
    )
    def test_forward_matches_full_attention(self, hq, hkv, causal):
        mesh, q, k, v = self._mesh_and_inputs(2, 64, hq, hkv, 8)
        out = self._ring(mesh, causal)(q, k, v)
        ref = multihead_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-6
        )

    @pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
    def test_gradients_match_full_attention(self, hq, hkv):
        mesh, q, k, v = self._mesh_and_inputs(1, 64, hq, hkv, 8)
        ring = self._ring(mesh, True)

        def loss_ring(q_, k_, v_):
            return jnp.sum(jnp.sin(ring(q_, k_, v_)))

        def loss_ref(q_, k_, v_):
            return jnp.sum(
                jnp.sin(multihead_attention(q_, k_, v_, causal=True))
            )

        g = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(g, gr):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-5
            )

    def test_unequal_shard_lengths_rejected(self):
        from torchdistx_tpu.ops.attention import ring_flash_attention

        q = jnp.zeros((1, 8, 4, 8))
        k = jnp.zeros((1, 16, 4, 8))
        with pytest.raises(ValueError, match="equal per-shard"):
            ring_flash_attention(q, k, q, axis="sp", causal=True)

    def test_llama_sp_flash_matches_single_device(self):
        # the model-level path: sp_axis + use_flash routes through
        # ring_flash_attention and must agree with the unsharded model
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from torchdistx_tpu.nn.module import functional_call
        from torchdistx_tpu.parallel import create_mesh

        mesh = create_mesh({"sp": 8})
        tdx.manual_seed(3)
        m_sp = tdx.deferred_init(
            Llama.from_name, "tiny", max_seq_len=64,
            sp_axis="sp", use_flash=True,
        )
        tdx.materialize_module(m_sp)
        from jax.sharding import NamedSharding

        # replicate params over the mesh (single-device-committed arrays
        # can't enter an 8-device shard_map)
        params = jax.device_put(
            dict(m_sp.named_parameters()),
            NamedSharding(mesh, P()),
        )
        tdx.manual_seed(3)
        m_ref = tdx.deferred_init(
            Llama.from_name, "tiny", max_seq_len=64, use_flash=False
        )
        tdx.materialize_module(m_ref)

        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (2, 64)), jnp.int32
        )
        logits_sp = shard_map(
            lambda t: functional_call(m_sp, params, (t,)),
            mesh=mesh,
            in_specs=P(None, "sp"),
            out_specs=P(None, "sp"),
            check_vma=False,
        )(tokens)
        logits_ref = m_ref(tokens)
        np.testing.assert_allclose(
            np.asarray(logits_sp), np.asarray(logits_ref),
            atol=2e-5, rtol=1e-5,
        )


class TestUlysses:
    """All-to-all sequence parallelism: bit-path-identical local attention
    after head/sequence resharding."""

    @staticmethod
    def _ulysses(mesh, causal, use_flash=False):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from torchdistx_tpu.ops.attention import ulysses_attention

        return shard_map(
            lambda q, k, v: ulysses_attention(
                q, k, v, axis="sp", causal=causal, use_flash=use_flash
            ),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )

    @pytest.mark.parametrize(
        "hq,hkv,causal,use_flash",
        [
            (8, 8, True, False),
            (16, 8, True, False),  # GQA (both divisible by 8)
            (8, 8, False, False),
            (8, 8, True, True),  # flash local attention (interpret)
        ],
    )
    def test_matches_full_attention(self, hq, hkv, causal, use_flash):
        from torchdistx_tpu.parallel import create_mesh

        mesh = create_mesh({"sp": 8})
        rng = np.random.RandomState(1)
        b, s, d = 2, 64, 8
        q = jnp.asarray(rng.randn(b, s, hq, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
        out = self._ulysses(mesh, causal, use_flash)(q, k, v)
        ref = multihead_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )

    @pytest.mark.parametrize("hq,hkv", [(8, 8), (16, 8)])
    @pytest.mark.slow
    def test_gradients_match_full_attention(self, hq, hkv):
        from torchdistx_tpu.parallel import create_mesh

        mesh = create_mesh({"sp": 8})
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(1, 64, hq, 8), jnp.float32)
        k = jnp.asarray(rng.randn(1, 64, hkv, 8), jnp.float32)
        v = jnp.asarray(rng.randn(1, 64, hkv, 8), jnp.float32)
        uly = self._ulysses(mesh, True)

        g = jax.grad(
            lambda a, b_, c: jnp.sum(jnp.sin(uly(a, b_, c))),
            argnums=(0, 1, 2),
        )(q, k, v)
        gr = jax.grad(
            lambda a, b_, c: jnp.sum(
                jnp.sin(multihead_attention(a, b_, c, causal=True))
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        for got, want in zip(g, gr):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-5
            )

    def test_indivisible_heads_rejected(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from torchdistx_tpu.ops.attention import ulysses_attention
        from torchdistx_tpu.parallel import create_mesh

        q = jnp.zeros((1, 8, 6, 8))  # 6 heads, axis of 8
        mesh = create_mesh({"sp": 8})
        f = shard_map(
            lambda a: ulysses_attention(a, a, a, axis="sp"),
            mesh=mesh,
            in_specs=P(None, "sp"),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
        with pytest.raises(ValueError, match="divisible"):
            f(q)

    def test_llama_sp_mode_ulysses_matches_single_device(self):
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P

        from torchdistx_tpu.nn.module import functional_call
        from torchdistx_tpu.parallel import create_mesh

        mesh = create_mesh({"sp": 8})
        tdx.manual_seed(4)
        m_sp = tdx.deferred_init(
            Llama.from_name, "tiny", max_seq_len=64,
            sp_axis="sp", sp_mode="ulysses", n_heads=8, dim=64,
        )
        tdx.materialize_module(m_sp)
        params = jax.device_put(
            dict(m_sp.named_parameters()), NamedSharding(mesh, P())
        )
        tdx.manual_seed(4)
        m_ref = tdx.deferred_init(
            Llama.from_name, "tiny", max_seq_len=64, n_heads=8, dim=64,
        )
        tdx.materialize_module(m_ref)

        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (2, 64)), jnp.int32
        )
        logits_sp = shard_map(
            lambda t: functional_call(m_sp, params, (t,)),
            mesh=mesh,
            in_specs=P(None, "sp"),
            out_specs=P(None, "sp"),
            check_vma=False,
        )(tokens)
        np.testing.assert_allclose(
            np.asarray(logits_sp), np.asarray(m_ref(tokens)),
            atol=2e-5, rtol=1e-5,
        )

    def test_bad_sp_mode_rejected(self):
        with pytest.raises(ValueError, match="sp_mode"):
            Llama.from_name("tiny", sp_mode="spiral")


class TestRingFlashBias:
    """Flash-backed ring attention with the T5-style additive bias: the
    per-hop column slices streamed into the kernels must reproduce full
    biased attention exactly, forward and gradients INCLUDING dbias
    (each device owns its query rows' bias gradient)."""

    @staticmethod
    def _reference(q, k, v, bias, causal):
        hq, hkv = q.shape[2], k.shape[2]
        if hq != hkv:
            k = jnp.repeat(k, hq // hkv, axis=2)
            v = jnp.repeat(v, hq // hkv, axis=2)
        s = q.shape[1]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        logits = logits / np.sqrt(q.shape[-1]) + bias[None]
        if causal:
            mask = jnp.tril(jnp.ones((s, s), bool))
            logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    @staticmethod
    def _ring(mesh, causal):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from torchdistx_tpu.ops.attention import ring_flash_attention

        return shard_map(
            lambda q, k, v, bias: ring_flash_attention(
                q, k, v, axis="sp", causal=causal, bias=bias,
                block_q=8, block_k=8,
            ),
            mesh=mesh,
            in_specs=(
                P(None, "sp"), P(None, "sp"), P(None, "sp"),
                P(None, "sp", None),  # query rows sharded, key dim full
            ),
            out_specs=P(None, "sp"),
            check_vma=False,
        )

    @pytest.mark.parametrize(
        "hq,hkv,causal",
        [(4, 4, True), (8, 2, True), (4, 4, False)],
    )
    def test_forward_matches_reference(self, hq, hkv, causal):
        from torchdistx_tpu.parallel import create_mesh

        mesh = create_mesh({"sp": 8})
        rng = np.random.RandomState(3)
        b, s, d = 2, 64, 8
        q = jnp.asarray(rng.randn(b, s, hq, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
        bias = jnp.asarray(rng.randn(hq, s, s) * 0.5, jnp.float32)
        out = self._ring(mesh, causal)(q, k, v, bias)
        ref = self._reference(q, k, v, bias, causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-6
        )

    @pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
    def test_gradients_including_dbias(self, hq, hkv):
        from torchdistx_tpu.parallel import create_mesh

        mesh = create_mesh({"sp": 8})
        rng = np.random.RandomState(4)
        b, s, d = 1, 64, 8
        q = jnp.asarray(rng.randn(b, s, hq, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
        bias = jnp.asarray(rng.randn(hq, s, s) * 0.5, jnp.float32)
        ring = self._ring(mesh, True)

        def loss_ring(q_, k_, v_, b_):
            return jnp.sum(jnp.sin(ring(q_, k_, v_, b_)))

        def loss_ref(q_, k_, v_, b_):
            return jnp.sum(
                jnp.sin(self._reference(q_, k_, v_, b_, True))
            )

        g = jax.grad(loss_ring, argnums=(0, 1, 2, 3))(q, k, v, bias)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for name, got, want in zip("qkvB", g, gr):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want),
                rtol=2e-4, atol=2e-5, err_msg=f"d{name}",
            )

    def test_bad_bias_shape_raises(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from torchdistx_tpu.ops.attention import ring_flash_attention
        from torchdistx_tpu.parallel import create_mesh

        mesh = create_mesh({"sp": 8})
        q = jnp.zeros((1, 64, 4, 8), jnp.float32)
        bad = jnp.zeros((4, 64, 64), jnp.float32)  # key dim sharded
        with pytest.raises(ValueError, match="UNsharded"):
            shard_map(
                lambda q, b: ring_flash_attention(
                    q, q, q, axis="sp", bias=b
                ),
                mesh=mesh,
                in_specs=(P(None, "sp"), P(None, None, "sp")),
                out_specs=P(None, "sp"),
                check_vma=False,
            )(q, bad)


class TestBucketBias:
    """In-kernel bucket bias: the kernels compute each tile's T5
    relative-position bias from the (H, buckets) table in VMEM — outputs
    and ALL gradients (incl. dtable via the fourth kernel) must match the
    materialized-bias path exactly."""

    @staticmethod
    def _setup(s=32, h=4, d=16, buckets=32, max_dist=128, key=0):
        from torchdistx_tpu.ops.flash_attention import rel_pos_bucket

        rs = np.random.RandomState(key)
        q = jnp.asarray(rs.randn(2, s, h, d), jnp.float32)
        k = jnp.asarray(rs.randn(2, s, h, d), jnp.float32)
        v = jnp.asarray(rs.randn(2, s, h, d), jnp.float32)
        table = jnp.asarray(rs.randn(h, buckets) * 0.5, jnp.float32)
        return q, k, v, table, rel_pos_bucket

    @pytest.mark.parametrize("bidir,causal", [(False, True), (True, False)])
    def test_matches_materialized_bias(self, bidir, causal):
        s, buckets, max_dist = 32, 32, 128
        q, k, v, table, bucket_fn = self._setup(s=s)

        bucket = bucket_fn(
            jnp.arange(s)[None, :] - jnp.arange(s)[:, None],
            bidirectional=bidir, buckets=buckets, max_dist=max_dist,
        )
        bias = jnp.transpose(table.T[bucket], (2, 0, 1))

        def ref_loss(q, k, v, t):
            b_ = jnp.transpose(t.T[bucket], (2, 0, 1))
            return jnp.sum(flash_attention(
                q, k, v, bias=b_, causal=causal, block_q=8, block_k=8
            ).astype(jnp.float32) ** 2)

        def tab_loss(q, k, v, t):
            return jnp.sum(flash_attention(
                q, k, v, causal=causal, block_q=8, block_k=8,
                rel_bias_table=t, rel_bias_buckets=buckets,
                rel_bias_max_dist=max_dist, rel_bias_bidirectional=bidir,
            ).astype(jnp.float32) ** 2)

        out_ref = flash_attention(
            q, k, v, bias=bias, causal=causal, block_q=8, block_k=8
        )
        out_tab = flash_attention(
            q, k, v, causal=causal, block_q=8, block_k=8,
            rel_bias_table=table, rel_bias_buckets=buckets,
            rel_bias_max_dist=max_dist, rel_bias_bidirectional=bidir,
        )
        np.testing.assert_allclose(
            np.asarray(out_tab), np.asarray(out_ref), atol=2e-6
        )
        gr = jax.grad(ref_loss, (0, 1, 2, 3))(q, k, v, table)
        gt = jax.grad(tab_loss, (0, 1, 2, 3))(q, k, v, table)
        for name, a, b_ in zip(("dq", "dk", "dv", "dtable"), gt, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-5,
                err_msg=name,
            )

    @pytest.mark.slow
    def test_t5_flash_bucket_bias_parity(self):
        from torchdistx_tpu.models import T5
        from torchdistx_tpu.nn import functional, functional_call

        tdx.manual_seed(15)
        a = tdx.deferred_init(T5.from_name, "tiny", use_flash=True)
        tdx.materialize_module(a)
        params = dict(a.named_parameters())
        bkt = T5.from_name("tiny", use_flash=True, flash_bucket_bias=True)
        bkt.load_state_dict(params)
        rs = np.random.RandomState(12)
        src = jnp.asarray(rs.randint(0, 256, (2, 32)), jnp.int32)
        tgt = jnp.asarray(rs.randint(0, 256, (2, 32)), jnp.int32)
        np.testing.assert_allclose(
            np.asarray(bkt(src, tgt)), np.asarray(a(src, tgt)),
            rtol=2e-4, atol=2e-4,
        )

        def loss(m, p):
            return functional.cross_entropy(
                functional_call(m, p, (src, tgt)), tgt
            )

        ga = jax.grad(lambda p: loss(a, p))(params)
        gb = jax.grad(lambda p: loss(bkt, p))(params)
        for k_ in ga:
            np.testing.assert_allclose(
                np.asarray(gb[k_]), np.asarray(ga[k_]),
                rtol=5e-4, atol=5e-5, err_msg=k_,
            )

    def test_rejects_bias_and_table_together(self):
        q, k, v, table, _ = self._setup()
        bias = jnp.zeros((4, 32, 32), jnp.float32)
        with pytest.raises(ValueError, match="not both"):
            flash_attention(q, k, v, bias=bias, rel_bias_table=table)

    def test_rejects_cross_shape(self):
        q, k, v, table, _ = self._setup()
        with pytest.raises(ValueError, match="Sq == Skv"):
            flash_attention(
                q[:, :16], k, v, causal=True, rel_bias_table=table
            )

    def test_bucket_bias_with_sp_rejected(self):
        from torchdistx_tpu.models import T5

        with pytest.raises(ValueError, match="flash_bucket_bias"):
            T5.from_name(
                "tiny", sp_axis="sp", flash_bucket_bias=True,
                use_flash=True,
            )


class TestSlidingWindow:
    """Mistral/Mixtral sliding-window attention: query i sees keys
    (i - window, i].  The kernel prunes out-of-band blocks at the grid
    level; forward, gradients, the jnp path, decode, and the model
    config must all agree."""

    @staticmethod
    def _ref(q, k, v, w):
        s, hq, d = q.shape[1], q.shape[2], q.shape[3]
        if k.shape[2] != hq:
            k = jnp.repeat(k, hq // k.shape[2], axis=2)
            v = jnp.repeat(v, hq // v.shape[2], axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(
            jnp.float32
        ) / np.sqrt(d)
        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        mask = (j <= i) & (j > i - w)
        logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, -1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    @pytest.mark.parametrize("hq,hkv,w", [(4, 4, 10), (8, 2, 16), (4, 4, 1)])
    def test_forward_and_grads_match_reference(self, hq, hkv, w):
        rs = np.random.RandomState(2)
        b, s, d = 2, 64, 16
        q = jnp.asarray(rs.randn(b, s, hq, d), jnp.float32)
        k = jnp.asarray(rs.randn(b, s, hkv, d), jnp.float32)
        v = jnp.asarray(rs.randn(b, s, hkv, d), jnp.float32)
        out = flash_attention(
            q, k, v, causal=True, window=w, block_q=8, block_k=8
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._ref(q, k, v, w)), atol=2e-6
        )

        def lf(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, window=w, block_q=8, block_k=8
            ).astype(jnp.float32) ** 2)

        def lr(q, k, v):
            return jnp.sum(self._ref(q, k, v, w).astype(jnp.float32) ** 2)

        gf = jax.grad(lf, (0, 1, 2))(q, k, v)
        gr = jax.grad(lr, (0, 1, 2))(q, k, v)
        for name, a, b_ in zip("qkv", gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-5,
                err_msg=f"d{name} w={w}",
            )

    def test_jnp_path_matches(self):
        rs = np.random.RandomState(3)
        q = jnp.asarray(rs.randn(1, 32, 2, 8), jnp.float32)
        out = multihead_attention(q, q, q, causal=True, window=6)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._ref(q, q, q, 6)), atol=2e-6
        )

    @pytest.mark.slow
    def test_llama_sliding_window_generate_matches_forward(self):
        # windowed decode through the KV cache must equal the windowed
        # full forward's next-token choices
        from torchdistx_tpu.generation import generate

        tdx.manual_seed(16)
        m = Llama.from_name("tiny", sliding_window=8, use_flash=False)
        toks = jnp.asarray(
            np.random.RandomState(4).randint(0, 256, (1, 12)), jnp.int32
        )
        out = generate(m, toks, max_new_tokens=6)
        # reference: recompute full windowed forward each step
        cur = toks
        for _ in range(6):
            logits = m(cur)
            nxt = jnp.argmax(logits[:, -1], -1)[:, None]
            cur = jnp.concatenate([cur, nxt.astype(cur.dtype)], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(cur))

    def test_validation(self):
        q = jnp.zeros((1, 16, 2, 8), jnp.float32)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, q, q, causal=False, window=4)
        with pytest.raises(ValueError, match="mutually exclusive"):
            flash_attention(
                q, q, q, causal=True, window=4,
                bias=jnp.zeros((2, 16, 16)),
            )
        with pytest.raises(ValueError, match="sliding_window"):
            Llama.from_name("tiny", sliding_window=8, sp_axis="sp")

    def test_windowed_flash_prefill(self):
        # cached_attention's flash-prefill branch with a window (padded
        # and unpadded prompt lengths) — interpret mode on CPU
        from torchdistx_tpu.ops.attention import cached_attention

        rs = np.random.RandomState(5)
        for s in (128, 100):  # 128 = no pad; 100 pads to the lane multiple
            q = jnp.asarray(rs.randn(1, s, 2, 8), jnp.float32)
            k = jnp.asarray(rs.randn(1, s, 2, 8), jnp.float32)
            v = jnp.asarray(rs.randn(1, s, 2, 8), jnp.float32)
            cache = (
                jnp.zeros((1, 160, 2, 8), jnp.float32),
                jnp.zeros((1, 160, 2, 8), jnp.float32),
            )
            out_flash, _ = cached_attention(
                q, k, v, cache, 0, use_flash=True, window=12
            )
            out_jnp, _ = cached_attention(
                q, k, v, cache, 0, use_flash=False, window=12
            )
            np.testing.assert_allclose(
                np.asarray(out_flash), np.asarray(out_jnp),
                rtol=2e-5, atol=2e-5, err_msg=f"s={s}",
            )

    def test_window_zero_rejected_everywhere(self):
        q = jnp.zeros((1, 16, 2, 8), jnp.float32)
        with pytest.raises(ValueError, match=">= 1"):
            flash_attention(q, q, q, causal=True, window=0)
        with pytest.raises(ValueError, match=">= 1"):
            multihead_attention(q, q, q, causal=True, window=0)
        with pytest.raises(ValueError, match=">= 1"):
            Llama.from_name("tiny", sliding_window=0)

    def test_windowed_decode_slice_matches_full_band(self):
        # the O(window) single-token decode slice must equal the full
        # max_seq band-mask computation at every cache position
        from torchdistx_tpu.ops.attention import cached_attention

        rs = np.random.RandomState(6)
        max_seq, w, h, d = 32, 8, 2, 8
        ck = jnp.asarray(rs.randn(1, max_seq, h, d), jnp.float32)
        cv = jnp.asarray(rs.randn(1, max_seq, h, d), jnp.float32)
        for pos in (0, 3, 7, 8, 20, max_seq - 1):
            q = jnp.asarray(rs.randn(1, 1, h, d), jnp.float32)
            kn = jnp.asarray(rs.randn(1, 1, h, d), jnp.float32)
            vn = jnp.asarray(rs.randn(1, 1, h, d), jnp.float32)
            # traced position (the generate() scan regime)
            out_w, _ = jax.jit(
                lambda q, kn, vn, p: cached_attention(
                    q, kn, vn, (ck, cv), p, use_flash=False, window=w
                )
            )(q, kn, vn, jnp.int32(pos))
            # full-band reference: window >= max_seq disables the slice
            ck2 = jax.lax.dynamic_update_slice(ck, kn, (0, pos, 0, 0))
            cv2 = jax.lax.dynamic_update_slice(cv, vn, (0, pos, 0, 0))
            logits = jnp.einsum(
                "bqhd,bkhd->bhqk", q, ck2
            ).astype(jnp.float32) / np.sqrt(d)
            j = jnp.arange(max_seq)
            vis = (j <= pos) & (j > pos - w)
            logits = jnp.where(vis[None, None, None], logits, -jnp.inf)
            ref = jnp.einsum(
                "bhqk,bkhd->bqhd",
                jax.nn.softmax(logits, -1).astype(q.dtype), cv2,
            )
            np.testing.assert_allclose(
                np.asarray(out_w), np.asarray(ref), rtol=2e-5, atol=2e-5,
                err_msg=f"pos={pos}",
            )


class TestStoredDtypeOperands:
    """Every matmul of the flash kernels takes its operands in the dtype
    the rows are stored in and accumulates in float32; the tiles between
    the matmuls (``p``, ``ds``) are rounded to that dtype where they meet
    stored rows, and nowhere else.  bf16 rows are held to a float32
    ``jax.numpy`` reference of the same (already rounded) inputs.

    The tolerances are what one bf16 rounding of ``p`` / ``ds`` and of
    the result allows.  An element rounds by at most 2**-9 of itself, the
    sum over keys of zero-mean values keeps that share (error and output
    shrink together), and the result rounds once more: over three seeds
    of the cases below the worst element read 0.30 % of its array's
    largest forward and 0.74 % backward.  The limits sit three times
    above that, and under what the NEXT precision down does: the
    reference with ``p`` rounded to float8_e4m3 (2**-4) read 2.1-3.8 %
    forward, and the forward's limit has to refuse it in every case."""

    FWD_TOL = 1e-2  # max |out - ref| over max |ref|
    GRAD_TOL = 2e-2

    #: name -> shapes, flash_attention's keywords, what is differentiated
    CASES = {
        # the train cell's shape class: equal widths, head 128, causal
        "mha128": dict(hq=2, hkv=2, d=128),
        "gqa4": dict(hq=8, hkv=2, d=64),  # n_rep 4: float32 dK/dV partials
        "one-kv-head": dict(hq=5, hkv=1, d=128),  # Jamba's 20 on 1
        "head256": dict(hq=4, hkv=1, d=256),  # Qwen3-Next's width
        "qk192-v128": dict(hq=2, hkv=2, d=192, dv=128, grads=False),  # MLA
        "window": dict(hq=4, hkv=2, d=64, kw=dict(window=40)),
        "bias": dict(hq=2, hkv=2, d=64, extra="bias"),  # dbias
        "bucket-table": dict(hq=2, hkv=2, d=64, extra="table"),  # dtable
    }
    B, S, BLOCK = 2, 128, dict(block_q=64, block_k=32)
    BUCKETS, MAX_DIST = 32, 128

    @classmethod
    def _inputs(cls, hq, hkv, d, dv=None, extra=None, seed=0, s=None, **_):
        rs = np.random.RandomState(seed)
        bf16 = lambda *s: jnp.asarray(rs.randn(*s), jnp.bfloat16)  # noqa: E731
        s = s or cls.S
        q = bf16(cls.B, s, hq, d)
        k = bf16(cls.B, s, hkv, d)
        v = bf16(cls.B, s, hkv, dv or d)
        g = bf16(cls.B, s, hq, dv or d)  # the cotangent
        if extra == "bias":
            return (q, k, v, bf16(hq, s, s)), g
        if extra == "table":
            return (q, k, v, bf16(hq, cls.BUCKETS) * 0.5), g
        return (q, k, v), g

    @classmethod
    def _bias_of(cls, table):
        from torchdistx_tpu.ops.flash_attention import rel_pos_bucket

        pos = jnp.arange(cls.S)
        bucket = rel_pos_bucket(
            pos[None, :] - pos[:, None], bidirectional=False,
            buckets=cls.BUCKETS, max_dist=cls.MAX_DIST,
        )
        return jnp.transpose(table.T[bucket], (2, 0, 1))

    @classmethod
    def _flash(cls, extra, kw):
        if extra == "table":
            kw = dict(kw, rel_bias_buckets=cls.BUCKETS,
                      rel_bias_max_dist=cls.MAX_DIST)
            return lambda q, k, v, t: flash_attention(
                q, k, v, rel_bias_table=t, **cls.BLOCK, **kw)
        if extra == "bias":
            return lambda q, k, v, b: flash_attention(
                q, k, v, bias=b, **cls.BLOCK, **kw)
        return lambda q, k, v: flash_attention(q, k, v, **cls.BLOCK, **kw)

    @classmethod
    def _reference(cls, extra, kw, p_dtype=None):
        """Float32 throughout, ``HIGHEST`` products; ``p_dtype`` plants
        the fault: the probabilities rounded to it before P.V."""
        window = kw.get("window")

        def ref(q, k, v, extra_arg=None):
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
            n_rep = q.shape[2] // k.shape[2]
            k, v = (jnp.repeat(x, n_rep, axis=2) for x in (k, v))
            logits = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k, precision="highest"
            ) / np.sqrt(q.shape[-1])
            if extra == "bias":
                logits = logits + extra_arg.astype(jnp.float32)[None]
            if extra == "table":
                logits = logits + cls._bias_of(
                    extra_arg.astype(jnp.float32))[None]
            i = jnp.arange(q.shape[1])[:, None]
            j = jnp.arange(q.shape[1])[None, :]
            mask = j <= i
            if window is not None:
                mask = mask & (j > i - window)
            p = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
            if p_dtype is not None:
                p = p.astype(p_dtype).astype(jnp.float32)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")

        return ref

    @staticmethod
    def _gap(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    @pytest.mark.parametrize("name", list(CASES))
    def test_bf16_rows_match_a_float32_reference(self, name):
        case = dict(self.CASES[name])
        kw, extra = case.pop("kw", {}), case.get("extra")
        args, g = self._inputs(**case)
        flash = self._flash(extra, kw)
        ref = self._reference(extra, kw)
        fp8 = self._reference(extra, kw, p_dtype=jnp.float8_e4m3fn)

        out = flash(*args)
        assert out.dtype == jnp.bfloat16
        gaps = {"out": self._gap(out, ref(*args))}
        if case.get("grads", True):
            def grads(fn):
                return jax.grad(
                    lambda *a: jnp.sum(
                        fn(*a).astype(jnp.float32) * g.astype(jnp.float32)),
                    argnums=tuple(range(len(args))),
                )(*args)

            names = ["dq", "dk", "dv"] + (["d" + extra] if extra else [])
            for n, a, b in zip(names, grads(flash), grads(ref)):
                assert a.dtype == jnp.bfloat16, n
                gaps[n] = self._gap(a, b)
        limits = {n: self.FWD_TOL if n == "out" else self.GRAD_TOL
                  for n in gaps}
        assert all(gaps[n] <= limits[n] for n in gaps), gaps
        # the same limit refuses the next precision down
        assert self._gap(out, fp8(*args)) > self.FWD_TOL

    def test_residuals_sum_the_float32_probabilities(self):
        """``return_residuals`` (ring attention's block): the raw float32
        accumulator, and ``l`` summed from the float32 ``p``, not from the
        tile rounded for P.V: ``l`` agrees with the reference to float32
        noise, and a sum of the rounded tile would not."""
        from torchdistx_tpu.ops.flash_attention import _flash_forward

        (q, k, v), _ = self._inputs(hq=4, hkv=2, d=64, seed=1)
        raw, m, l = _flash_forward(
            q, k, v, causal=True, return_residuals=True, interpret=True,
            **self.BLOCK,
        )
        assert raw.dtype == m.dtype == l.dtype == jnp.float32
        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
        k32, v32 = (jnp.repeat(x, 2, axis=2) for x in (k32, v32))
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q32, k32, precision="highest") / 8.0
        mask = jnp.tril(jnp.ones((self.S, self.S), bool))
        logits = jnp.where(mask, logits, -jnp.inf)
        m_ref = jnp.max(logits, axis=-1)
        p = jnp.exp(logits - m_ref[..., None])
        np.testing.assert_allclose(m, m_ref, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(l, p.sum(-1), rtol=2e-5)
        rounded = p.astype(jnp.bfloat16).astype(jnp.float32).sum(-1)
        assert float(jnp.max(jnp.abs(rounded / l - 1.0))) > 2e-5
        ref = jnp.einsum("bhqk,bkhd->bqhd", p, v32, precision="highest")
        assert self._gap(raw, ref) <= self.FWD_TOL

    @staticmethod
    def _kernels(fn, *args):
        """{kernel name: (grid, [(lhs dtype, rhs dtype, out dtype) of
        every ``dot_general`` inside])} over the ``pallas_call``s of
        ``fn``'s jaxpr."""
        found = {}

        def walk(jaxpr, kernel):
            for eqn in jaxpr.eqns:
                inside = kernel
                if eqn.primitive.name == "pallas_call":
                    inside = eqn.params["name"]
                    found[inside] = (
                        tuple(eqn.params["grid_mapping"].grid), [])
                if eqn.primitive.name == "dot_general" and kernel:
                    found[kernel][1].append(tuple(
                        str(x.aval.dtype) for x in (*eqn.invars, *eqn.outvars)
                    ))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, inside)

        walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
        return found

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    def test_every_kernel_product_takes_the_stored_dtype(self, dtype):
        """The reading that says the mechanism engaged: forward and both
        backward kernels (and the dbias kernel's recompute) hold only
        products of the INPUT dtype with a float32 result."""
        q, k, v, bias = (
            jnp.zeros(s, dtype) for s in
            [(1, 128, 4, 64), (1, 128, 2, 64), (1, 128, 2, 64), (4, 128, 128)]
        )

        def loss(q, k, v, b):
            return jnp.sum(flash_attention(
                q, k, v, bias=b, causal=True, interpret=True, **self.BLOCK
            ).astype(jnp.float32))

        kernels = self._kernels(jax.grad(loss, (0, 1, 2, 3)), q, k, v, bias)
        assert {n: len(dots) for n, (_, dots) in kernels.items()} == {
            "tdx_flash_forward": 2, "tdx_flash_backward_dkv": 4,
            "tdx_flash_backward_dq": 3, "tdx_flash_backward_dbias": 2,
        }
        for name, (_, dots) in kernels.items():
            assert set(dots) == {(dtype, dtype, "float32")}, name

    def test_rows_of_two_dtypes_meet_in_the_wider_one(self):
        # bf16 queries on a float32 cache: float32 products, as before
        (q, k, v), _ = self._inputs(hq=2, hkv=2, d=64, seed=2)
        k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        flash = self._flash(None, {})
        _, dots = self._kernels(flash, q, k, v)["tdx_flash_forward"]
        assert set(dots) == {("float32",) * 3}
        gap = self._gap(flash(q, k, v), self._reference(None, {})(q, k, v))
        assert gap <= self.FWD_TOL

    def test_tile_bounds_follow_the_rows_bytes_and_the_bias(self):
        """Where the caller names no bounds, rows of two bytes take
        1024 x 1024 tiles in all three kernels; float32 rows and either
        bias mode keep 256 x 512 (their tiles hold more bytes: a float32
        dK/dV tile of 1024 x 1024 does not fit the kernel's VMEM)."""
        def grids_of(dtype, bias=False):
            q = jnp.zeros((1, 2048, 2, 64), dtype)
            extra = (jnp.zeros((2, 2048, 2048), dtype),) if bias else ()

            def loss(q, k, v, *b):
                kw = {"bias": b[0]} if b else {}
                return jnp.sum(flash_attention(
                    q, k, v, causal=True, interpret=True, **kw
                ).astype(jnp.float32))

            kernels = self._kernels(
                jax.grad(loss, (0, 1, 2)), q, q, q, *extra)
            return {n.removeprefix("tdx_flash_"): grid
                    for n, (grid, _) in kernels.items()}

        large = {"forward": (2, 2, 2), "backward_dkv": (2, 2, 2),
                 "backward_dq": (2, 2, 2)}
        small = {"forward": (2, 8, 4), "backward_dkv": (2, 4, 8),
                 "backward_dq": (2, 8, 4)}
        assert grids_of(jnp.bfloat16) == large
        assert grids_of(jnp.float32) == small
        assert grids_of(jnp.bfloat16, bias=True) == {
            **small, "backward_dbias": (2, 8, 4, 1)}

    def test_the_large_tiles_match_the_reference(self):
        # 2048 rows in 1024 x 1024 tiles: a diagonal tile, a whole one
        # and a pruned one, held to the same limits as the small tiles
        args, g = self._inputs(hq=2, hkv=1, d=64, s=2048, seed=3)
        ref = self._reference(None, {})

        def grads(fn):
            return jax.grad(
                lambda *a: jnp.sum(
                    fn(*a).astype(jnp.float32) * g.astype(jnp.float32)),
                argnums=(0, 1, 2),
            )(*args)

        assert self._gap(flash_attention(*args), ref(*args)) <= self.FWD_TOL
        for n, a, b in zip(("dq", "dk", "dv"), grads(flash_attention),
                           grads(ref)):
            assert self._gap(a, b) <= self.GRAD_TOL, n

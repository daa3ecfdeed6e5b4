"""KV-cache generation: cached incremental decode must exactly reproduce
full-recompute greedy decoding, and the cached forward must equal the plain
forward position-for-position."""

import jax
import jax.numpy as jnp
import numpy as np

import pytest

import torchdistx_tpu as tdx
from torchdistx_tpu.generation import generate
from torchdistx_tpu.models import Llama
from torchdistx_tpu.nn import functional_call


def _model():
    tdx.manual_seed(0)
    return Llama.from_name("tiny", n_kv_heads=2, max_seq_len=64)


class TestCachedForward:
    def test_prefill_matches_plain_forward(self):
        m = _model()
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (2, 12)), jnp.int32
        )
        plain = m(tokens)
        cache = m.init_cache(2, 32)
        cached, _ = m.forward_cached(tokens, cache, 0)
        np.testing.assert_allclose(
            np.asarray(cached), np.asarray(plain), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.slow
    def test_incremental_matches_prefill(self):
        m = _model()
        rs = np.random.RandomState(1)
        tokens = jnp.asarray(rs.randint(0, 256, (1, 10)), jnp.int32)
        full = m(tokens)

        cache = m.init_cache(1, 16)
        logits, cache = m.forward_cached(tokens[:, :4], cache, 0)
        outs = [logits]
        for i in range(4, 10):
            logits, cache = m.forward_cached(tokens[:, i : i + 1], cache, i)
            outs.append(logits)
        inc = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(inc), np.asarray(full), rtol=3e-5, atol=3e-5
        )


class TestGenerate:
    @pytest.mark.slow
    def test_greedy_matches_full_recompute(self):
        m = _model()
        prompt = jnp.asarray(
            np.random.RandomState(2).randint(0, 256, (2, 6)), jnp.int32
        )
        out = generate(m, prompt, max_new_tokens=8)
        assert out.shape == (2, 14)
        np.testing.assert_array_equal(np.asarray(out[:, :6]), np.asarray(prompt))

        # naive full-recompute greedy reference
        ids = np.asarray(prompt)
        for _ in range(8):
            logits = np.asarray(m(jnp.asarray(ids)))
            ids = np.concatenate(
                [ids, logits[:, -1].argmax(-1, keepdims=True).astype(ids.dtype)],
                axis=1,
            )
        np.testing.assert_array_equal(np.asarray(out), ids)

    def test_sampling_deterministic_per_key(self):
        m = _model()
        prompt = jnp.zeros((1, 4), jnp.int32)
        a = generate(m, prompt, 6, temperature=0.8, key=jax.random.PRNGKey(7))
        b = generate(m, prompt, 6, temperature=0.8, key=jax.random.PRNGKey(7))
        c = generate(m, prompt, 6, temperature=0.8, key=jax.random.PRNGKey(8))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_sampling_requires_key(self):
        m = _model()
        import pytest

        with pytest.raises(ValueError, match="requires a PRNG key"):
            generate(m, jnp.zeros((1, 4), jnp.int32), 4, temperature=1.0)

    def test_zero_new_tokens_returns_prompt(self):
        m = _model()
        prompt = jnp.zeros((1, 4), jnp.int32)
        out = generate(m, prompt, 0)
        assert out is prompt

    def test_exceeding_max_seq_len_raises(self):
        import pytest

        m = _model()  # max_seq_len=64
        with pytest.raises(ValueError, match="maximum sequence length"):
            generate(m, jnp.zeros((1, 32), jnp.int32), 40)


class TestProfilingHelpers:
    def test_trace_and_memory_stats(self, tmp_path):
        import os

        from torchdistx_tpu.obs import get_tracer
        from torchdistx_tpu.utils import (
            device_memory_stats,
            format_memory_stats,
            trace,
        )

        with trace(str(tmp_path)):
            with get_tracer().span("probe"):
                jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
        files = sum(len(f) for _, _, f in os.walk(tmp_path))
        assert files > 0
        stats = device_memory_stats()
        assert isinstance(stats, dict) and stats
        assert isinstance(format_memory_stats(stats), str)


class TestGPT2Generate:
    """GPT-2 KV-cache decode (same generate() contract as Llama)."""

    @staticmethod
    def _model():
        from torchdistx_tpu.models import GPT2

        tdx.manual_seed(11)
        m = tdx.deferred_init(GPT2.from_name, "tiny")
        tdx.materialize_module(m)
        return m

    def test_cached_prefill_matches_plain_forward(self):
        m = self._model()
        params = dict(m.named_parameters())
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (2, 12)), jnp.int32
        )
        full = functional_call(m, params, (tokens,))
        cache = m.init_cache(2, 32)
        cached, _ = functional_call(
            m, params, (tokens, cache, 0), method="forward_cached"
        )
        np.testing.assert_allclose(
            np.asarray(full), np.asarray(cached), rtol=2e-5, atol=2e-5
        )

    def test_greedy_matches_full_recompute(self):
        m = self._model()
        prompt = jnp.asarray(
            np.random.RandomState(1).randint(0, 256, (1, 6)), jnp.int32
        )
        out = generate(m, prompt, max_new_tokens=6)
        # re-derive greedily with full forwards
        params = dict(m.named_parameters())
        cur = prompt
        for _ in range(6):
            logits = functional_call(m, params, (cur,))
            nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(cur.dtype)
            cur = jnp.concatenate([cur, nxt], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(cur))

    def test_limit_enforced(self):
        m = self._model()
        with pytest.raises(ValueError, match="maximum sequence length"):
            generate(m, jnp.zeros((1, 60), jnp.int32), 10)


class TestT5GenerateEncDec:
    """T5 encoder-decoder incremental decode (generate_encdec): greedy
    decode with the KV/cross cache must equal greedy decode by repeated
    full teacher-forced forwards."""

    @staticmethod
    def _model():
        from torchdistx_tpu.models import T5

        tdx.manual_seed(21)
        m = tdx.deferred_init(T5.from_name, "tiny")
        tdx.materialize_module(m)
        return m

    @pytest.mark.slow
    def test_greedy_matches_full_recompute(self):
        from torchdistx_tpu.generation import generate_encdec

        m = self._model()
        params = dict(m.named_parameters())
        enc_tokens = jnp.asarray(
            np.random.RandomState(2).randint(0, 256, (2, 9)), jnp.int32
        )
        n_new = 5
        out = generate_encdec(m, enc_tokens, n_new)
        assert out.shape == (2, n_new)

        # reference: greedy with full decoder forwards (teacher forcing)
        dec = jnp.zeros((2, 1), jnp.int32)  # start token 0
        for _ in range(n_new):
            logits = functional_call(m, params, (enc_tokens, dec))
            nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            dec = jnp.concatenate([dec, nxt], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(dec[:, 1:]))

    def test_sampling_seeded(self):
        from torchdistx_tpu.generation import generate_encdec

        m = self._model()
        enc = jnp.asarray(
            np.random.RandomState(3).randint(0, 256, (1, 6)), jnp.int32
        )
        a = generate_encdec(m, enc, 4, temperature=0.9, key=jax.random.PRNGKey(1))
        b = generate_encdec(m, enc, 4, temperature=0.9, key=jax.random.PRNGKey(1))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestSamplingFilters:
    def test_top_k_one_equals_greedy(self):
        m = _model()
        prompt = jnp.asarray([[3, 5, 7]], jnp.int32)
        greedy = generate(m, prompt, 6)
        topk1 = generate(
            m, prompt, 6, temperature=1.0, top_k=1, key=jax.random.PRNGKey(0)
        )
        np.testing.assert_array_equal(np.asarray(greedy), np.asarray(topk1))

    def test_top_p_one_equals_plain_sampling(self):
        m = _model()
        prompt = jnp.asarray([[2, 4]], jnp.int32)
        a = generate(m, prompt, 5, temperature=0.9, key=jax.random.PRNGKey(5))
        b = generate(
            m, prompt, 5, temperature=0.9, top_p=1.0, key=jax.random.PRNGKey(5)
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_filters_unit_semantics(self):
        from torchdistx_tpu.generation import _apply_top_k, _apply_top_p

        logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
        k2 = _apply_top_k(logits, 2)
        assert bool(jnp.isfinite(k2[0, 0])) and bool(jnp.isfinite(k2[0, 1]))
        assert not bool(jnp.isfinite(k2[0, 2])) and not bool(jnp.isfinite(k2[0, 3]))
        # nucleus 0.6: keep tokens whose preceding mass < 0.6 -> {0.5, 0.3}
        p6 = _apply_top_p(logits, 0.6)
        assert bool(jnp.isfinite(p6[0, 0])) and bool(jnp.isfinite(p6[0, 1]))
        assert not bool(jnp.isfinite(p6[0, 2]))
        # always keeps at least top-1
        p_tiny = _apply_top_p(logits, 1e-9)
        assert bool(jnp.isfinite(p_tiny[0, 0]))
        assert not bool(jnp.isfinite(p_tiny[0, 1]))

    def test_invalid_filter_args_raise_loudly(self):
        m = _model()
        p = jnp.zeros((1, 3), jnp.int32)
        with pytest.raises(ValueError, match="top_k"):
            generate(m, p, 2, temperature=1.0, top_k=0, key=jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="top_p"):
            generate(m, p, 2, temperature=1.0, top_p=0.0, key=jax.random.PRNGKey(0))
        # top_k larger than vocab clamps instead of crashing mid-trace
        out = generate(
            m, p, 2, temperature=1.0, top_k=10**6, key=jax.random.PRNGKey(0)
        )
        assert out.shape == (1, 5)


class TestFlashPrefill:
    """The from-empty prefill routes through the flash kernel when
    use_flash resolves on; parity vs the jnp cache path (interpret mode
    on CPU — exact)."""

    def test_cached_attention_flash_prefill_parity(self):
        from torchdistx_tpu.ops.attention import cached_attention

        rs = np.random.RandomState(6)
        b, s, hq, hkv, d, max_seq = 2, 16, 4, 2, 8, 32
        q = jnp.asarray(rs.randn(b, s, hq, d), jnp.float32)
        k = jnp.asarray(rs.randn(b, s, hkv, d), jnp.float32)
        v = jnp.asarray(rs.randn(b, s, hkv, d), jnp.float32)
        cache = (
            jnp.zeros((b, max_seq, hkv, d)),
            jnp.zeros((b, max_seq, hkv, d)),
        )
        out_jnp, (ck1, cv1) = cached_attention(
            q, k, v, cache, 0, use_flash=False
        )
        out_flash, (ck2, cv2) = cached_attention(
            q, k, v, cache, 0, use_flash=True
        )
        np.testing.assert_allclose(
            np.asarray(out_flash), np.asarray(out_jnp), rtol=2e-5, atol=2e-5
        )
        np.testing.assert_array_equal(np.asarray(ck1), np.asarray(ck2))
        np.testing.assert_array_equal(np.asarray(cv1), np.asarray(cv2))

    def test_traced_cache_pos_stays_on_jnp_path(self):
        # a TRACED cache_pos (mid-cache chunked prefill) must not take the
        # flash branch: its causal mask is end-aligned, not pos-aligned,
        # so at pos > 0 the two paths DIVERGE — chunk 2 must still see
        # chunk 1's cached keys
        from torchdistx_tpu.ops.attention import (
            cached_attention,
            multihead_attention,
        )

        rs = np.random.RandomState(7)
        b, s, hkv, d, max_seq = 1, 8, 2, 8, 32
        q = jnp.asarray(rs.randn(b, s, hkv, d), jnp.float32)
        k = jnp.asarray(rs.randn(b, s, hkv, d), jnp.float32)
        v = jnp.asarray(rs.randn(b, s, hkv, d), jnp.float32)
        cache = (
            jnp.zeros((b, max_seq, hkv, d)),
            jnp.zeros((b, max_seq, hkv, d)),
        )

        @jax.jit
        def two_chunks(pos):
            # chunk 1 at static 0, chunk 2 at TRACED pos — the traced call
            # must route to the jnp path even with use_flash=True
            _, c = cached_attention(
                q[:, :4], k[:, :4], v[:, :4], cache, 0, use_flash=True
            )
            out2, _ = cached_attention(
                q[:, 4:], k[:, 4:], v[:, 4:], c, pos, use_flash=True
            )
            return out2

        whole = multihead_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(two_chunks(jnp.int32(4))),
            np.asarray(whole[:, 4:]),
            rtol=2e-5,
            atol=2e-5,
        )

    def test_generate_with_flash_prefill_matches_full_recompute(self):
        tdx.manual_seed(8)
        m = Llama.from_name(
            "tiny", n_kv_heads=2, max_seq_len=64, use_flash=True
        )
        prompt = jnp.asarray(
            np.random.RandomState(9).randint(0, 256, (1, 10)), jnp.int32
        )
        out = generate(m, prompt, max_new_tokens=4)
        cur = prompt
        for _ in range(4):
            nxt = jnp.argmax(m(cur)[:, -1], axis=-1)[:, None]
            cur = jnp.concatenate([cur, nxt.astype(cur.dtype)], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(cur))

"""Int8 KV-cache quantization (serve/kv_cache.py + ops/decode_attention.py
+ ServeEngine(kv_dtype=)).

The load-bearing invariants, pinned on the 8-device CPU mesh:

- **Exact roundtrip**: scales are POWERS OF TWO (mantissa untouched), so
  ``quantize(dequantize(quantize(x)))`` is bit-stable — the chunked /
  persistent RMW loops (quantize on write, dequantize on read, every
  step) never re-round.  This is the reason the repo deviates from
  per-tensor float scales.
- **Kernel parity**: every quantized kernel branch (slab / paged, the
  block variants ride the engine tests) matches the jnp path computed on
  the DEQUANTIZED cache at the repo's ≤2-ulp interpret bar — quantization
  error lives entirely in the stored values, never in the kernel math.
- **Within-dtype bit-identity**: int8 streams are bit-identical across
  slab / paged / speculative engines (same stored values ⇒ same math);
  divergence exists only ACROSS dtypes and is pinned at the geometry
  under test.
- **Priced end-to-end**: ``memory_plan()`` halves the KV data component
  exactly vs bf16 and surfaces the scales; migration / handoff wire
  closed forms price each entry array at its own itemsize and stay
  exact against audit + counters; mixed-dtype moves refuse loudly.
- **No stale scales**: page reuse after retire cannot leak a previous
  request's scale rows (the int8 twin of the paged stale-row
  regression).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchdistx_tpu as tdx
from torchdistx_tpu.models import Llama
from torchdistx_tpu.obs.comm import CommProfile, comm_audit
from torchdistx_tpu.serve import ServeEngine, ServeFleet
from torchdistx_tpu.serve.kv_cache import (
    canonicalize_kv_dtype,
    dequantize_cache,
    dequantize_kv,
    merge_heads,
    quantize_cache,
    quantize_kv,
)

_ULP = 3e-7  # ~2 f32 ulps at unit scale (tests/test_decode_attention.py)


def _llama():
    tdx.manual_seed(0)
    return Llama.from_name("tiny", n_kv_heads=2, max_seq_len=64)


def _prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 256, (n,)).astype(np.int32) for n in lengths]


def _tp_mesh(tp):
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:tp]), ("tp",))


def _engine(tp=1, slots=3, paged=False, **kw):
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", (16,))
    if paged:
        kw.setdefault("page_size", 8)
        kw.setdefault("num_pages", 32)
    if tp > 1:
        kw["mesh"] = _tp_mesh(tp)
    return ServeEngine(_llama(), num_slots=slots, **kw)


class TestQuantizeRoundtrip:
    def test_scales_are_powers_of_two(self):
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(4, 7, 2, 8) * 13.0, jnp.float32)
        _, scale = quantize_kv(x)
        m, _ = np.frexp(np.asarray(scale))
        assert np.all(m == 0.5)  # exactly 2^e: mantissa is always 0.5

    def test_roundtrip_is_idempotent(self):
        """quantize -> dequantize -> quantize is a fixpoint: int8 times a
        power of two is exact in f32, so re-quantizing re-derives the
        same scale and the same codes.  THE invariant that lets the RMW
        decode loops requantize freely."""
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.randn(3, 5, 2, 8), jnp.float32)
        q1, s1 = quantize_kv(x)
        deq = dequantize_kv(q1, s1)
        q2, s2 = quantize_kv(deq)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        np.testing.assert_array_equal(
            np.asarray(deq), np.asarray(dequantize_kv(q2, s2))
        )

    def test_grid_covers_amax_and_clips(self):
        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.randn(2, 4, 1, 16) * 100.0, jnp.float32)
        q, scale = quantize_kv(x)
        assert q.dtype == jnp.int8
        assert scale.shape == x.shape[:-1] + (1,)
        q_np = np.asarray(q, np.int32)
        assert q_np.min() >= -127 and q_np.max() <= 127
        # relative error bounded by half a step: |x - q*s| <= s/2, and
        # s < 2*amax/127 by the pow-2 ceiling
        err = np.abs(np.asarray(x) - np.asarray(dequantize_kv(q, scale)))
        assert np.all(err <= np.asarray(scale) / 2 + 1e-9)

    def test_zero_rows_are_harmless(self):
        x = jnp.zeros((2, 3, 2, 8), jnp.float32)
        q, scale = quantize_kv(x)
        assert np.all(np.asarray(q) == 0)
        assert np.all(np.isfinite(np.asarray(scale)))
        np.testing.assert_array_equal(
            np.asarray(dequantize_kv(q, scale)), np.zeros_like(x)
        )

    def test_cache_helpers_and_passthrough(self):
        rs = np.random.RandomState(3)
        kv = [
            (
                jnp.asarray(rs.randn(2, 4, 2, 8), jnp.float32),
                jnp.asarray(rs.randn(2, 4, 2, 8), jnp.float32),
            )
        ]
        quant = quantize_cache(kv)
        assert len(quant[0]) == 4
        back = dequantize_cache(quant)
        assert len(back[0]) == 2
        # unquantized pairs pass through dequantize_cache untouched
        assert dequantize_cache(kv)[0][0] is kv[0][0]

    def test_canonicalize(self):
        assert canonicalize_kv_dtype(None) is None
        assert canonicalize_kv_dtype("int8") == "int8"
        with pytest.raises(ValueError):
            canonicalize_kv_dtype("int4")


class TestQuantizedKernelParity:
    """Kernel-vs-jnp on the DEQUANTIZED cache: the quantized kernel's
    only new math is ``q * scale`` in VMEM, so it must match the jnp
    path fed the dequantized arrays at the standard interpret bar."""

    def _quant_case(self, seed, b=3, hq=4, hkv=2, d=8, max_seq=16):
        rs = np.random.RandomState(seed)
        q = jnp.asarray(rs.randn(b, 1, hq, d), jnp.float32)
        ck = jnp.asarray(rs.randn(b, max_seq, hkv, d), jnp.float32)
        cv = jnp.asarray(rs.randn(b, max_seq, hkv, d), jnp.float32)
        qk, sk = quantize_kv(ck)
        qv, sv = quantize_kv(cv)
        pos = jnp.asarray(rs.randint(0, max_seq, (b,)), jnp.int32)
        return q, (qk, qv, sk, sv), pos

    @staticmethod
    def _stored(*arrays):
        """Model layout (…, Hkv, D) / scales (…, Hkv, 1) → the engine's
        stored layout, head tail merged."""
        return tuple(merge_heads(a) for a in arrays)

    def test_slab_kernel_matches_dequantized_jnp(self):
        from torchdistx_tpu.ops.attention import slot_cached_attention
        from torchdistx_tpu.ops.decode_attention import decode_attention

        q, (qk, qv, sk, sv), pos = self._quant_case(7)
        dk, dv = dequantize_kv(qk, sk), dequantize_kv(qv, sv)
        # post-write contract: re-write the row already AT ``pos`` so the
        # jnp path attends exactly the dequantized cache, bit for bit
        idx = pos[:, None, None, None]
        ref, (rk, _) = slot_cached_attention(
            q,
            jnp.take_along_axis(dk, idx, axis=1),
            jnp.take_along_axis(dv, idx, axis=1),
            self._stored(dk, dv),
            pos,
            use_flash=False,
        )
        np.testing.assert_array_equal(
            np.asarray(rk), np.asarray(merge_heads(dk))
        )
        qk, qv, sk, sv = self._stored(qk, qv, sk, sv)
        out = decode_attention(
            q, qk, qv, pos, k_scale=sk, v_scale=sv, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=_ULP, atol=_ULP
        )

    def test_paged_kernel_matches_dequantized_jnp(self):
        from torchdistx_tpu.ops.attention import slot_cached_attention
        from torchdistx_tpu.ops.decode_attention import (
            paged_decode_attention,
        )

        rs = np.random.RandomState(11)
        b, hq, hkv, d, pp, ps = 3, 4, 2, 8, 8, 4
        q = jnp.asarray(rs.randn(b, 1, hq, d), jnp.float32)
        ck = jnp.asarray(rs.randn(pp, ps, hkv, d), jnp.float32)
        cv = jnp.asarray(rs.randn(pp, ps, hkv, d), jnp.float32)
        qk, sk = quantize_kv(ck)
        qv, sv = quantize_kv(cv)
        tables = jnp.asarray(
            np.stack([rs.permutation(pp)[: pp // 2] for _ in range(b)]),
            jnp.int32,
        )
        pos = jnp.asarray(rs.randint(0, (pp // 2) * ps, (b,)), jnp.int32)
        dk, dv = dequantize_kv(qk, sk), dequantize_kv(qv, sv)
        # jnp reference: gather the dequantized pages into slab layout,
        # then no-op-rewrite the row at ``pos`` (post-write contract)
        slab_k = dk[tables].reshape(b, -1, hkv, d)
        slab_v = dv[tables].reshape(b, -1, hkv, d)
        idx = pos[:, None, None, None]
        ref, _ = slot_cached_attention(
            q,
            jnp.take_along_axis(slab_k, idx, axis=1),
            jnp.take_along_axis(slab_v, idx, axis=1),
            self._stored(slab_k, slab_v),
            pos,
            use_flash=False,
        )
        qk, qv, sk, sv = self._stored(qk, qv, sk, sv)
        out = paged_decode_attention(
            q, qk, qv, tables, pos, k_scale=sk, v_scale=sv, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=_ULP, atol=_ULP
        )

    def test_scales_must_come_together_and_shaped(self):
        from torchdistx_tpu.ops.decode_attention import decode_attention

        q, quant, pos = self._quant_case(13)
        qk, qv, sk, sv = self._stored(*quant)
        with pytest.raises(ValueError, match="together"):
            decode_attention(q, qk, qv, pos, k_scale=sk, interpret=True)
        with pytest.raises(ValueError, match="scale shapes"):
            decode_attention(
                q, qk, qv, pos, k_scale=sk[..., :1], v_scale=sv[..., :1],
                interpret=True,
            )
        with pytest.raises(ValueError, match="stored layout"):
            decode_attention(q, *quant[:2], pos, interpret=True)


class TestQuantizedEngine:
    def test_streams_pinned_and_internally_bit_identical(self):
        """Divergence exists only ACROSS dtypes (pinned at this
        geometry: 4/5 greedy streams identical to f32); WITHIN int8 the
        slab, paged and speculative engines are bit-identical — same
        stored values, same kernels, same math."""
        prompts = _prompts(0, (6, 11, 9, 4, 13))
        reqs = [{"prompt": p, "max_new_tokens": 12} for p in prompts]
        t_f32 = [list(r.tokens) for r in _engine().run(reqs)]
        t_i8 = [
            list(r.tokens)
            for r in _engine(kv_dtype="int8").run(reqs)
        ]
        agree = sum(a == b for a, b in zip(t_i8, t_f32))
        assert agree >= 4  # deterministic at this seed; 5 exceeds spec
        t_paged = [
            list(r.tokens)
            for r in _engine(paged=True, kv_dtype="int8").run(reqs)
        ]
        t_spec = [
            list(r.tokens)
            for r in _engine(speculate=2, kv_dtype="int8").run(reqs)
        ]
        assert t_paged == t_i8
        assert t_spec == t_i8

    def test_memory_plan_halves_and_names_dtype(self):
        e_i8 = _engine(kv_dtype="int8")
        e_bf = _engine(kv_dtype="bfloat16")
        e_f32 = _engine()
        p_i8, p_bf, p_f32 = (
            e.memory_plan() for e in (e_i8, e_bf, e_f32)
        )
        assert p_i8["components"]["kv_cache"] * 2 == (
            p_bf["components"]["kv_cache"]
        )
        assert p_i8["components"]["kv_cache"] * 4 == (
            p_f32["components"]["kv_cache"]
        )
        assert p_i8["components"]["kv_scales"] > 0
        assert p_i8["kv_cache_dtype"] == "int8"
        # default plans: unchanged surface — data-only equals the cache
        # nbytes, no scales line, dtype named
        for e, p in ((e_bf, p_bf), (e_f32, p_f32)):
            assert "kv_scales" not in p["components"]
            assert p["components"]["kv_cache"] == e.cache.nbytes
        assert p_f32["kv_cache_dtype"] == "float32"

    def test_metrics_gauges_survive_reset(self):
        """``kv_cache_bytes`` is the TOTAL resident pool — int8 data
        plus the f32 scale sidecar — and the split reconciles exactly
        with the cache's own accounting."""
        e = _engine(kv_dtype="int8")
        g = e.metrics.to_json()["gauges"]
        assert g["kv_cache_bytes"] == e.cache.nbytes
        assert e.cache.nbytes == (
            e.cache.kv_data_nbytes + e.cache.kv_scale_nbytes
        )
        rows = e.num_slots * e.max_len
        assert g["kv_bytes_per_token"] == e.cache.nbytes // rows
        # int8 data is exactly a quarter of the f32 pool, and the total
        # stays under half of it even with the f32 sidecar riding
        f32 = _engine()
        g_f32 = f32.metrics.to_json()["gauges"]
        assert e.cache.kv_data_nbytes * 4 == f32.cache.nbytes
        assert g["kv_cache_bytes"] * 2 < g_f32["kv_cache_bytes"]
        e.reset_metrics()
        g2 = e.metrics.to_json()["gauges"]
        assert g2["kv_cache_bytes"] == g["kv_cache_bytes"]
        assert g2["kv_bytes_per_token"] == g["kv_bytes_per_token"]

    def test_static_key_separates_dtypes(self):
        assert (
            _engine(kv_dtype="int8")._static_key()
            != _engine()._static_key()
        )

    def test_submit_rejection_names_cache_dtype(self):
        e = _engine(paged=True, num_pages=4, kv_dtype="int8")
        # fits max_len (44 <= 64) and the prefill bucket (14 <= 16) but
        # needs 6 pages of 8 against a 4-page pool
        with pytest.raises(ValueError, match="int8 cache pool"):
            e.submit(_prompts(1, (14,))[0], max_new_tokens=30)

    def test_no_stale_scales_across_page_reuse(self):
        """The int8 twin of the paged stale-row regression
        (tests/test_prefix_cache.py): retire a LONG request, admit a
        SHORTER one onto its freed pages — stale SCALE rows beyond the
        new request's depth must not perturb the stream."""
        model = _llama()
        long_p, short_p = _prompts(3, (40, 6))
        engine = ServeEngine(
            model, num_slots=1, max_len=64, page_size=8,
            num_pages=8, prefix_cache=False, kv_dtype="int8",
        )
        engine.run([{"prompt": long_p, "max_new_tokens": 8}])
        assert engine.pool.in_use == 0
        got = engine.run([{"prompt": short_p, "max_new_tokens": 8}])[0]
        fresh = ServeEngine(
            model, num_slots=1, max_len=64, page_size=8,
            num_pages=8, prefix_cache=False, kv_dtype="int8",
        ).run([{"prompt": short_p, "max_new_tokens": 8}])[0]
        np.testing.assert_array_equal(got.tokens, fresh.tokens)


class TestKVQuantNumerics:
    """ISSUE 19 satellite: the numerics observatory's KV dequant-error
    digests feed ``kv_quant_err_max`` / ``kv_quant_err_rms`` gauges
    (int8 pools only), and the observed max is pinned by the power-of-
    two quantizer's round-to-nearest bound ``s/2``."""

    def _run(self, **kw):
        e = _engine(kv_dtype="int8", numerics=True, **kw)
        e.run(
            [
                {"prompt": p, "max_new_tokens": 8, "temperature": 0.0}
                for p in _prompts(11, (5, 9, 12))
            ]
        )
        return e

    @pytest.mark.parametrize("paged", [False, True])
    def test_err_max_pinned_by_half_scale(self, paged):
        e = self._run(paged=paged)
        book = e.numerics_book
        err = book.digest("kv_quant_err")
        scale = book.digest("kv_quant_scale")
        assert err is not None and err.count > 0
        assert err.nonfinite == 0
        # round-to-nearest int8 against a power-of-two scale: every
        # dequant error is <= s/2 with s the LARGEST scale the write
        # sites produced (max_abs of the scale digest) — tiny float
        # headroom only for the digest's own f32 max reduction
        bound = 0.5 * scale.max_abs
        assert err.max_abs <= bound * (1 + 1e-6), (err.max_abs, bound)
        g = e.metrics.to_json()["gauges"]
        assert g["kv_quant_err_max"] == err.max_abs
        assert g["kv_quant_err_max"] <= bound * (1 + 1e-6)
        assert 0 < g["kv_quant_err_rms"] <= g["kv_quant_err_max"]

    def test_gauges_survive_reset_metrics(self):
        e = self._run()
        g = e.metrics.to_json()["gauges"]
        e.reset_metrics()
        g2 = e.metrics.to_json()["gauges"]
        assert g2["kv_quant_err_max"] == g["kv_quant_err_max"]
        assert g2["kv_quant_err_rms"] == g["kv_quant_err_rms"]

    def test_gauges_int8_pools_only(self):
        # plain bf16/f32 caches have no quantizer, hence no error gauge
        # family — even with the observatory on
        e = _engine(numerics=True)
        e.run([{"prompt": _prompts(11, (5,))[0], "max_new_tokens": 4}])
        g = e.metrics.to_json()["gauges"]
        assert "kv_quant_err_max" not in g
        assert "kv_quant_err_rms" not in g


class TestQuantizedMoves:
    def _reqs(self):
        prompts = _prompts(7, (6, 9, 5, 11))
        mnt = [8, 10, 12, 6]
        return [
            {"prompt": p, "max_new_tokens": m}
            for p, m in zip(prompts, mnt)
        ]

    @staticmethod
    def _entry_wire_bytes(entry, g):
        """The per-layer closed form: each array of the entry tuple —
        int8 data AND f32 scales — priced at its own itemsize through
        the ring all-gather, ``unit * (g-1) // g``."""
        total = 0
        for arr in entry:
            unit = int(np.prod(arr.shape[1:])) * np.dtype(arr.dtype).itemsize
            total += unit * (g - 1) // g
        return total

    def test_migration_scales_ride_and_wire_is_exact(self):
        reqs = self._reqs()
        ref = [r.tokens for r in _engine(tp=2, kv_dtype="int8").run(reqs)]
        src = _engine(tp=2, kv_dtype="int8", decode_chunk=2)
        dst = _engine(tp=1, slots=4, kv_dtype="int8", decode_chunk=2)
        handles = [
            src.submit(r["prompt"], max_new_tokens=r["max_new_tokens"])
            for r in reqs
        ]
        for _ in range(2):
            src.step()
        src.drain()
        prof = CommProfile()
        with comm_audit(prof):
            summary = src.migrate_to(dst)
        while dst.step():
            pass
        for h, r in zip(handles, ref):
            np.testing.assert_array_equal(h.result().tokens, r)
        n_moved = summary["migrated_running"]
        expect = (
            n_moved
            * len(src.cache.kv)
            * self._entry_wire_bytes(src.cache.kv[0], 2)
        )
        assert summary["wire_bytes"] == expect
        assert int(prof.wire_bytes("all_gather", "tp")) == expect
        assert src.metrics.counters["migration_wire_bytes"] == expect
        # int8 moves strictly fewer bytes than the same scenario in bf16
        src2 = _engine(tp=2, kv_dtype="bfloat16", decode_chunk=2)
        dst2 = _engine(tp=1, slots=4, kv_dtype="bfloat16", decode_chunk=2)
        for r in reqs:
            src2.submit(r["prompt"], max_new_tokens=r["max_new_tokens"])
        for _ in range(2):
            src2.step()
        src2.drain()
        assert summary["wire_bytes"] < src2.migrate_to(dst2)["wire_bytes"]

    def test_migrate_dtype_mismatch_refused(self):
        a = _engine(slots=2, kv_dtype="int8")
        b = _engine(slots=2)
        with pytest.raises(RuntimeError, match="KV dtype mismatch"):
            a.migrate_to(b)

    def test_disagg_handoff_scales_ride_and_wire_is_exact(self):
        rs = np.random.RandomState(13)
        prefix = rs.randint(0, 256, (16,)).astype(np.int32)
        reqs = [
            {
                "prompt": np.concatenate(
                    [prefix, rs.randint(0, 256, (4,)).astype(np.int32)]
                ),
                "max_new_tokens": m,
            }
            for m in (6, 8, 6, 8)
        ]
        ref = _engine(
            slots=4, prefill_buckets=(32,), kv_dtype="int8"
        ).run(reqs)
        pre = _engine(
            tp=2, slots=4, prefill_buckets=(32,), kv_dtype="int8"
        )
        dec = _engine(
            slots=4, prefill_buckets=(32,), kv_dtype="int8"
        )
        fleet = ServeFleet(
            [pre, dec], disaggregate=True, roles=["prefill", "decode"]
        )
        prof = CommProfile()
        with comm_audit(prof):
            out = fleet.run(reqs)
        for r, o in zip(ref, out):
            np.testing.assert_array_equal(o.tokens, r.tokens)
        expect = (
            len(reqs)
            * len(pre.cache.kv)
            * TestQuantizedMoves._entry_wire_bytes(pre.cache.kv[0], 2)
        )
        got = pre.metrics.counters["handoff_wire_bytes"]
        assert got == expect
        assert int(prof.wire_bytes("all_gather", "tp")) == expect

    def test_handoff_dtype_mismatch_refused(self):
        pre = _engine(slots=2, kv_dtype="int8")
        dec = _engine(slots=2)
        fleet = ServeFleet([pre, dec], disaggregate=True)
        with pytest.raises(RuntimeError, match="KV dtype mismatch"):
            fleet.run(
                [
                    {
                        "prompt": _prompts(15, (8,))[0],
                        "max_new_tokens": 2,
                    }
                ]
            )

"""Pallas slot-paged decode attention (ops/decode_attention.py).

Exactness bar (kernel docstring): interpret mode is exact math modulo
floating-point association — the probabilities match the jnp path's
``jax.nn.softmax`` op order bitwise; the final P@V contraction reduction
is associated differently by XLA's batched-einsum emitter than by any
per-(slot, head) kernel dot, measured <= 2 f32 ulps.  Tests pin that bar
(atol/rtol ~1 ulp), far tighter than the flash-attention interpret
tolerance (2e-5), against ``slot_cached_attention``'s jnp path for
single-block AND multi-block configurations, all GQA widths, and the
position edges.  Engine-level BIT-identity of fused-vs-sequential decode
is pinned in tests/test_serve.py (both sides share this kernel).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchdistx_tpu.ops.attention import slot_cached_attention
from torchdistx_tpu.ops.decode_attention import (
    _LANES,
    _MIN_BLOCK_K,
    _VMEM_BUDGET,
    _blocking,
    _vmem_bytes,
    decode_attention,
    paged_decode_attention,
)
from torchdistx_tpu.serve.kv_cache import (
    merge_heads,
    quantize_kv,
    split_heads,
)

_ULP = 3e-7  # ~2 f32 ulps at unit scale


def _case(rs, b, hq, hkv, d, max_seq, positions, dtype=jnp.float32):
    """New rows as the model makes them, (B, 1, H, D); the cache as the
    engine stores it, (B, max_seq, Hkv * D)."""
    q = jnp.asarray(rs.randn(b, 1, hq, d), dtype)
    k = jnp.asarray(rs.randn(b, 1, hkv, d), dtype)
    v = jnp.asarray(rs.randn(b, 1, hkv, d), dtype)
    cache = (
        merge_heads(jnp.asarray(rs.randn(b, max_seq, hkv, d), dtype)),
        merge_heads(jnp.asarray(rs.randn(b, max_seq, hkv, d), dtype)),
    )
    return q, k, v, cache, jnp.asarray(positions, jnp.int32)


class TestKernelMatchesReference:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 2), (16, 1)])
    def test_single_block_matches_jnp_path(self, hq, hkv):
        rs = np.random.RandomState(hq * 10 + hkv)
        b, d, max_seq = 3, 8, 16
        q, k, v, cache, pos = _case(
            rs, b, hq, hkv, d, max_seq, rs.randint(0, max_seq, (b,))
        )
        ref, (rk, rv) = slot_cached_attention(
            q, k, v, cache, pos, use_flash=False
        )
        out = decode_attention(q, rk, rv, pos, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=_ULP, atol=_ULP
        )

    @pytest.mark.parametrize("block_k", [8, 16])
    def test_multi_block_online_softmax_matches(self, block_k):
        rs = np.random.RandomState(block_k)
        b, hq, hkv, d, max_seq = 3, 4, 2, 8, 64
        # positions straddling block edges: first block only, exact edge,
        # mid-block, last row
        q, k, v, cache, pos = _case(
            rs, b, hq, hkv, d, max_seq,
            [block_k - 1, block_k, max_seq - 1],
        )
        ref, (rk, rv) = slot_cached_attention(
            q, k, v, cache, pos, use_flash=False
        )
        out = decode_attention(q, rk, rv, pos, block_k=block_k, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=_ULP, atol=_ULP
        )

    def test_position_zero_and_full_row(self):
        rs = np.random.RandomState(0)
        b, hq, hkv, d, max_seq = 2, 4, 2, 8, 32
        q, k, v, cache, pos = _case(rs, b, hq, hkv, d, max_seq, [0, 31])
        ref, (rk, rv) = slot_cached_attention(
            q, k, v, cache, pos, use_flash=False
        )
        out = decode_attention(q, rk, rv, pos, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=_ULP, atol=_ULP
        )

    @pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
    @pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
    def test_head_per_lane_block_layout_matches(self, paged, quantized):
        """head_dim 128 — the real width, where a KV head is a whole
        128-lane tile of the stored (Hkv * D) tail and the kernel's
        static lane slices are tile-aligned (until PR 33 the grid picked
        one head's lane block a step; TestFoldedGrid has the wider
        cases).  Multi-block rows, GQA, and the int8 one-hot
        scale-column select, against the jnp path."""
        from torchdistx_tpu.serve.kv_cache import dequantize_kv, quantize_kv

        rs = np.random.RandomState(128 + 2 * paged + quantized)
        b, hq, hkv, d, ps, pp = 2, 4, 2, 128, 8, 4
        q = jnp.asarray(rs.randn(b, 1, hq, d), jnp.float32)
        pos = jnp.asarray([5, ps * pp - 1], jnp.int32)
        slab = [
            jnp.asarray(rs.randn(b, ps * pp, hkv, d), jnp.float32)
            for _ in range(2)
        ]
        scales = {}
        if quantized:
            (slab[0], ks), (slab[1], vs) = map(quantize_kv, slab)
            scales = dict(k_scale=ks, v_scale=vs)
            dense = [dequantize_kv(slab[0], ks), dequantize_kv(slab[1], vs)]
        else:
            dense = slab
        from torchdistx_tpu.ops.attention import _slot_attend

        ref = _slot_attend(q, dense[0], dense[1], pos, None, None)
        # the kernels take the stored layout: head tails merged
        slab = [merge_heads(c) for c in slab]
        scales = {n: merge_heads(x) for n, x in scales.items()}
        if paged:
            # the same rows as pages, in a shuffled pool order
            order = rs.permutation(b * pp)
            tables = jnp.asarray(
                np.argsort(order).reshape(b, pp), jnp.int32
            )
            to_pool = lambda c: c.reshape(b * pp, ps, *c.shape[2:])[order]
            out = paged_decode_attention(
                q, to_pool(slab[0]), to_pool(slab[1]), tables, pos,
                interpret=True,
                **{n: to_pool(x) for n, x in scales.items()},
            )
        else:
            out = decode_attention(
                q, slab[0], slab[1], pos, block_k=ps, interpret=True,
                **scales,
            )
        # 128-term f32 dots and a 4-block online-softmax merge, outputs
        # up to ~3: a few ulps at that scale (measured <= 8.4e-7)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-6, atol=2e-6
        )

    def test_bf16_inputs(self):
        rs = np.random.RandomState(5)
        b, hq, hkv, d, max_seq = 2, 4, 2, 8, 16
        q, k, v, cache, pos = _case(
            rs, b, hq, hkv, d, max_seq, [3, 12], dtype=jnp.bfloat16
        )
        ref, (rk, rv) = slot_cached_attention(
            q, k, v, cache, pos, use_flash=False
        )
        out = decode_attention(q, rk, rv, pos, interpret=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2,
        )


class TestRouting:
    def test_slot_cached_attention_routes_to_kernel(self):
        """use_flash=True takes the kernel path end to end: identical
        cache writes, output within the kernel tolerance."""
        rs = np.random.RandomState(1)
        q, k, v, cache, pos = _case(rs, 3, 4, 2, 8, 16, [2, 9, 5])
        ref, (rk, rv) = slot_cached_attention(
            q, k, v, cache, pos, use_flash=False
        )
        out, (fk, fv) = slot_cached_attention(
            q, k, v, cache, pos, use_flash=True
        )
        np.testing.assert_array_equal(np.asarray(fk), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(rv))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=_ULP, atol=_ULP
        )

    def test_windowed_decode_stays_on_jnp_path(self):
        """The kernel has no sliding-window mode: window= must fall back
        to the jnp band path bit-for-bit even with use_flash on."""
        rs = np.random.RandomState(2)
        q, k, v, cache, pos = _case(rs, 2, 4, 2, 8, 16, [5, 11])
        ref, _ = slot_cached_attention(
            q, k, v, cache, pos, window=4, use_flash=False
        )
        out, _ = slot_cached_attention(
            q, k, v, cache, pos, window=4, use_flash=True
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_auto_resolution_off_tpu_is_jnp(self):
        """resolve_use_flash(None) off-TPU keeps the jnp path: the
        default engine on the CPU mesh stays on its pinned bit-exact
        decode."""
        rs = np.random.RandomState(3)
        q, k, v, cache, pos = _case(rs, 2, 4, 2, 8, 16, [5, 11])
        auto, _ = slot_cached_attention(q, k, v, cache, pos)
        ref, _ = slot_cached_attention(q, k, v, cache, pos, use_flash=False)
        if jax.devices()[0].platform == "tpu":
            pytest.skip("auto resolves to the kernel on TPU")
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))

    def test_rejects_multi_token(self):
        rs = np.random.RandomState(4)
        q = jnp.asarray(rs.randn(2, 2, 4, 8), jnp.float32)
        ck = jnp.asarray(rs.randn(2, 16, 2 * 8), jnp.float32)
        with pytest.raises(ValueError, match="one token per slot"):
            decode_attention(q, ck, ck, jnp.zeros((2,), jnp.int32))

    def test_rejects_indivisible_heads(self):
        rs = np.random.RandomState(4)
        q = jnp.asarray(rs.randn(2, 1, 3, 8), jnp.float32)
        ck = jnp.asarray(rs.randn(2, 16, 2 * 8), jnp.float32)
        with pytest.raises(ValueError, match="not a multiple"):
            decode_attention(q, ck, ck, jnp.zeros((2,), jnp.int32))


def _paged_case(rs, b, hq, hkv, d, pp, ps, positions, dtype=jnp.float32):
    """Pools + a shuffled page-table (identity mappings would let a
    kernel that ignores the table pass) + per-slot new K/V."""
    num_pages = b * pp + 1  # page 0 stays scratch, like the engine's pool
    q = jnp.asarray(rs.randn(b, 1, hq, d), dtype)
    k = jnp.asarray(rs.randn(b, 1, hkv, d), dtype)
    v = jnp.asarray(rs.randn(b, 1, hkv, d), dtype)
    pools = (  # as the engine stores them: (num_pages, ps, Hkv * D)
        merge_heads(jnp.asarray(rs.randn(num_pages, ps, hkv, d), dtype)),
        merge_heads(jnp.asarray(rs.randn(num_pages, ps, hkv, d), dtype)),
    )
    tables = 1 + rs.permutation(b * pp).reshape(b, pp).astype(np.int32)
    return (
        q, k, v, pools,
        jnp.asarray(tables), jnp.asarray(positions, jnp.int32),
    )


class TestPagedKernel:
    """paged_decode_attention vs the jnp paged path (page-table gather +
    the shared _slot_attend math) — same exactness bar as the slot
    kernel: single-page rows bitwise-softmax (<= ULP overall), multi-page
    rows the online-softmax merge at <= 2 f32 ulps."""

    def _ref_and_kernel(self, q, k, v, pools, tables, pos):
        ref, (rk, rv) = slot_cached_attention(
            q, k, v, pools, pos, use_flash=False, page_tables=tables
        )
        out = paged_decode_attention(q, rk, rv, tables, pos, interpret=True)
        return np.asarray(ref), np.asarray(out), (rk, rv)

    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 2), (16, 1)])
    def test_single_page_matches_jnp_path(self, hq, hkv):
        rs = np.random.RandomState(hq * 10 + hkv)
        b, d, ps = 3, 8, 16
        case = _paged_case(rs, b, hq, hkv, d, 1, ps, rs.randint(0, ps, (b,)))
        ref, out, _ = self._ref_and_kernel(*case)
        np.testing.assert_allclose(out, ref, rtol=_ULP, atol=_ULP)

    @pytest.mark.parametrize("ps", [8, 16])
    def test_multi_page_online_softmax_matches(self, ps):
        rs = np.random.RandomState(ps)
        b, hq, hkv, d, pp = 4, 4, 2, 8, 4
        # positions straddling page edges: first page only, exact edge,
        # mid-chain, last row
        case = _paged_case(
            rs, b, hq, hkv, d, pp, ps,
            [ps - 1, ps, 2 * ps + 3, pp * ps - 1],
        )
        ref, out, _ = self._ref_and_kernel(*case)
        np.testing.assert_allclose(out, ref, rtol=_ULP, atol=_ULP)

    def test_matches_contiguous_layout_bitwise_on_jnp_path(self):
        """The jnp paged path IS the slab path behind a gather: build a
        slab holding exactly what the page chains spell and pin the
        outputs (and written rows) bit-for-bit."""
        rs = np.random.RandomState(5)
        b, hq, hkv, d, pp, ps = 3, 4, 2, 8, 4, 8
        q, k, v, pools, tables, pos = _paged_case(
            rs, b, hq, hkv, d, pp, ps, [3, 17, 30]
        )
        slab = tuple(
            jnp.stack([p.reshape(-1, hkv * d)[
                (np.asarray(tables[row])[:, None] * ps
                 + np.arange(ps)[None, :]).reshape(-1)
            ] for row in range(b)])
            for p in pools
        )
        want, _ = slot_cached_attention(
            q, k, v, slab, pos, use_flash=False
        )
        got, (gk, gv) = slot_cached_attention(
            q, k, v, pools, pos, use_flash=False, page_tables=tables
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # the write landed at page tables[b, pos//ps], offset pos%ps
        for row, p in enumerate([3, 17, 30]):
            page = int(tables[row, p // ps])
            np.testing.assert_array_equal(
                np.asarray(gk[page, p % ps]),
                np.asarray(k[row, 0]).reshape(-1),
            )

    def test_routing_through_slot_cached_attention(self):
        rs = np.random.RandomState(6)
        q, k, v, pools, tables, pos = _paged_case(
            rs, 2, 4, 2, 8, 2, 16, [5, 20]
        )
        ref, (rk, rv) = slot_cached_attention(
            q, k, v, pools, pos, use_flash=False, page_tables=tables
        )
        out, (fk, fv) = slot_cached_attention(
            q, k, v, pools, pos, use_flash=True, page_tables=tables
        )
        np.testing.assert_array_equal(np.asarray(fk), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(rv))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=_ULP, atol=_ULP
        )

    def test_tiny_pages_take_the_kernel(self):
        """Pages below the f32 sublane height feed the kernel too: the
        page is the K/V block's whole (page_size, D) extent, which
        Mosaic accepts at any size (tests/test_chip_compile.py compiles
        16; 1-4 were compiled by hand in PR 24), so there is no tiny-page
        route to the gather path any more — multi-page rows match the
        jnp path at the kernel's usual <= 2-ulp bar."""
        rs = np.random.RandomState(7)
        q, k, v, pools, tables, pos = _paged_case(
            rs, 2, 4, 2, 8, 4, 4, [3, 11]
        )
        ref, _ = slot_cached_attention(
            q, k, v, pools, pos, use_flash=False, page_tables=tables
        )
        out, _ = slot_cached_attention(
            q, k, v, pools, pos, use_flash=True, page_tables=tables
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=_ULP, atol=_ULP
        )

    def test_rejects_bad_shapes(self):
        rs = np.random.RandomState(8)
        q = jnp.asarray(rs.randn(2, 2, 4, 8), jnp.float32)
        pool = jnp.asarray(rs.randn(5, 16, 2 * 8), jnp.float32)
        pt = jnp.zeros((2, 2), jnp.int32)
        with pytest.raises(ValueError, match="one token per slot"):
            paged_decode_attention(q, pool, pool, pt, jnp.zeros(2, jnp.int32))
        q1 = jnp.asarray(rs.randn(3, 1, 4, 8), jnp.float32)
        with pytest.raises(ValueError, match="page_tables rows"):
            paged_decode_attention(
                q1, pool, pool, pt, jnp.zeros(3, jnp.int32)
            )


class TestWindowedDecodeBoundaries:
    """Windowed slot_cached_attention vs an independently computed dense
    reference, at the boundaries the paged refactor could plausibly
    break: window == page_size, window < prompt depth, and a window
    straddling a page edge.  The paged windowed path must also stay
    bit-identical to the slab windowed path (both run the shared
    _slot_attend on the same visible values)."""

    def _dense_reference(self, q, ck, cv, positions, window):
        """Per-row, slice the exact visible band and softmax over it —
        no masking tricks shared with the implementation under test."""
        outs = []
        for row, p in enumerate(positions):
            lo = max(0, int(p) - window + 1)
            ks = np.asarray(ck[row, lo : int(p) + 1], np.float32)
            vs = np.asarray(cv[row, lo : int(p) + 1], np.float32)
            qv = np.asarray(q[row, 0], np.float32)  # (Hq, D)
            n_rep = qv.shape[0] // ks.shape[1]
            ks = np.repeat(ks, n_rep, axis=1)
            vs = np.repeat(vs, n_rep, axis=1)
            logits = np.einsum("hd,khd->hk", qv, ks) / np.sqrt(qv.shape[-1])
            probs = np.exp(logits - logits.max(-1, keepdims=True))
            probs /= probs.sum(-1, keepdims=True)
            outs.append(np.einsum("hk,khd->hd", probs, vs))
        return np.stack(outs)[:, None]

    @pytest.mark.parametrize(
        "window,positions",
        [
            (8, [7, 12, 20]),   # window == page_size (ps=8 in the grid)
            (5, [9, 15, 23]),   # window < prompt depth everywhere
            (6, [11, 8, 19]),   # band straddles a page edge (8, 16)
        ],
    )
    def test_windowed_matches_dense_reference(self, window, positions):
        rs = np.random.RandomState(window)
        b, hq, hkv, d, max_seq = 3, 4, 2, 8, 32
        q, k, v, cache, pos = _case(rs, b, hq, hkv, d, max_seq, positions)
        out, (ck, cv) = slot_cached_attention(
            q, k, v, cache, pos, window=window, use_flash=False
        )
        ref = self._dense_reference(
            q, split_heads(ck, hkv), split_heads(cv, hkv), positions, window
        )
        np.testing.assert_allclose(
            np.asarray(out), ref, rtol=1e-6, atol=1e-6
        )

    @pytest.mark.parametrize("window", [5, 8, 6])
    def test_paged_windowed_bitwise_matches_slab(self, window):
        rs = np.random.RandomState(20 + window)
        b, hq, hkv, d, pp, ps = 3, 4, 2, 8, 4, 8
        positions = [11, 8, 19]
        q, k, v, pools, tables, pos = _paged_case(
            rs, b, hq, hkv, d, pp, ps, positions
        )
        slab = tuple(
            jnp.stack([p.reshape(-1, hkv * d)[
                (np.asarray(tables[row])[:, None] * ps
                 + np.arange(ps)[None, :]).reshape(-1)
            ] for row in range(b)])
            for p in pools
        )
        want, _ = slot_cached_attention(
            q, k, v, slab, pos, window=window, use_flash=False
        )
        got, _ = slot_cached_attention(
            q, k, v, pools, pos, window=window, use_flash=False,
            page_tables=tables,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
class TestKernelSweep:
    """Full grid of (GQA width, geometry, block split, position pattern) —
    the heavyweight sibling of TestKernelMatchesReference (nightly)."""

    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 2), (8, 1)])
    @pytest.mark.parametrize("max_seq,block_k", [(16, 512), (64, 16), (128, 32)])
    def test_grid(self, hq, hkv, max_seq, block_k):
        rs = np.random.RandomState(hq + hkv + max_seq + block_k)
        b, d = 4, 16
        positions = np.concatenate(
            [[0, max_seq - 1], rs.randint(0, max_seq, (b - 2,))]
        )
        q, k, v, cache, pos = _case(rs, b, hq, hkv, d, max_seq, positions)
        ref, (rk, rv) = slot_cached_attention(
            q, k, v, cache, pos, use_flash=False
        )
        out = decode_attention(q, rk, rv, pos, block_k=block_k, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=_ULP, atol=_ULP
        )

    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 2), (8, 1)])
    @pytest.mark.parametrize("pp,ps", [(1, 16), (4, 8), (4, 32)])
    def test_paged_grid(self, hq, hkv, pp, ps):
        rs = np.random.RandomState(hq + hkv + pp * ps)
        b, d = 4, 16
        max_seq = pp * ps
        positions = np.concatenate(
            [[0, max_seq - 1], rs.randint(0, max_seq, (b - 2,))]
        )
        q, k, v, pools, tables, pos = _paged_case(
            rs, b, hq, hkv, d, pp, ps, positions
        )
        ref, (rk, rv) = slot_cached_attention(
            q, k, v, pools, pos, use_flash=False, page_tables=tables
        )
        out = paged_decode_attention(q, rk, rv, tables, pos, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=_ULP, atol=_ULP
        )


def _grids(fn, *args):
    """The grid of every ``pallas_call`` that tracing ``fn`` records."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


# (id, Hq, Hkv, S, int8, page size or None); head_dim 128 throughout
_FOLDED = [
    ("gqa-8x4", 32, 8, 1, False, None),
    ("mha-16", 16, 16, 1, False, None),
    ("verify-s4", 8, 2, 4, False, None),
    ("verify-s4-mha", 4, 4, 4, False, None),
    ("int8", 16, 8, 1, True, None),
    ("int8-verify-s4", 16, 8, 4, True, None),
    ("paged-shared-prefix", 8, 2, 1, False, 16),
    ("paged-shared-prefix-int8", 8, 2, 1, True, 16),
    ("paged-verify-s4", 8, 2, 4, False, 16),
]


class TestFoldedGrid:
    """head_dim 128, the real width: ONE grid step reads a slot's row
    block for every KV head (grid ``(B, 1, n_k)``), where the parent of
    PR 33 took a grid step a head.  Against ``slot_cached_attention``'s
    jnp path through the same entry point, so the write, the int8
    quantize-on-write and the page gather are the engine's own; slots
    at depth 0, mid-block, on a block's edge and at ``max_len - S``."""

    @pytest.mark.parametrize(
        "hq,hkv,s,quantized,ps", [c[1:] for c in _FOLDED],
        ids=[c[0] for c in _FOLDED],
    )
    def test_matches_jnp_path(self, hq, hkv, s, quantized, ps):
        rs = np.random.RandomState(hq + 7 * hkv + 31 * s + quantized)
        b, d, max_len = 4, 128, 256
        positions = jnp.asarray([0, 77, 128, max_len - s], jnp.int32)
        q, k_new, v_new = (
            jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
            for h in (hq, hkv, hkv)
        )
        slabs = [
            jnp.asarray(rs.randn(b, max_len, hkv, d), jnp.float32)
            for _ in range(2)
        ]
        tables = None
        if ps is not None:
            # slots 0/1 and 2/3 share their first two pages (a prompt's
            # prefix, attended in place); each slot writes past them
            pp = max_len // ps
            tables = 1 + np.arange(b * pp, dtype=np.int32).reshape(b, pp)
            tables[1, :2], tables[3, :2] = tables[0, :2], tables[2, :2]
            positions = jnp.maximum(positions, 2 * ps)
            pool = rs.permutation(b * pp + 1)  # shuffled: no identity map
            tables = jnp.asarray(pool[tables].astype(np.int32))
            slabs = [
                jnp.zeros((b * pp + 1, ps, hkv, d), jnp.float32)
                .at[tables.reshape(-1)]
                .set(x.reshape(b * pp, ps, hkv, d))
                for x in slabs
            ]
        if quantized:
            (kq, ks), (vq, vs) = map(quantize_kv, slabs)
            cache = tuple(merge_heads(x) for x in (kq, vq, ks, vs))
        else:
            cache = tuple(merge_heads(x) for x in slabs)

        def attend(use_flash):
            return slot_cached_attention(
                q, k_new, v_new, cache, positions, use_flash=use_flash,
                page_tables=tables,
            )

        ref, ref_cache = attend(False)
        out, out_cache = attend(True)
        for x, y in zip(out_cache, ref_cache):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        # the head-per-lane-block test's bar: 128-term f32 dots and an
        # online-softmax merge over the blocks
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-6, atol=2e-6
        )
        rows = -(-s * (hq // hkv) // 8) * 8
        g, block_k = _blocking(
            hkv, d, 1 if quantized else 4, max_len, rows,
            ps or 512, ps or _MIN_BLOCK_K,
        )
        assert g == hkv and max_len // block_k > 1
        assert _grids(lambda: attend(True)) == [(b, 1, max_len // block_k)]


# (Hkv, D, item size, rows a slot, page size or None) -> (g, block_k) at
# 8 query rows a head and the default bound of 512.  The first three are
# the benchmark's widths (Mistral-7B, Llama-2-7B, deepseek-coder-1.3b)
# at their 2048-row slabs: what scripts/bench_decode_attention.py
# measured fastest on the chip (PERF.md §6 PR 33).
_BLOCKING = [
    ((8, 128, 2, 2048, None), (8, 256)),
    ((32, 128, 2, 2048, None), (32, 128)),
    ((16, 128, 2, 2048, None), (16, 256)),
    ((8, 128, 2, 8192, None), (8, 512)),  # longer slots: fewer steps
    ((8, 128, 1, 2048, None), (8, 512)),  # int8: half the bytes a row
    ((8, 128, 2, 2048, 16), (8, 16)),  # paged: the block is the page
    ((32, 128, 2, 4096, 16), (32, 16)),
    ((128, 128, 2, 2048, None), (32, 128)),  # the tail does not fit
    ((8, 256, 2, 8192, None), (8, 256)),
    ((12, 64, 4, 1024, None), (12, 128)),  # GPT-2's 64: the whole tail
    ((2, 64, 4, 96, None), (2, 96)),
    ((2, 8, 4, 64, None), (2, 64)),  # the test models: one block
    ((3, 64, 2, 8192, 128), (3, 128)),
]


class TestBlocking:
    @pytest.mark.parametrize(
        "shapes,want", _BLOCKING, ids=[str(c[0]) for c in _BLOCKING]
    )
    def test_rule(self, shapes, want):
        hkv, d, itemsize, kv_rows, ps = shapes
        g, block_k = _blocking(
            hkv, d, itemsize, kv_rows, 8, ps or 512, ps or _MIN_BLOCK_K
        )
        assert (g, block_k) == want
        assert hkv % g == 0 and kv_rows % block_k == 0
        assert block_k <= 512 and (ps is None or block_k == ps)
        assert _vmem_bytes(g, block_k, hkv, d, itemsize, 8) <= _VMEM_BUDGET
        if d % _LANES != 0:
            assert g == hkv

    def test_the_bound_is_an_upper_bound(self):
        """``block_k=`` caps what the rule may pick and never raises it;
        a bound that does not divide the rows is halved until it does."""
        assert _blocking(8, 128, 2, 2048, 8, 128) == (8, 128)
        assert _blocking(8, 128, 2, 2048, 8, 64) == (8, 64)
        assert _blocking(8, 128, 2, 2048, 8, 4096) == (8, 256)
        assert _blocking(2, 8, 4, 48, 8, 32) == (2, 16)

    def test_more_query_rows_never_widen_a_block(self):
        """The verify block's taller matmul (rows = S * n_rep) takes
        more fast memory a head, so ``g`` can only fall with it."""
        gs = [_blocking(64, 128, 2, 2048, r, 512)[0] for r in (8, 16, 64)]
        assert gs == sorted(gs, reverse=True)

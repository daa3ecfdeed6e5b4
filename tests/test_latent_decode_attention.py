"""Pallas absorbed latent (MLA) decode attention
(ops/latent_decode_attention.py).

Exactness bar, as for ``tdx_decode_attention``: with ONE row block the
kernel follows ``jax.nn.softmax``'s own op order, so in interpret mode
it matches the jnp path (``latent_attend``) to <= 2 float32 ulps at unit
scale (the P@V contraction is associated otherwise by XLA's batched
einsum than by a per-slot kernel dot).  Across blocks the online softmax
defers the normalisation (the standard flash trade): 2e-6 absolute on
outputs of order one, float32 — an order under flash attention's
interpret tolerance (2e-5), three orders under what bf16 operands give.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchdistx_tpu.ops.attention import latent_slot_cached_attention
from torchdistx_tpu.ops.latent_decode_attention import (
    _work_list,
    latent_attend,
    latent_decode_attention,
)

_ULP = 3e-7  # ~2 f32 ulps at unit scale
R, ROPE = 32, 8  # value (latent) and rope lanes of the toy row
W = R + ROPE


def _case(rs, b, h, rows, positions, dtype=jnp.float32, w=W):
    q = jnp.asarray(rs.randn(b, h, w), dtype)
    cache = jnp.asarray(rs.randn(b, rows, w), dtype)
    return q, cache, jnp.asarray(positions, jnp.int32)


def _dense(q, cache, positions, r, scale):
    """The absorbed form written out in float64 numpy."""
    q, cache = np.asarray(q, np.float64), np.asarray(cache, np.float64)
    out = np.zeros((*q.shape[:2], r))
    for b, pos in enumerate(np.asarray(positions)):
        rows = cache[b, : pos + 1]
        s = q[b] @ rows.T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (p / p.sum(-1, keepdims=True)) @ rows[:, :r]
    return out


class TestKernelMatchesJnpPath:
    @pytest.mark.parametrize("h", [4, 32])
    def test_single_block_within_two_ulps(self, h):
        q, cache, pos = _case(np.random.RandomState(0), 3, h, 64, [0, 17, 63])
        kw = dict(value_width=R, scale=0.2)
        want = latent_attend(q, cache, pos, **kw)
        got = latent_decode_attention(q, cache, pos, block_k=64, **kw)
        assert got.shape == (3, h, R)
        np.testing.assert_allclose(got, want, rtol=_ULP, atol=_ULP)

    @pytest.mark.parametrize("block_k", [8, 16, 32])
    def test_multi_block_online_softmax(self, block_k):
        q, cache, pos = _case(
            np.random.RandomState(1), 4, 8, 64, [0, 7, 8, 63]
        )
        kw = dict(value_width=R, scale=0.2)
        want = latent_attend(q, cache, pos, **kw)
        got = latent_decode_attention(q, cache, pos, block_k=block_k, **kw)
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)

    def test_both_paths_are_the_absorbed_form(self):
        """Scores over the whole row, values its first ``value_width``
        lanes, rows past the slot's depth invisible."""
        q, cache, pos = _case(np.random.RandomState(2), 3, 4, 32, [0, 9, 31])
        want = _dense(q, cache, pos, R, 0.3)
        kw = dict(value_width=R, scale=0.3)
        for attend in (
            latent_attend,
            lambda *a, **k: latent_decode_attention(*a, block_k=8, **k),
        ):
            np.testing.assert_allclose(
                attend(q, cache, pos, **kw), want, rtol=1e-5, atol=1e-5
            )

    def test_rows_past_the_depth_do_not_matter(self):
        rs = np.random.RandomState(3)
        q, cache, pos = _case(rs, 2, 4, 32, [5, 20])
        junk = cache.at[0, 6:].set(1e4).at[1, 21:].set(-1e4)
        kw = dict(value_width=R, scale=0.2, block_k=8)
        np.testing.assert_array_equal(
            latent_decode_attention(q, cache, pos, **kw),
            latent_decode_attention(q, junk, pos, **kw),
        )

    def test_bf16_cache_and_padded_lanes(self):
        """The engine's storage: bf16, the row zero-padded to whole
        128-lane tiles (the pad lanes are zero in rows and queries, so
        they add nothing to a score).  bf16 operands, float32 softmax:
        the two paths round the probabilities at different points."""
        rs = np.random.RandomState(4)
        q, cache, pos = _case(rs, 2, 8, 64, [3, 50], jnp.bfloat16, w=128)
        q = q.at[..., W:].set(0)
        cache = cache.at[..., W:].set(0)
        kw = dict(value_width=R, scale=0.2)
        got = latent_decode_attention(q, cache, pos, block_k=16, **kw)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(latent_attend(q, cache, pos, **kw), np.float32),
            atol=2e-2,
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            _dense(q[..., :W], cache[..., :W], pos, R, 0.2),
            atol=3e-2,
        )


class TestVisibleGrid:
    """One grid step per visible (slot, row block) pair: a slot walks its
    blocks ``0 .. min(pos, rows - 1) // block_k`` in order and no
    further, slots one after the other."""

    ROWS, BLOCK_K = 64, 16

    @pytest.mark.parametrize(
        "positions",
        [
            [0, 15, 16, 63, 70],  # 0, block_k - 1, block_k, rows - 1, past
            [0, 63],  # a slot at depth 0 beside one at full depth
            [0, 3, 9, 15],  # every slot in one block
            [63, 0, 40, 0],  # the deep slot first
        ],
        ids=["depths", "empty-beside-full", "one-block-each", "deep-first"],
    )
    def test_matches_jnp_path(self, positions):
        q, cache, pos = _case(
            np.random.RandomState(7), len(positions), 8, self.ROWS, positions
        )
        kw = dict(value_width=R, scale=0.2)
        want = latent_attend(q, cache, pos, **kw)
        got = latent_decode_attention(
            q, cache, pos, block_k=self.BLOCK_K, **kw
        )
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)

    @pytest.mark.parametrize(
        "positions", [[0, 15, 16, 63, 70], [0, 63], [5, 5, 5], [-1, 17]]
    )
    def test_work_list(self, positions):
        steps, slot_of, block_of, last = (
            np.asarray(a) for a in _work_list(
                jnp.asarray(positions, jnp.int32), self.ROWS, self.BLOCK_K
            )
        )
        n_k = self.ROWS // self.BLOCK_K
        want_last = [min(max(p, 0), self.ROWS - 1) // self.BLOCK_K
                     for p in positions]
        np.testing.assert_array_equal(last, want_last)
        assert int(steps) == sum(n + 1 for n in want_last)
        assert slot_of.shape == block_of.shape == (len(positions) * n_k,)
        walked = list(zip(slot_of[:steps].tolist(), block_of[:steps].tolist()))
        # slots in order, each slot's blocks 0 .. last ascending, once
        assert walked == [
            (b, k) for b, n in enumerate(want_last) for k in range(n + 1)
        ]
        # the kernel starts a slot where block_of is 0 and emits where it
        # is the slot's last: one of each a slot
        firsts = [b for b, k in walked if k == 0]
        lasts = [b for b, k in walked if k == last[b]]
        assert firsts == lasts == list(range(len(positions)))
        # entries past the grid repeat the last pair: in bounds, no DMA
        assert all(
            (b, k) == walked[-1]
            for b, k in zip(slot_of[steps:], block_of[steps:])
        )

    def test_slot_with_no_visible_row_is_written(self):
        """A negative position still takes the slot's first step, whose
        arithmetic is skipped: its output is zeros, as under the fixed
        grid, and the other slots are untouched."""
        q, cache, _ = _case(np.random.RandomState(8), 2, 4, self.ROWS, [0, 0])
        kw = dict(value_width=R, scale=0.2)
        got = latent_decode_attention(
            q, cache, jnp.asarray([-1, 40], jnp.int32),
            block_k=self.BLOCK_K, **kw,
        )
        np.testing.assert_array_equal(got[0], 0.0)
        np.testing.assert_allclose(
            got[1],
            latent_attend(q, cache, jnp.asarray([0, 40], jnp.int32), **kw)[1],
            rtol=2e-6, atol=2e-6,
        )

    def test_grid_bound_is_traced(self):
        """The step count is data: one grid axis whose bound comes from
        the positions, so no step of a call is a pruned one."""
        q, cache, pos = _case(np.random.RandomState(9), 2, 4, 32, [4, 30])
        jaxpr = jax.make_jaxpr(
            lambda q, c, p: latent_decode_attention(
                q, c, p, value_width=R, scale=0.2, block_k=8
            )
        )(q, cache, pos)
        (eqn,) = [
            e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"
        ]
        mapping = eqn.params["grid_mapping"]
        assert len(mapping.grid) == 1
        assert mapping.num_dynamic_grid_bounds == 1


class TestRouting:
    def _args(self):
        rs = np.random.RandomState(5)
        q, cache, pos = _case(rs, 3, 4, 32, [0, 9, 31])
        row = jnp.asarray(rs.randn(3, 1, W), jnp.float32)
        return q, row, (cache,), pos

    def test_writes_the_row_then_attends(self):
        q, row, cache, pos = self._args()
        kw = dict(value_width=R, scale=0.2)
        out, (latent,) = latent_slot_cached_attention(
            q, row, cache, pos, use_flash=False, **kw
        )
        for b, p in enumerate(np.asarray(pos)):
            np.testing.assert_array_equal(latent[b, p], row[b, 0])
        np.testing.assert_array_equal(
            out, latent_attend(q, latent, pos, **kw)
        )

    def test_use_flash_takes_the_kernel(self, monkeypatch):
        from torchdistx_tpu.ops import latent_decode_attention as lda

        calls = []
        real = lda.latent_decode_attention
        monkeypatch.setattr(
            lda, "latent_decode_attention",
            lambda *a, **k: calls.append(1) or real(*a, **k),
        )
        q, row, cache, pos = self._args()
        kw = dict(value_width=R, scale=0.2)
        on, _ = latent_slot_cached_attention(
            q, row, cache, pos, use_flash=True, **kw
        )
        assert calls == [1]
        off, _ = latent_slot_cached_attention(
            q, row, cache, pos, use_flash=None, **kw  # auto: jnp off-TPU
        )
        assert calls == [1]
        np.testing.assert_allclose(on, off, rtol=2e-6, atol=2e-6)

    @pytest.mark.parametrize(
        "q_shape,cache_shape,value_width,match",
        [
            ((2, 4, W), (3, 16, W), R, "does not fit"),
            ((2, 4, W), (2, 16, W + 8), R, "does not fit"),
            ((2, 4, W), (2, 16, W), W, "value_width"),
            ((2, 4, W), (2, 16, W), 0, "value_width"),
        ],
    )
    def test_rejects_bad_shapes(self, q_shape, cache_shape, value_width, match):
        with pytest.raises(ValueError, match=match):
            latent_decode_attention(
                jnp.zeros(q_shape), jnp.zeros(cache_shape),
                jnp.zeros((q_shape[0],), jnp.int32),
                value_width=value_width, scale=1.0,
            )


def test_kernel_jits_with_traced_positions():
    q, cache, pos = _case(np.random.RandomState(6), 2, 4, 32, [4, 30])
    kw = dict(value_width=R, scale=0.2, block_k=8)
    fn = jax.jit(lambda q, c, p: latent_decode_attention(q, c, p, **kw))
    np.testing.assert_allclose(
        fn(q, cache, pos), latent_decode_attention(q, cache, pos, **kw),
        rtol=_ULP, atol=_ULP,
    )
    pos2 = jnp.asarray([30, 4], jnp.int32)  # no recompile: depths are data
    np.testing.assert_allclose(
        fn(q, cache, pos2),
        latent_attend(q, cache, pos2, value_width=R, scale=0.2),
        rtol=2e-6, atol=2e-6,
    )

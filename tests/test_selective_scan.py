"""ops/selective_scan.py: ``tdx_selective_scan`` (a prefill) and
``tdx_selective_state_update`` (a decode step) in interpret mode against
the ``jax.numpy`` forms beside them, which are the path off the chip.

Tolerance: kernel and jnp form do the same float32 arithmetic in the
same order a step (``exp(dt a) h + (dt x) b``, then the sum over the
state axis, ``Dskip`` and the gate), so they differ by the compiler's
fusing of a multiply-add at most: 4e-6 on values of order 1-10 read
here, ``TOL`` = 2e-5.  A step skipped, a row of padding let through or a
chunk's carry dropped moves the state by its whole size (order 1)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchdistx_tpu.ops import selective_scan as ss

TOL = 2e-5
N = 16


def _operands(b, length, c, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (b, length, c), dtype)
    z = jax.random.normal(ks[1], (b, length, c), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (b, length, c)) - 2.0)
    a = -jnp.exp(0.3 * jax.random.normal(ks[3], (N, c)))
    bm = jax.random.normal(ks[4], (b, length, N))
    cm = jax.random.normal(ks[5], (b, length, N))
    dskip = jax.random.normal(ks[6], (c,))
    h0 = jax.random.normal(ks[7], (b, N, c))
    return x, dt, a, bm, cm, dskip, z, h0


def _close(got, want):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=0, atol=TOL,
    )


@pytest.mark.parametrize(
    "length,true_len,block_t",
    [
        (64, 64, 16),   # whole chunks, the carry across four of them
        (64, 40, 16),   # true_len inside a chunk: its tail is masked
        (64, 32, 16),   # true_len at the end of a chunk: two chunks skipped
        (64, 2, 16),    # fewer real rows than the convolution keeps
        (37, 37, 16),   # a length that is no multiple of 8: padded, cut off
        (24, 9, 128),   # one chunk shorter than the block
    ],
)
def test_scan_kernel_matches_the_jnp_form(length, true_len, block_t):
    ops = _operands(2, length, 256, seed=length + true_len)
    want_y, want_h = ss.selective_scan_jnp(*ops, true_len)
    got_y, got_h = ss.selective_scan(
        *ops, true_len, use_kernel=True, block_c=128, block_t=block_t
    )
    assert got_y.shape == want_y.shape and got_h.dtype == jnp.float32
    _close(got_y[:, :true_len], want_y[:, :true_len])
    _close(got_h, want_h)
    # and the rows past true_len left the state alone: it is the state
    # of the real rows by themselves
    cut = tuple(
        v[:, :true_len] if v.ndim == 3 and v.shape[1] == length else v
        for v in ops
    )
    _, alone = ss.selective_scan_jnp(*cut, true_len)
    _close(got_h, alone)


def test_scan_kernel_takes_a_length_a_row():
    ops = _operands(3, 48, 128, seed=5)
    lens = jnp.asarray([48, 1, 17], jnp.int32)
    want_y, want_h = ss.selective_scan_jnp(*ops, lens)
    got_y, got_h = ss.selective_scan(
        *ops, lens, use_kernel=True, block_t=16
    )
    _close(got_h, want_h)
    for row, n in enumerate((48, 1, 17)):
        _close(got_y[row, :n], want_y[row, :n])


def test_scan_in_bfloat16_keeps_the_state_in_float32():
    ops = _operands(1, 32, 128, seed=6, dtype=jnp.bfloat16)
    want_y, want_h = ss.selective_scan_jnp(*ops, 32)
    got_y, got_h = ss.selective_scan(*ops, 32, use_kernel=True, block_t=16)
    assert got_y.dtype == jnp.bfloat16 and got_h.dtype == jnp.float32
    _close(got_h, want_h)
    # one rounding to bfloat16 of values up to ~8: half an ulp is 2^-5
    np.testing.assert_allclose(
        np.asarray(got_y, np.float32), np.asarray(want_y, np.float32),
        rtol=0, atol=2.0 ** -4,
    )


def test_scan_is_steps_of_the_update():
    """The decode form, one token after another, is the prefill form."""
    x, dt, a, bm, cm, dskip, z, h0 = _operands(2, 12, 128, seed=7)
    want_y, want_h = ss.selective_scan_jnp(x, dt, a, bm, cm, dskip, z, h0, 12)
    h = h0
    for t in range(12):
        y, h = ss.selective_state_update(
            h, x[:, t], dt[:, t], a, bm[:, t], cm[:, t], dskip, z[:, t],
            use_kernel=True, block_c=128,
        )
        _close(y, want_y[:, t])
    _close(h, want_h)


@pytest.mark.parametrize("slots,block_s", [(32, 16), (5, 16), (16, 8)])
def test_update_kernel_matches_the_jnp_form_at_mixed_state(slots, block_s):
    """Slots at mixed state: some empty, some deep into a context, one
    with a step size of zero (its state must come back as it went in)."""
    x, dt, a, bm, cm, dskip, z, h = _operands(slots, 1, 256, seed=slots)
    h = h.at[0].set(0.0).at[1].multiply(50.0)
    dt = dt.at[2].set(0.0)
    args = (h, x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], dskip, z[:, 0])
    want_y, want_h = ss.selective_state_update_jnp(*args)
    got_y, got_h = ss.selective_state_update(
        *args, use_kernel=True, block_s=block_s, block_c=128
    )
    # the deep slot's values run to the hundreds: a relative bound too
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=TOL)
    np.testing.assert_allclose(got_h, want_h, rtol=1e-6, atol=TOL)
    np.testing.assert_array_equal(got_h[2], h[2])


def test_update_refuses_a_state_that_is_not_float32():
    x, dt, a, bm, cm, dskip, z, h = _operands(4, 1, 128)
    with pytest.raises(ValueError, match="float32"):
        ss.selective_state_update(
            h.astype(jnp.bfloat16), x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
            dskip, z[:, 0], use_kernel=True,
        )


def test_off_the_chip_the_jnp_forms_are_the_path():
    """``use_kernel=None`` resolves like every kernel of the repo: the
    kernel on a TPU, the jnp form elsewhere."""
    ops = _operands(1, 8, 128, seed=8)
    want = ss.selective_scan_jnp(*ops, 8)
    got = ss.selective_scan(*ops, 8)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    text = jax.jit(lambda *o: ss.selective_scan(*o, 8)).lower(*ops).as_text()
    assert "tdx_selective_scan" not in text

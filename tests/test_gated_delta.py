"""ops/gated_delta.py: ``tdx_gated_delta_chunk`` (a prefill) and
``tdx_gated_delta_update`` (a decode step) in interpret mode, and the
chunked ``jax.numpy`` form that is the path off the chip, against the
token-by-token recurrence (``gated_delta_recurrence_jnp``): the oracle
of all three.

Tolerance: float32 on every side at sizes where ``o`` and ``S`` are of
order 0.1-1.  The chunked form sums a chunk's corrections through
``(I + A)^-1`` (five or so products) where the recurrence adds them one
by one: 1e-7 … 3e-7 read here, ``TOL`` = 5e-6.  A chunk's carry dropped,
a row of padding let through, a decay or a ``beta`` misplaced moves the
state by its own size (order 0.1)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchdistx_tpu.ops import gated_delta as gd

TOL = 5e-6
HK, HV, DK, DV = 2, 4, 16, 32


def _operands(b, length, seed=0, hk=HK, hv=HV, dk=DK, dv=DV):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, length, hk, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, length, hk, dk)))
    v = jax.random.normal(ks[2], (b, length, hv, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, length, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, hv)))
    s0 = 0.1 * jax.random.normal(ks[5], (b, hv, dk, dv))
    return q, k, v, g, beta, s0


def _close(got, want):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=0, atol=TOL,
    )


CASES = [
    (64, 64, 16),   # whole chunks, the carry across four of them
    (64, 40, 16),   # true_len inside a chunk: its tail is masked
    (64, 32, 16),   # true_len at the end of a chunk: two chunks skipped
    (64, 2, 16),    # fewer real rows than the convolution keeps
    (37, 37, 16),   # a length that is no multiple of 8: padded, cut off
    (24, 9, 64),    # one chunk shorter than the block
    (64, 64, 64),   # five squarings
    (160, 150, 128),  # the default chunk: six squarings, and a second chunk
]


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("length,true_len,block_t", CASES)
def test_chunked_forms_match_the_recurrence(length, true_len, block_t, use_kernel):
    ops = _operands(2, length, seed=length + true_len)
    want_o, want_s = gd.gated_delta_recurrence_jnp(*ops, true_len)
    got_o, got_s = gd.gated_delta_chunk(
        *ops, true_len, use_kernel=use_kernel, block_t=block_t
    )
    assert got_o.shape == want_o.shape and got_s.dtype == jnp.float32
    assert np.abs(np.asarray(want_s)).max() > 0.05  # not a comparison of zeros
    _close(got_o[:, :true_len], want_o[:, :true_len])
    _close(got_s, want_s)
    # and the rows past true_len left the state alone: it is the state
    # of the real rows by themselves
    cut = tuple(x[:, :true_len] for x in ops[:5]) + (ops[5],)
    _, alone = gd.gated_delta_recurrence_jnp(*cut, true_len)
    _close(got_s, alone)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_chunk_takes_a_length_a_row(use_kernel):
    ops = _operands(3, 48, seed=5)
    lens = jnp.asarray([48, 1, 17], jnp.int32)
    want_o, want_s = gd.gated_delta_recurrence_jnp(*ops, lens)
    got_o, got_s = gd.gated_delta_chunk(
        *ops, lens, use_kernel=use_kernel, block_t=16
    )
    _close(got_s, want_s)
    for row, n in enumerate((48, 1, 17)):
        _close(got_o[row, :n], want_o[row, :n])


def test_one_prompt_in_two_buckets_writes_the_same_state():
    """PR 34's contract: a prompt right-padded to 32 and to 64 rows (the
    padding rows hold other values each time) ends in one state."""
    real = _operands(1, 21, seed=7)
    states = []
    for bucket, seed in ((32, 8), (64, 9)):
        junk = _operands(1, bucket, seed=seed)
        padded = tuple(
            jnp.concatenate([r, j[:, 21:]], axis=1)
            for r, j in zip(real[:5], junk[:5])
        )
        for use_kernel in (False, True):
            _, s = gd.gated_delta_chunk(
                *padded, real[5], 21, use_kernel=use_kernel, block_t=16
            )
            states.append(s)
    _, want = gd.gated_delta_recurrence_jnp(*real, 21)
    for s in states:
        _close(s, want)


@pytest.mark.parametrize("block_h", [4, 2], ids=["heads4", "heads2"])
def test_update_kernel_matches_the_jnp_form(block_h):
    q, k, v, g, beta, s0 = _operands(5, 1, seed=11)
    row = tuple(x[:, 0] for x in (q, k, v, g, beta))
    want_o, want_s = gd.gated_delta_update_jnp(s0, *row)
    got_o, got_s = gd.gated_delta_update(
        s0, *row, use_kernel=True, block_h=block_h
    )
    assert got_s.dtype == jnp.float32 and got_o.dtype == v.dtype
    _close(got_o, want_o)
    _close(got_s, want_s)


def test_update_kernel_at_blocks_of_eight_heads():
    """The cell's blocking rule at a size the interpreter runs: 16 value
    heads on 8 key heads in blocks of 8 (4 key heads a block)."""
    q, k, v, g, beta, s0 = _operands(2, 1, seed=12, hk=8, hv=16, dk=8, dv=128)
    row = tuple(x[:, 0] for x in (q, k, v, g, beta))
    want_o, want_s = gd.gated_delta_update_jnp(s0, *row)
    got_o, got_s = gd.gated_delta_update(s0, *row, use_kernel=True, block_h=8)
    _close(got_o, want_o)
    _close(got_s, want_s)


def test_update_refuses_a_state_that_is_not_float32():
    q, k, v, g, beta, s0 = _operands(1, 1, seed=13)
    row = tuple(x[:, 0] for x in (q, k, v, g, beta))
    with pytest.raises(ValueError, match="must be float32"):
        gd.gated_delta_update(s0.astype(jnp.bfloat16), *row, use_kernel=True)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_state_carried_from_prefill_to_decode_is_the_full_recurrence(use_kernel):
    """13 rows through the chunked form, then 8 one by one through the
    update, against all 21 through the recurrence."""
    ops = _operands(2, 21, seed=14)
    want_o, want_s = gd.gated_delta_recurrence_jnp(*ops, 21)
    head = tuple(x[:, :13] for x in ops[:5])
    o, s = gd.gated_delta_chunk(
        *head, ops[5], 13, use_kernel=use_kernel, block_t=8
    )
    _close(o, want_o[:, :13])
    for t in range(13, 21):
        row = tuple(x[:, t] for x in ops[:5])
        o, s = gd.gated_delta_update(s, *row, use_kernel=use_kernel, block_h=4)
        _close(o, want_o[:, t])
    _close(s, want_s)


def test_bfloat16_values_keep_a_float32_state():
    q, k, v, g, beta, s0 = _operands(1, 24, seed=15)
    o, s = gd.gated_delta_chunk(
        q, k, v.astype(jnp.bfloat16), g, beta, s0, 24, use_kernel=True,
        block_t=8,
    )
    assert o.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    want_o, want_s = gd.gated_delta_recurrence_jnp(
        q, k, v.astype(jnp.bfloat16), g, beta, s0, 24
    )
    _close(s, want_s)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(want_o, np.float32),
        rtol=0, atol=0.02,
    )

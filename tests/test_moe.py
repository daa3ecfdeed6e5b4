"""MoE layer + expert parallelism: routing correctness, deferred init,
ep-sharded == unsharded, gradient flow."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import torchdistx_tpu as tdx
from torchdistx_tpu.nn import functional_call
from torchdistx_tpu.nn.moe import MoE, moe_shard_rule
from torchdistx_tpu.parallel import create_mesh


def test_topk_routing_selects_k_experts():
    tdx.manual_seed(0)
    m = MoE(16, 32, n_experts=4, top_k=1)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16), jnp.float32)
    y = m(x)
    assert y.shape == (2, 8, 16)
    # top-1: output must equal the single selected expert's output weighted 1
    logits = np.asarray(m.router(x))
    sel = logits.argmax(-1)
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, m.w_gate)) * jnp.einsum(
        "bsd,edf->bsef", x, m.w_up
    )
    eo = np.asarray(jnp.einsum("bsef,efd->bsed", h, m.w_down))
    expected = np.take_along_axis(eo, sel[..., None, None], axis=2)[:, :, 0]
    np.testing.assert_allclose(np.asarray(y), expected, rtol=1e-5, atol=1e-6)


def test_deferred_init_moe():
    tdx.manual_seed(1)
    m = tdx.deferred_init(MoE, 8, 16, n_experts=4, top_k=2)
    assert tdx.is_deferred(m)
    tdx.materialize_module(m)
    y = m(jnp.ones((2, 4, 8)))
    assert y.shape == (2, 4, 8)


def test_ep_sharded_matches_unsharded():
    mesh = create_mesh({"dp": 2, "ep": 4})
    tdx.manual_seed(2)
    m = tdx.deferred_init(MoE, 16, 32, n_experts=8, top_k=2)
    tdx.materialize_module(m, sharding_rule=moe_shard_rule(mesh, "ep"))
    assert m._parameters["w_up"].sharding.spec == P("ep", None, None)
    params = dict(m.named_parameters())

    x = jnp.asarray(np.random.RandomState(1).randn(4, 8, 16), jnp.float32)
    sharded = jax.jit(lambda p, x: functional_call(m, p, (x,)))(params, x)

    tdx.manual_seed(2)
    m2 = MoE(16, 32, n_experts=8, top_k=2)
    unsharded = m2(x)
    np.testing.assert_allclose(
        np.asarray(sharded), np.asarray(unsharded), rtol=1e-4, atol=1e-5
    )


def test_gradients_flow_and_balance_loss():
    tdx.manual_seed(3)
    m = MoE(8, 16, n_experts=4, top_k=2)
    params = dict(m.named_parameters())
    x = jnp.asarray(np.random.RandomState(2).randn(2, 4, 8), jnp.float32)

    def loss(p):
        y, aux = functional_call(m, p, (x,), {"return_aux": True})
        return jnp.mean(y**2) + 0.01 * aux

    g = jax.grad(loss)(params)
    for k in ("w_up", "w_gate", "w_down", "router.weight"):
        assert float(jnp.abs(g[k]).sum()) > 0.0, k


def test_invalid_topk():
    import pytest

    with pytest.raises(ValueError, match="top_k"):
        MoE(8, 16, n_experts=4, top_k=5)


class TestCapacityDispatch:
    """Capacity-based token dispatch must equal the dense path when no
    token can be dropped (capacity_factor >= E / top_k), and must drop the
    overflow (zero combine weight) when capacity is tight."""

    def test_matches_dense_when_capacity_sufficient(self):
        tdx.manual_seed(5)
        dense = tdx.deferred_init(MoE, 16, 32, 4, 2)
        tdx.materialize_module(dense)
        params = dict(dense.named_parameters())

        disp = MoE(16, 32, 4, 2, capacity_factor=4 / 2)  # C = n: no drops
        disp.load_state_dict(params)

        x = jnp.asarray(
            np.random.RandomState(0).randn(3, 8, 16).astype(np.float32)
        )
        y_dense = dense(x)
        y_disp = disp(x)
        np.testing.assert_allclose(
            np.asarray(y_dense), np.asarray(y_disp), rtol=2e-5, atol=2e-5
        )

    def test_gradients_flow(self):
        tdx.manual_seed(6)
        m = MoE(8, 16, 4, 2, capacity_factor=2.0)
        params = dict(m.named_parameters())
        x = jnp.asarray(np.random.RandomState(1).randn(4, 8).astype(np.float32))

        def loss(p):
            return jnp.mean(functional_call(m, p, (x,)) ** 2)

        g = jax.grad(loss)(params)
        assert all(jnp.all(jnp.isfinite(v)) for v in g.values())
        assert float(jnp.abs(g["w_gate"]).sum()) > 0

    def test_tight_capacity_drops_tokens(self):
        tdx.manual_seed(7)
        # capacity_factor tiny -> C = 1: most tokens dropped, output is
        # partial but finite; combine weights for dropped tokens are zero
        m = MoE(8, 16, 4, 1, capacity_factor=0.1)
        x = jnp.asarray(np.random.RandomState(2).randn(16, 8).astype(np.float32))
        y = m(x)
        assert y.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(y)))
        # at least one token passed through, not all
        norms = jnp.linalg.norm(y, axis=-1)
        assert float(jnp.max(norms)) > 0
        assert float(jnp.min(norms)) == 0.0

    def test_gather_dispatch_matches_einsum(self):
        # gather mode removes the O(n*E*C*D) bookkeeping MACs; outputs and
        # gradients must agree with the einsum path — including under
        # tight capacity, where both must drop the SAME tokens (shared
        # GShard slot assignment)
        for cf in (2.0, 0.5):
            tdx.manual_seed(9)
            a = tdx.deferred_init(
                MoE, 16, 32, 4, 2, capacity_factor=cf
            )
            tdx.materialize_module(a)
            params = dict(a.named_parameters())
            b = MoE(
                16, 32, 4, 2, capacity_factor=cf, dispatch_mode="gather"
            )
            b.load_state_dict(params)
            x = jnp.asarray(
                np.random.RandomState(4).randn(3, 8, 16).astype(np.float32)
            )
            ya, yb = a(x), b(x)
            np.testing.assert_allclose(
                np.asarray(ya), np.asarray(yb), rtol=2e-5, atol=2e-5,
                err_msg=f"capacity_factor={cf}",
            )

            def loss(p, m):
                return jnp.mean(functional_call(m, p, (x,)) ** 2)

            ga = jax.grad(lambda p: loss(p, a))(params)
            gb = jax.grad(lambda p: loss(p, b))(params)
            for k in ga:
                np.testing.assert_allclose(
                    np.asarray(ga[k]), np.asarray(gb[k]),
                    rtol=2e-4, atol=1e-6,
                    err_msg=f"grad {k} capacity_factor={cf}",
                )

    def test_gather_dispatch_jits(self):
        m = MoE(8, 16, 4, 2, capacity_factor=1.5, dispatch_mode="gather")
        x = jnp.asarray(np.random.RandomState(5).randn(2, 4, 8).astype(np.float32))
        y = jax.jit(lambda x: m(x))(x)
        assert y.shape == x.shape and bool(jnp.all(jnp.isfinite(y)))

    def test_bad_dispatch_mode_rejected(self):
        with pytest.raises(ValueError, match="dispatch_mode"):
            MoE(8, 16, 4, 2, dispatch_mode="bogus")

    def test_gather_without_capacity_rejected(self):
        # silent fallback to dense compute would waste E/top_k x FLOPs
        with pytest.raises(ValueError, match="capacity_factor"):
            MoE(8, 16, 4, 2, dispatch_mode="gather")

    def test_ep_sharded_dispatch(self):
        mesh = create_mesh({"ep": 4}, devices=jax.devices()[:4])
        tdx.manual_seed(8)
        m = tdx.deferred_init(MoE, 16, 32, 4, 2, capacity_factor=2.0)
        tdx.materialize_module(m, sharding_rule=moe_shard_rule(mesh, "ep"))
        x = jnp.asarray(np.random.RandomState(3).randn(2, 8, 16).astype(np.float32))
        y = m(x)
        assert y.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(np.asarray(y))))


def _pair(dispatch_kw, seed=9, dim=16, ffn=32, e=8, k=3, **router):
    """The same weights under dense compute and under ``dispatch_kw``."""
    tdx.manual_seed(seed)
    dense = MoE(dim, ffn, e, k, **router)
    other = MoE(dim, ffn, e, k, **router, **dispatch_kw)
    other.load_state_dict(dict(dense.named_parameters()))
    return dense, other


DSV3_ROUTER = dict(
    scoring="sigmoid", selection_bias=True, routed_scale=2.448,
    shared_ffn_dim=24,
)


class TestGroupedDispatch:
    """``dispatch_mode="grouped"``: every (token, expert) row computed,
    none dropped, whatever the routing; an expert without a row never
    read."""

    @pytest.mark.parametrize("router", [{}, DSV3_ROUTER], ids=["softmax", "dsv3"])
    @pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "pallas"])
    def test_matches_dense(self, router, use_kernel):
        dense, grouped = _pair(
            dict(dispatch_mode="grouped", use_kernel=use_kernel), **router
        )
        x = jnp.asarray(np.random.RandomState(0).randn(3, 7, 16), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(grouped(x)), np.asarray(dense(x)), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "pallas"])
    def test_uneven_routing_drops_nothing(self, use_kernel):
        """A selection bias that sends EVERY token to expert 2 and none
        to expert 5: the long group is computed whole (a capacity of the
        mean load would have dropped most of it) and expert 5's weights,
        poisoned with NaN, are never read."""
        dense, grouped = _pair(
            dict(dispatch_mode="grouped", use_kernel=use_kernel), **DSV3_ROUTER
        )
        bias = jnp.zeros((8,)).at[2].set(10.0).at[5].set(-10.0)
        for m in (dense, grouped):
            m.e_score_correction_bias = tdx.nn.Parameter(bias)
        x = jnp.asarray(np.random.RandomState(1).randn(40, 16), jnp.float32)
        _, top_i = grouped._choose(grouped._route(x))
        top_i = np.asarray(top_i)
        assert (top_i == 2).any(-1).all() and not (top_i == 5).any()
        want = np.asarray(dense(x))
        for name in ("w_gate", "w_up", "w_down"):
            w = getattr(grouped, name)
            setattr(grouped, name, tdx.nn.Parameter(w.at[5].set(jnp.nan)))
        got = np.asarray(grouped(x))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_counts_rows_and_groups(self):
        from torchdistx_tpu.nn.moe import moe_count_tape, tape_totals

        _, grouped = _pair(dict(dispatch_mode="grouped"), **DSV3_ROUTER)
        x = jnp.asarray(np.random.RandomState(2).randn(5, 16), jnp.float32)
        with moe_count_tape() as tape:
            grouped(x)
            grouped(x[:2])
        rows, groups = (int(v) for v in tape_totals(tape))
        assert rows == 5 * 3 + 2 * 3  # tokens x top_k: no more, no fewer
        _, top_i = grouped._choose(grouped._route(x))
        touched = len(np.unique(np.asarray(top_i)))
        touched2 = len(np.unique(np.asarray(top_i)[:2]))
        assert groups == touched + touched2
        grouped(x)  # no tape open: nothing recorded, nothing raised

    def test_jits_and_takes_gradients(self):
        _, grouped = _pair(dict(dispatch_mode="grouped"), **DSV3_ROUTER)
        params = dict(grouped.named_parameters())
        x = jnp.asarray(np.random.RandomState(3).randn(6, 16), jnp.float32)
        y = jax.jit(lambda p, x: functional_call(grouped, p, (x,)))(params, x)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(grouped(x)), rtol=1e-6, atol=1e-6
        )
        g = jax.grad(
            lambda p: jnp.mean(functional_call(grouped, p, (x,)) ** 2)
        )(params)
        for name in ("w_gate", "w_down", "router.weight", "shared.w_up.weight"):
            assert float(jnp.abs(g[name]).sum()) > 0.0, name

    def test_grouped_takes_no_capacity(self):
        with pytest.raises(ValueError, match="grouped.*capacity_factor"):
            MoE(8, 16, 4, 2, dispatch_mode="grouped", capacity_factor=1.0)


class TestGroupedMatmul:
    """``ops/grouped_matmul.py``: the layout and the kernel."""

    def _plan(self, ids, n_groups=6, tm=8, tiles=None):
        from torchdistx_tpu.ops.grouped_matmul import plan_groups

        return plan_groups(jnp.asarray(ids, jnp.int32), n_groups, tm, tiles)

    def test_plan_layout(self):
        ids = [3, 0, 3, 3, 5, 0, 3, 3, 3, 3, 3, 3]  # group 3: 9 rows, 2 tiles
        plan = self._plan(ids)
        assert int(plan.groups) == 3 and int(plan.n_tiles[0]) == 4
        assert plan.tile_group.shape == (2 + 6,)  # ceil(12 / 8) + groups
        np.testing.assert_array_equal(plan.tile_group[:4], [0, 3, 3, 5])
        np.testing.assert_array_equal(plan.tile_group[4:], [5] * 4)  # dead
        # every pair sits in a row of a tile of its own group, in the
        # order the pairs came (a row's rank in its group: no sort)
        dest = np.asarray(plan.dest)
        np.testing.assert_array_equal(
            dest, [8, 0, 9, 10, 24, 1, 11, 12, 13, 14, 15, 16]
        )
        np.testing.assert_array_equal(
            np.asarray(plan.tile_group)[dest // 8], ids
        )
        np.testing.assert_array_equal(np.asarray(plan.src)[dest], range(12))
        # a static count of tiles of the caller's own: the same rows
        few = self._plan(ids, tiles=5)
        assert few.tile_group.shape == (5,) and few.src.shape == (40,)
        np.testing.assert_array_equal(few.dest, dest)

    @pytest.mark.parametrize("swiglu", [False, True])
    def test_kernel_matches_jnp_path_and_rows(self, swiglu):
        from torchdistx_tpu.ops.grouped_matmul import grouped_matmul

        rs = np.random.RandomState(4)
        ids = rs.randint(0, 6, 50)
        ids[ids == 4] = 1  # group 4 has no row
        plan = self._plan(ids)
        rows = jnp.asarray(rs.randn(50, 16), jnp.float32)
        w = jnp.asarray(rs.randn(6, 16, 256), jnp.float32)
        up = jnp.asarray(rs.randn(6, 16, 256), jnp.float32) if swiglu else None
        lhs = rows[plan.src]
        kw = dict(rhs_up=up, block_n=128)
        got = grouped_matmul(lhs, w.at[4].set(jnp.nan), plan, use_kernel=True, **kw)
        ref = grouped_matmul(lhs, w.at[4].set(jnp.nan), plan, use_kernel=False, **kw)
        want = np.einsum("rk,rkn->rn", rows, np.asarray(w)[ids])
        if swiglu:
            want = np.asarray(jax.nn.silu(want)) * np.einsum(
                "rk,rkn->rn", rows, np.asarray(up)[ids]
            )
        for out in (got, ref):
            np.testing.assert_allclose(
                np.asarray(out)[plan.dest], want, rtol=1e-5, atol=1e-4
            )

    def test_rejects_a_layout_that_does_not_fit(self):
        from torchdistx_tpu.ops.grouped_matmul import grouped_matmul

        plan = self._plan([0, 1, 2])
        with pytest.raises(ValueError, match="do not fit the plan"):
            grouped_matmul(jnp.zeros((8, 4)), jnp.zeros((6, 4, 4)), plan)
        lhs = jnp.zeros((plan.tile_group.shape[0] * 8, 4))
        with pytest.raises(ValueError, match="gate .* and up .* differ"):
            grouped_matmul(
                lhs, jnp.zeros((6, 4, 4)), plan, rhs_up=jnp.zeros((6, 4, 8))
            )


class TestRouter:
    def test_selection_bias_changes_the_choice_not_the_weights(self):
        tdx.manual_seed(21)
        m = MoE(16, 32, 8, 2, **DSV3_ROUTER)
        m.e_score_correction_bias = tdx.nn.Parameter(jnp.zeros((8,)))
        x = jnp.asarray(np.random.RandomState(5).randn(12, 16), jnp.float32)
        scores = m._route(x)
        p0, i0 = m._choose(scores)
        np.testing.assert_allclose(np.asarray(p0.sum(-1)), 2.448, rtol=1e-6)
        # a bias large enough lifts expert 7 into every token's choice
        m.e_score_correction_bias = tdx.nn.Parameter(
            jnp.zeros((8,)).at[7].set(5.0)
        )
        p1, i1 = m._choose(scores)
        assert (np.asarray(i1) == 7).any(-1).all()
        assert not (np.asarray(i0) == 7).any(-1).all()
        # the weights are the chosen experts' OWN scores, renormalised:
        # the bias is nowhere in them
        own = np.take_along_axis(np.asarray(scores), np.asarray(i1), -1)
        np.testing.assert_allclose(
            np.asarray(p1), own / own.sum(-1, keepdims=True) * 2.448, rtol=1e-6
        )

    def test_sigmoid_scores_are_float32(self):
        tdx.manual_seed(22)
        m = MoE(16, 32, 8, 2, dtype=jnp.bfloat16, scoring="sigmoid")
        x = jnp.asarray(np.random.RandomState(6).randn(4, 16), jnp.bfloat16)
        scores = m._route(x)
        assert scores.dtype == jnp.float32
        want = jax.nn.sigmoid(
            np.asarray(x, np.float32) @ np.asarray(m.router.weight, np.float32).T
        )
        np.testing.assert_allclose(np.asarray(scores), want, rtol=1e-5, atol=1e-6)

    def test_shared_expert_is_added_for_every_token(self):
        tdx.manual_seed(23)
        m = MoE(16, 32, 4, 2, shared_ffn_dim=24)
        x = jnp.asarray(np.random.RandomState(7).randn(5, 16), jnp.float32)
        params = dict(m.named_parameters())
        no_shared = dict(
            params, **{"shared.w_down.weight": jnp.zeros((16, 24))}
        )
        routed = functional_call(m, no_shared, (x,))
        np.testing.assert_allclose(
            np.asarray(m(x)), np.asarray(routed + m.shared(x)),
            rtol=1e-6, atol=1e-6,
        )
        assert float(jnp.abs(m.shared(x)).max()) > 1e-3

    def test_bad_scoring_rejected(self):
        with pytest.raises(ValueError, match="scoring"):
            MoE(8, 16, 4, 2, scoring="tanh")


class TestHeldShare:
    """``MoE(held=(lo, hi))``: a layer told which experts it holds (one
    chip's share of an expert-parallel group, without the exchange)."""

    E, K, D, F = 16, 4, 32, 24

    def _whole(self, **kw):
        tdx.manual_seed(0)
        return MoE(
            self.D, self.F, self.E, top_k=self.K, dispatch_mode="grouped",
            shared_ffn_dim=self.F, shared_gate=True, **kw,
        )

    def _share(self, whole, lo, hi, **kw):
        part = self._whole(held=(lo, hi), **kw)
        params = dict(whole.named_parameters())
        for name in ("w_gate", "w_up", "w_down"):
            params[name] = params[name][lo:hi]
        assert {n: p.shape for n, p in part.named_parameters()} == {
            n: p.shape for n, p in params.items()
        }
        return part, params

    def _shared_term(self, whole, x):
        gate = jax.nn.sigmoid(whole.shared_gate(x))
        return gate * whole.shared(x)

    @pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
    def test_four_shares_add_up_to_the_uncut_layer(self, use_kernel):
        """The shared expert counted once: ``sum_shares(y - shared) +
        shared`` is the layer that holds every expert, the router's
        weights renormalised over all ``top_k`` choices in each."""
        from torchdistx_tpu.nn.moe import moe_count_tape, tape_totals

        whole = self._whole()
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 7, self.D))
        want = whole(x)
        shared = self._shared_term(whole, x)
        total, rows, elsewhere = shared, 0, 0
        for lo in range(0, self.E, 4):
            part, params = self._share(whole, lo, lo + 4, use_kernel=use_kernel)
            assert part.router.weight.shape == (self.E, self.D)  # all scored
            assert part.w_gate.shape == (4, self.D, self.F)
            with moe_count_tape() as tape:
                y = functional_call(part, params, (x,))
            counts = np.asarray(tape_totals(tape))
            assert counts.shape == (4,) and counts[0] + counts[2] == 21 * self.K
            assert 0 < counts[1] <= 4  # held experts touched
            assert counts[3] == 0  # an even routing fits the capped layout
            rows, elsewhere = rows + counts[0], elsewhere + counts[2]
            total = total + y - shared
        assert rows == 21 * self.K and elsewhere == 3 * rows
        assert float(jnp.abs(want).max()) > 0.01
        np.testing.assert_allclose(
            np.asarray(total), np.asarray(want), rtol=0, atol=2e-6
        )

    def test_the_default_is_bit_for_bit_todays_layer(self):
        """``held=None`` and ``held=(0, n_experts)``: the same leaves, the
        same jaxpr (no op and no shape differs), the same bits, and a
        two-number tape."""
        from torchdistx_tpu.nn.moe import moe_count_tape, tape_totals

        whole, spelled = self._whole(), self._whole(held=(0, self.E))
        assert spelled.held is None
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, self.D))
        params = dict(whole.named_parameters())
        texts = [
            str(jax.make_jaxpr(lambda p, v, m=m: functional_call(m, p, (v,)))(
                params, x))
            for m in (whole, spelled)
        ]
        assert texts[0] == texts[1]
        np.testing.assert_array_equal(
            np.asarray(whole(x)), np.asarray(functional_call(spelled, params, (x,)))
        )
        with moe_count_tape() as tape:
            whole(x)
        assert np.asarray(tape_totals(tape)).shape == (2,)
        assert tape[0][0] == 10 * self.K  # static: every row is here

    def test_a_share_no_token_chose_adds_nothing(self):
        """No held row at all (top-1 of 16, the share one expert nobody
        picked): the layer computes one dead tile and returns zeros."""
        tdx.manual_seed(3)
        x = jax.random.normal(jax.random.PRNGKey(4), (1, 6, self.D))
        m = MoE(self.D, self.F, self.E, top_k=1, dispatch_mode="grouped")
        picked = set(np.asarray(jnp.argmax(m.router(x), -1)).ravel().tolist())
        lo = next(e for e in range(self.E) if e not in picked)
        tdx.manual_seed(3)
        part = MoE(self.D, self.F, self.E, top_k=1, dispatch_mode="grouped",
                   held=(lo, lo + 1))
        np.testing.assert_array_equal(np.asarray(part(x)), 0.0)

    @pytest.mark.parametrize(
        "kwargs,match",
        [
            (dict(held=(4, 2)), "not a range"),
            (dict(held=(0, 99)), "not a range"),
            (dict(held=(0, 4), dispatch_mode="einsum"), "needs dispatch_mode='grouped'"),
        ],
    )
    def test_refusals(self, kwargs, match):
        kw = dict(dispatch_mode="grouped")
        kw.update(kwargs)
        with pytest.raises(ValueError, match=match):
            MoE(self.D, self.F, self.E, top_k=2, **kw)
        with pytest.raises(ValueError, match="shared_gate=True without"):
            MoE(self.D, self.F, self.E, top_k=2, shared_gate=True)

    def test_plan_leaves_absent_rows_out_of_the_layout(self):
        from torchdistx_tpu.ops.grouped_matmul import plan_groups

        ids = jnp.asarray([3, 1, 3, 0, 3, 1, 3, 3], jnp.int32)  # 3 = absent
        plan = plan_groups(ids, 3, 2)
        assert int(plan.groups) == 2 and int(plan.n_tiles[0]) == 2
        assert np.asarray(plan.tile_group)[:2].tolist() == [0, 1]
        padded_rows = plan.src.shape[0]
        assert padded_rows == (4 + 3) * 2
        dest = np.asarray(plan.dest)
        # one past the layout: a scatter there drops, a gather there fills
        assert dest[[0, 2, 4, 6, 7]].tolist() == [padded_rows] * 5
        assert dest[[3, 1, 5]].tolist() == [0, 2, 3]
        src = np.asarray(plan.src)
        assert src[:4].tolist() == [3, 0, 1, 5] and not src[4:].any()
        # every row absent: one tile stays, of a group that exists
        none = plan_groups(jnp.full((4,), 3, jnp.int32), 3, 2)
        assert int(none.groups) == 0 and int(none.n_tiles[0]) == 1
        assert int(none.tile_group[0]) <= 2
        assert np.asarray(none.dest).tolist() == [none.src.shape[0]] * 4


#: (name, layer, tokens, top_k, boosted experts): the routing of every
#: case is the router's own but for the experts whose logit is lifted by
#: 50 (every token then chooses them first).  ``share`` holds experts
#: 0-3 of 16 (and the test walks the other three shares too)
LAYOUT_CASES = [
    ("whole-decode", "whole", 8, 4, ()),
    ("whole-prefill", "whole", 96, 4, ()),
    ("whole-one_group_holds_every_row", "whole", 24, 1, (5,)),
    ("share-decode", "share", 8, 4, ()),
    ("share-prefill", "share", 96, 4, ()),
    ("share-every_row_absent", "share", 24, 4, (4, 9, 10, 15)),
    ("share-one_group_holds_every_row", "share", 24, 4, (2, 9, 10, 15)),
    ("share-held_rows_over_cap", "share", 24, 4, (0, 1, 2, 3)),
]


class TestGroupedLayout:
    """The layout of an expert layer's rows (``plan_groups``, built by
    counting; sized by the rows held where the layer holds a share, with
    the full-size layout as the exact fallback), case by case."""

    E, D, F = 16, 32, 24

    def _routing(self, tokens, top_k, boosted, seed=0):
        """A dense float32 layer whose router lifts ``boosted``, its
        input, and the choices it makes."""
        tdx.manual_seed(seed)
        dense = MoE(self.D, self.F, self.E, top_k=top_k)
        x = np.random.RandomState(seed).randn(tokens, self.D).astype(np.float32)
        x[:, 0] = 1.0
        lift = np.zeros((self.E,), np.float32)
        lift[list(boosted)] = 50.0
        dense.router.weight = tdx.nn.Parameter(
            dense.router.weight.at[:, 0].set(jnp.asarray(lift))
        )
        x = jnp.asarray(x)
        _, top_i = dense._choose(dense._route(x))
        return dense, x, np.asarray(top_i).reshape(-1)

    def _layout_of(self, layer, tokens, top_k, held):
        """``(n_groups, tm, cap)`` as ``MoE._grouped_forward`` sizes it
        (``cap`` None for the whole layer)."""
        from torchdistx_tpu.ops.grouped_matmul import row_tile

        if layer == "whole":
            return self.E, row_tile(tokens * top_k, self.E, jnp.float32), None
        n_held = held[1] - held[0]
        expected = tokens * top_k * n_held // self.E
        tm = row_tile(expected, n_held, jnp.float32)
        return n_held, tm, -(-2 * expected // tm) * tm

    @pytest.mark.parametrize(
        "layer,tokens,top_k,boosted",
        [c[1:] for c in LAYOUT_CASES], ids=[c[0] for c in LAYOUT_CASES],
    )
    def test_plan_holds_each_held_pair_once(self, layer, tokens, top_k, boosted):
        """Each held pair sits in exactly one padded row, a tile belongs
        to one group, an absent pair sits in none, and ``n_tiles`` /
        ``groups`` are NumPy's counts: in the layout the layer would
        use (the capped one where the held rows fit it)."""
        from torchdistx_tpu.ops.grouped_matmul import plan_groups

        _, _, chosen = self._routing(tokens, top_k, boosted)
        held = (0, self.E) if layer == "whole" else (0, 4)
        n_groups, tm, cap = self._layout_of(layer, tokens, top_k, held)
        here = (chosen >= held[0]) & (chosen < held[1])
        ids = np.where(here, chosen - held[0], n_groups).astype(np.int32)
        fits = cap is not None and here.sum() <= cap
        assert fits == (layer == "share" and boosted != (0, 1, 2, 3))
        tiles = cap // tm + n_groups if fits else None
        plan = plan_groups(jnp.asarray(ids), n_groups, tm, tiles)
        n_layout = plan.tile_group.shape[0]
        if tiles is None:
            assert n_layout == -(-len(ids) // tm) + min(n_groups, len(ids))
        else:
            assert n_layout == tiles < -(-len(ids) // tm) + n_groups
        padded_rows = n_layout * tm
        assert plan.src.shape == (padded_rows,) and plan.tm == tm
        sizes = np.bincount(ids[here], minlength=n_groups)[:n_groups]
        n_tiles = max(1, int(sum(-(-s // tm) for s in sizes)))
        assert int(plan.n_tiles[0]) == n_tiles <= n_layout
        assert int(plan.groups) == int((sizes > 0).sum())
        dest, src = np.asarray(plan.dest), np.asarray(plan.src)
        tile_group = np.asarray(plan.tile_group)
        assert (dest[~here] == padded_rows).all()  # an absent pair: no row
        mine = dest[here]
        assert len(set(mine.tolist())) == len(mine)  # one row a held pair
        assert (mine < n_tiles * tm).all()
        np.testing.assert_array_equal(src[mine], np.flatnonzero(here))
        np.testing.assert_array_equal(tile_group[mine // tm], ids[here])
        dead = np.ones((padded_rows,), bool)
        dead[mine] = False
        assert not src[dead].any()  # and nobody else's pair in any row
        assert (tile_group[n_tiles:] == tile_group[n_tiles - 1]).all()
        assert ((0 <= tile_group) & (tile_group < n_groups)).all()

    @pytest.mark.parametrize(
        "layer,tokens,top_k,boosted",
        [c[1:] for c in LAYOUT_CASES], ids=[c[0] for c in LAYOUT_CASES],
    )
    def test_layer_equals_the_dense_reference(self, layer, tokens, top_k, boosted):
        """The grouped layer (every one of the four shares, where the
        case is a share's) against the dense float32 layer over the
        experts it holds; the shares add up to the whole layer; the
        call whose held rows pass its layout's cap says so and is still
        exact."""
        from torchdistx_tpu.nn.moe import moe_count_tape, tape_totals

        dense, x, chosen = self._routing(tokens, top_k, boosted)
        params = dict(dense.named_parameters())
        want = np.asarray(dense(x))
        assert np.abs(want).max() > 1e-3
        kw = dict(top_k=top_k, dispatch_mode="grouped")
        if layer == "whole":
            grouped = MoE(self.D, self.F, self.E, **kw)
            with moe_count_tape() as tape:
                got = functional_call(grouped, params, (x,))
            counts = np.asarray(tape_totals(tape))
            assert counts.tolist() == [
                tokens * top_k, len(np.unique(chosen))]
            np.testing.assert_allclose(
                np.asarray(got), want, rtol=1e-5, atol=1e-5)
            return
        total = np.zeros_like(want)
        for lo in range(0, self.E, 4):
            hi = lo + 4
            part = MoE(self.D, self.F, self.E, held=(lo, hi), **kw)
            mine = dict(params)
            for name in ("w_gate", "w_up", "w_down"):
                mine[name] = params[name][lo:hi]

            def run(p, v, m=part):  # the tape read where it was written
                with moe_count_tape() as tape:
                    y = functional_call(m, p, (v,))
                return y, tape_totals(tape)

            got, counts = (np.asarray(a) for a in jax.jit(run)(mine, x))
            here = (chosen >= lo) & (chosen < hi)
            _, tm, cap = self._layout_of("share", tokens, top_k, (lo, hi))
            assert counts.tolist() == [
                here.sum(), len(np.unique(chosen[here])), (~here).sum(),
                int(here.sum() > cap),
            ]
            # the dense layer with every other expert's output zeroed
            outside = jnp.ones((self.E, 1, 1)).at[lo:hi].set(0.0) > 0
            alone = dict(params, w_down=jnp.where(outside, 0.0, params["w_down"]))
            np.testing.assert_allclose(
                got, np.asarray(functional_call(dense, alone, (x,))),
                rtol=1e-5, atol=1e-5,
            )
            total += got
        np.testing.assert_allclose(total, want, rtol=1e-5, atol=2e-5)
        over = boosted == (0, 1, 2, 3)
        assert (int((chosen < 4).sum()) > self._layout_of(
            "share", tokens, top_k, (0, 4))[2]) == over

    @pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "pallas"])
    def test_fallback_is_exact_under_the_kernel_and_counted_by_the_engine_names(
        self, use_kernel
    ):
        """Held rows over ``cap``: the full-size layout runs (both
        branches of the ``cond`` under ``jit`` and under ``grad``), and
        ``ServeMetrics`` files the call under ``moe_layout_overflows``."""
        from torchdistx_tpu.nn.moe import moe_count_tape, tape_totals
        from torchdistx_tpu.serve.metrics import ServeMetrics

        dense, x, chosen = self._routing(24, 4, (0, 1, 2, 3))
        params = dict(dense.named_parameters())
        part = MoE(self.D, self.F, self.E, top_k=4, dispatch_mode="grouped",
                   held=(0, 4), use_kernel=use_kernel)
        mine = dict(params)
        for name in ("w_gate", "w_up", "w_down"):
            mine[name] = params[name][:4]

        def run(p, v):
            with moe_count_tape() as tape:
                y = functional_call(part, p, (v,))
            return y, tape_totals(tape)

        got, counts = jax.jit(run)(mine, x)
        assert np.asarray(counts).tolist() == [96, 4, 0, 1]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(dense(x)), rtol=1e-5, atol=1e-5)
        metrics = ServeMetrics(num_slots=1)
        metrics.add_device_counts("prefill", counts)
        metrics.add_device_counts("decode", jnp.asarray([8, 2, 24, 0]))
        metrics.sync_device_counters()
        c = metrics.counters
        assert (c["moe_layout_overflows"], c["moe_layout_overflows_prefill"],
                c["moe_layout_overflows_decode"]) == (1, 1, 0)
        assert c["moe_routed_rows"] == 104 and c["moe_rows_elsewhere"] == 24
        if not use_kernel:  # the kernel has no derivative rule
            g = jax.grad(
                lambda p: jnp.mean(functional_call(part, p, (x,)) ** 2))(mine)
            assert float(jnp.abs(g["w_gate"]).sum()) > 0.0

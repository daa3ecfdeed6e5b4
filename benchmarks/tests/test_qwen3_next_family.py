"""The Qwen3-Next family in the harness: its counts at the published
widths worked by hand (ISSUE 36's arithmetic), what it brings to the
serving driver, the share of the experts it is given, its rehearsal cell
on the CPU, its control, the planted fault (a reference that loses the
delta-rule state at the prefill/decode seam), and its readers on a
recorded reduction and on a program that has nothing for them to read."""

import json
import os
import types

import numpy as np
import pytest
import run
from harness import loader, reference

QWEN = os.path.join(loader.ROOT, "configs", "qwen3-next-80b-a3b-1chip.json")
NEEDS = ("reference.ServeReference", "reference.FAULTS", "counts.serve_flops",
         "counts.gdn_update_need", "counts.gdn_chunk_need",
         "counts.grouped_matmul_need")
TINY = "tiny-qwen3-next.batch4"


@pytest.fixture(scope="module")
def family():
    return loader.load_family("qwen3_next", needs=NEEDS)


@pytest.fixture(scope="module")
def qwen():
    with open(QWEN) as f:
        return json.load(f)


def test_model_counts_at_the_published_widths(family, qwen):
    c = family.counts
    assert c.layer_split(qwen) == (6, 2)  # attention at layers 3 and 7
    # in_proj_qkvz 2048 x 12288, in_proj_ba 2048 x 64, out_proj 4096 x 2048
    assert c.gdn_matmul_params(qwen) == 25165824 + 131072 + 8388608
    # conv 8192 x 4, dt_bias 32, A_log 32, the gated norm 128
    assert c.gdn_other_params(qwen) == 32768 + 32 + 32 + 128
    assert c.gdn_params(qwen) == 33718464
    # q_proj 2048 x 8192 (query and gate), k_proj, v_proj 2048 x 512,
    # o_proj 4096 x 2048, q/k norms 2 x 256
    assert c.attention_params(qwen) == 16777216 + 2097152 + 8388608 + 512
    assert c.attention_params(qwen) == 27263488
    # router 2048 x 512, shared expert 3 x 2048 x 512, its gate, two norms
    assert c.expert_layer_fixed_params(qwen) == 1048576 + 3145728 + 2048 + 4096
    assert c.expert_layer_fixed_params(qwen) == 4200448
    assert c.expert_params(qwen) == 3145728
    gdn_layer = 33718464 + 4200448 + 128 * 3145728  # 440.57 M
    attn_layer = 27263488 + 4200448 + 128 * 3145728  # 434.12 M
    assert c.total_params(qwen) == (
        2 * 37984 * 2048 + 2048 + 6 * gdn_layer + 2 * attn_layer)
    assert round(c.total_params(qwen) / 1e9, 3) == 3.667
    # 10 choices over 512 experts, 128 held: 2.5 held experts a token
    assert c.experts_used_here(qwen) == 2.5
    ffn = 1048576 + 3145728 + 2048 + 2.5 * 3145728
    used = 6 * (33685504 + ffn) + 2 * (27262976 + ffn)
    assert c.matmul_params_used(qwen) == used
    # a decoded token over no rows: the matrices it uses, the head over
    # the vocabulary's slice, and 7 x 32 x 128 x 128 a Gated-DeltaNet layer
    assert c.delta_rule_flops(qwen) == 7 * 32 * 128 * 128 == 3670016
    assert c.serve_flops(qwen, [], [0]) == (
        2.0 * used + 2.0 * 37984 * 2048 + 6 * 3670016)
    # a prompt of 3 tokens and a decoded token over 4 rows: the head works
    # for the 2 tokens that are sampled; attention 16 heads of 256, QK^T
    # and PV, in the 2 attention layers
    assert c.serve_flops(qwen, [3], [4]) == (
        2.0 * used * 4 + 2.0 * 37984 * 2048 * 2 + 6 * 3670016 * 4
        + 2.0 * (2 * 16 * 256) * 2 * (6 + 4))


def test_kernel_needs_at_the_published_widths(family, qwen):
    c = family.counts
    assert c.gdn_state_bytes(qwen) == 32 * 128 * 128 * 4 == 2097152
    assert c.conv_state_bytes(qwen) == 3 * 8192 * 2 == 49152
    assert c.state_slot_bytes(qwen) == 6 * (2097152 + 49152) == 12877824
    # a token's operands: q, k (2048 each), v, o (4096 each) in bf16; g
    # and beta a head in float32
    row = (2 * 2048 + 2 * 4096) * 2 + 2 * 32 * 4
    flops, nbytes = c.gdn_update_need(qwen, 128)
    assert flops == 3670016.0 * 128
    assert nbytes == 128 * (2 * 2097152 + row)
    assert c.gdn_update_need(qwen, 64)[1] == nbytes / 2  # half the slots
    # a prompt of 600 true tokens, whatever its bucket: 10 chunks of 64
    flops, nbytes = c.gdn_chunk_need(qwen, 600)
    per_chunk = (4 * 64 * 64 * 128 + 4 * 5 * 64 ** 3 + 6 * 64 * 128 * 128
                 + 4 * 64 * 64 * 128)
    assert flops == float(per_chunk) * 10 * 32
    assert nbytes == 600 * row + 2 * 2097152
    # 1280 held rows over 118 held experts touched
    flops, nbytes = c.grouped_matmul_need(qwen, 1280, 118)
    assert flops == 2.0 * 3145728 * 1280
    assert nbytes == 118 * 3145728 * 2 + 1280 * 2 * 2048 * 2


def test_needs_are_what_the_engines_slots_hold(family, qwen):
    """The bytes the decode kernel's need counts a slot are the bytes the
    program's cache holds a slot (``state_slot_bytes``, 12.88 MB), less
    the convolution's rows, which the kernel does not touch; a KV row at
    head 256 is 2048 B."""
    import torchdistx_tpu as tdx
    from torchdistx_tpu.serve import SlotKVCache

    model = tdx.deferred_init(family.constructor(qwen))  # no weight is made
    cache = SlotKVCache(model, num_slots=2, max_len=16)
    c = family.counts
    assert cache.state_slot_bytes == c.state_slot_bytes(qwen) == 12877824
    assert cache.kv_row_bytes == 2 * 2 * 256 * 2 == 2048
    assert cache.kinds == ("state",) * 3 + ("pair",) + ("state",) * 3 + ("pair",)
    one, none = (c.gdn_update_need(qwen, s)[1] for s in (1, 0))
    row = (2 * 2048 + 2 * 4096) * 2 + 2 * 32 * 4
    assert 6 * (one - none - row) / 2 == (
        cache.state_slot_bytes - 6 * c.conv_state_bytes(qwen))
    params = dict(model.named_parameters())
    assert sum(int(np.prod(p.shape)) for p in params.values()) == (
        c.total_params(qwen))


def test_the_family_brings_what_the_serving_driver_needs(family, qwen):
    assert set(family.reference.PRECISIONS) == {"f32", "bf16", "int8"}
    assert not hasattr(family.reference, "TrainReference")  # no training cell
    arch = family.reference.Arch.from_config(qwen)
    assert (arch.router_width, arch.num_experts, arch.held_from) == (512, 128, 0)
    plan = family.reference.leaf_plan(arch)
    counters = [c for _, _, c in plan if c is not None]
    assert counters == list(range(len(counters)))
    # the embedding, the final norm, the head; 17 leaves in a
    # Gated-DeltaNet block (7 the mixer's, 8 the expert layer's), 16 in an
    # attention block
    assert len(plan) == 3 + 6 * 17 + 2 * 16
    shapes = {name: shape for name, shape, _ in plan}
    assert shapes["lm_head.weight"] == (37984, 2048)  # untied
    assert shapes["blocks.0.mixer.in_proj_qkvz.weight"] == (12288, 2048)
    assert shapes["blocks.0.mixer.in_proj_ba.weight"] == (64, 2048)
    assert shapes["blocks.0.mixer.conv_weight"] == (8192, 4)
    assert shapes["blocks.0.mixer.A_log"] == (32,)
    assert shapes["blocks.3.mixer.wq.weight"] == (8192, 2048)  # query and gate
    assert shapes["blocks.7.mixer.wk.weight"] == (512, 2048)
    assert "blocks.4.mixer.wq.weight" not in shapes
    # the router scores all 512; the stacks hold the share
    assert shapes["blocks.0.mlp.router.weight"] == (512, 2048)
    assert shapes["blocks.0.mlp.w_gate"] == (128, 2048, 512)
    assert shapes["blocks.0.mlp.w_down"] == (128, 512, 2048)
    assert shapes["blocks.0.mlp.shared_gate.weight"] == (1, 2048)
    ones = {name for name, _, c in plan if c is None}
    assert "blocks.0.mixer.norm.weight" in ones
    assert "blocks.3.mixer.q_norm.weight" in ones
    assert "blocks.0.mixer.A_log" not in ones


def test_the_configuration_carries_the_catalog_rows_values(qwen):
    """Every key of the catalog row's ``config`` under its published
    name and value, but for the four that are reduced."""
    catalog = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    differ = {k for k, v in catalog.items() if qwen.get(k, "absent") != v}
    assert differ == set(qwen["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"}
    assert qwen["published"] == {k: catalog[k] for k in qwen["reduced"]}
    assert (qwen["num_hidden_layers"], qwen["num_experts"], qwen["vocab_size"],
            qwen["max_position_embeddings"]) == (8, 128, 37984, 4096)
    # the share is stated beside the reduced count: a router of 512, top 10
    assert (qwen["router_width"], qwen["experts_held"]) == (512, [0, 128])
    assert qwen["num_experts_per_tok"] == 10
    assert set(qwen["assumed"]) == {
        "torch_dtype", "initializer_range", "gdn_state_dtype",
        "conv_state_dtype", "mtp", "norm_scales", "column_order",
        "gdn_initialisation"}
    assert qwen["deployment"].startswith("one chip of a four-chip host")
    bench = loader.benchmark_json()
    entry = [c for c in bench["configs"]
             if c["name"] == "qwen3-next-80b-a3b-1chip"][0]
    assert entry["reduced"] == qwen["reduced"]
    assert entry["source"] == qwen["source"]
    assert bench["configs"][-1] is entry and len(entry["why"]) <= 200


def test_the_cell_is_the_issues_traffic_letter_for_letter():
    cell = loader.load_cell("qwen3-next-80b.batch128-4k")
    assert cell.chips == 1 and cell.driver_kind == "serve_closed_loop"
    assert len(cell.why) <= 200
    t = cell.traffic
    assert t["clients"] == 128 and t["temperature"] == 0.0
    assert t["engine"] == {"num_slots": 128, "max_len": 4096,
                           "prefill_buckets": [512, 1024, 2048, 3072]}
    assert t["prompt_len"] == {
        "dist": "log_uniform", "min": 256, "max": 3072, "levels": 8}
    assert t["output_len"] == {
        "dist": "log_uniform", "min": 128, "max": 1024, "levels": 8}
    assert (t["check_requests"], t["check_width"], t["trace_seconds"]) == (
        8, 4096, 4.0)
    from harness import traffic

    prompts = traffic.length_grid(t["prompt_len"])
    outputs = traffic.length_grid(t["output_len"])
    assert (prompts[0], prompts[-1]) == (299, 2630)
    assert (outputs[0], outputs[-1]) == (146, 899)
    # 2 / 2 / 3 / 1 of 8 prompts in the four buckets
    assert [sum(lo < n <= hi for n in prompts) for lo, hi in
            ((0, 512), (512, 1024), (1024, 2048), (2048, 3072))] == [2, 2, 3, 1]
    assert max(prompts) + max(outputs) < t["engine"]["max_len"] == t["check_width"]
    assert {m.name for m in cell.end_to_end} == {"setup_s", "serve_tokens_per_s"}
    names = {m.name for m in cell.per_layer}
    assert {"serve.gdn_update_roofline", "serve.gdn_chunk_roofline",
            "serve.expert_share_matmul_roofline",
            "serve.gated_attn_decode_roofline", "serve.moe_rows_held_pct",
            "serve.step_mfu_pct", "serve.device_idle_pct",
            "serve.moe_rows_per_group", "serve.scan_pad_share_pct",
            "materialize_s", "compile_s"} <= names
    # readers that count num_hidden_layers attention or expert layers of
    # another family's shape are not this cell's
    assert not {"serve.decode_attn_roofline", "serve.flash_prefill_roofline",
                "serve.grouped_matmul_roofline",
                "serve.state_update_roofline"} & names


def test_sound_serve_run_of_the_family_is_correct(drive):
    result = drive(TINY)
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["weights_differ"]["value"] == 0
    assert result["counts"]["serve.requests_finished"] > 0


def test_altered_token_is_not_correct(drive, monkeypatch):
    from torchdistx_tpu.serve.engine import ServeEngine

    real = ServeEngine._record_first
    monkeypatch.setattr(
        ServeEngine, "_record_first",
        lambda self, req, tok, now: real(self, req, (int(tok) + 1) % 256, now))
    result = drive(TINY)
    assert result["correct"] is False
    c = result["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_serve_control_is_not_correct():
    """The float32 toy's served tokens stay within the cell's limits; the
    tokens its reference in the next precision down (bfloat16) puts
    first do not."""
    seed = 2**31 + 4
    cell = loader.load_cell(TINY, rehearsal=True)
    drv = loader.load_driver(cell.driver_kind).Driver(
        run.Context(cell, seed, 1.0, False))
    drv.setup()
    drv.window(2.0)
    drv.after_window()
    drv.free()
    seqs, lens = drv.sample()
    family_ref = drv.family.reference
    served, ctl = reference.served_gaps(
        family_ref.ServeReference(drv.arch, seed, "f32"), seqs, lens,
        family_ref.ServeReference(drv.arch, seed, "bf16"))
    lim = cell.limits
    assert drv.weights_differ == 0
    assert max(served["max"]) <= lim["logit_gap"]
    assert sum(served["sum"]) / sum(served["tokens"]) <= lim["logit_gap_mean"]
    assert (max(ctl["max"]) > lim["logit_gap"]
            or sum(ctl["sum"]) / sum(ctl["tokens"]) > lim["logit_gap_mean"])


def test_the_plan_is_the_programs_construction_order(family):
    """``weights_differ`` (0 in the sound run above) holds the plan to
    the order in which the program draws: the convolution after the two
    in projections, ``A_log`` after ``dt_bias``, the shared expert's gate
    last of the expert layer, the norms not drawn at all."""
    ref = family.reference
    arch = ref.Arch.from_config(loader.load_cell(TINY, rehearsal=True).config)
    assert (arch.router_width, arch.num_experts, arch.held_from) == (32, 8, 8)
    plan = {name: counter for name, _, counter in ref.leaf_plan(arch)}
    assert plan["blocks.0.mixer.in_proj_qkvz.weight"] == 1
    assert plan["blocks.0.mixer.in_proj_ba.weight"] + 1 == plan["blocks.0.mixer.conv_weight"]
    assert plan["blocks.0.mixer.dt_bias"] + 1 == plan["blocks.0.mixer.A_log"]
    assert plan["blocks.0.mixer.A_log"] + 1 == plan["blocks.0.mixer.out_proj.weight"]
    assert plan["blocks.0.mixer.norm.weight"] is None
    assert plan["blocks.0.mlp.router.weight"] == plan["blocks.0.mixer.out_proj.weight"] + 1
    assert plan["blocks.0.mlp.shared_gate.weight"] == plan["blocks.0.mlp.shared.w_down.weight"] + 1
    assert plan["blocks.1.mixer.in_proj_qkvz.weight"] == plan["blocks.0.mlp.shared_gate.weight"] + 1
    assert plan["blocks.3.mixer.wq.weight"] == plan["blocks.2.mlp.shared_gate.weight"] + 1


def test_the_reference_is_given_the_same_share(family):
    """The reference's expert layer sums the held experts only, under
    weights renormalised over ALL the chosen: the four shares of a layer
    (the shared expert counted once) add up to the layer that holds all."""
    import jax
    import jax.numpy as jnp

    ref = family.reference
    base = loader.load_cell(TINY, rehearsal=True).config
    whole = ref.Arch.from_config(
        dict(base, num_experts=32, router_width=32, experts_held=[0, 32]))
    rs = np.random.RandomState(0)
    d, f = whole.hidden_size, whole.moe_intermediate_size
    w = {"mlp.router.weight": rs.randn(32, d), "mlp.w_gate": rs.randn(32, d, f),
         "mlp.w_up": rs.randn(32, d, f), "mlp.w_down": rs.randn(32, f, d),
         "mlp.shared.w_gate.weight": rs.randn(f, d),
         "mlp.shared.w_up.weight": rs.randn(f, d),
         "mlp.shared.w_down.weight": rs.randn(d, f),
         "mlp.shared_gate.weight": rs.randn(1, d)}
    w = {k: jnp.asarray(0.1 * v, jnp.float32) for k, v in w.items()}
    x = jnp.asarray(rs.randn(2, 5, d), jnp.float32)
    full = ref.experts(whole, "f32", x, w)
    shared = jax.nn.sigmoid(x @ w["mlp.shared_gate.weight"].T) * ref.swiglu(
        x, w["mlp.shared.w_gate.weight"], w["mlp.shared.w_up.weight"],
        w["mlp.shared.w_down.weight"], "f32")
    total = shared
    for lo in (0, 8, 16, 24):
        part = ref.Arch.from_config(dict(base, experts_held=[lo, lo + 8]))
        held = {k: (v[lo:lo + 8] if k in ("mlp.w_gate", "mlp.w_up", "mlp.w_down")
                    else v) for k, v in w.items()}
        total = total + ref.experts(part, "f32", x, held) - shared
    assert float(jnp.abs(full).max()) > 0.01
    np.testing.assert_allclose(np.asarray(total), np.asarray(full), atol=1e-5)


def test_the_planted_fault_reads_far_above_the_next_precision_down(family):
    """A reference that drops ``S`` and ``conv`` at the seam (decode
    starts from empty state) against the sound one, float32, two periods
    of four layers at hidden 128: the same logits before the seam to the
    bit; AT the seam the logits move by several times what the control in
    the next precision down (bfloat16) moves them anywhere."""
    cfg = dict(loader.load_cell(TINY, rehearsal=True).config,
               hidden_size=128, num_hidden_layers=8, vocab_size=1024)
    ref = family.reference
    arch = ref.Arch.from_config(cfg)
    t, p, seed = 48, 32, 2**31 + 9
    tokens = np.random.RandomState(0).randint(0, 1024, (4, t)).astype(np.int32)
    lens = [(p, t)] * 4
    assert ref.FAULTS["drop_state_at_seam"](lens) == {"drop_state_at": [p] * 4}

    def logits(**kw):
        return np.stack([np.asarray(row) for _, row in
                         ref.ServeReference(arch, seed, **kw).logits_rows(tokens)])

    sound = logits(precision="f32")
    fault = logits(precision="f32", **ref.FAULTS["drop_state_at_seam"](lens))
    control = logits(precision="bf16")
    np.testing.assert_array_equal(fault[:, :p], sound[:, :p])
    moved = np.abs(fault - sound).max(axis=(0, 2))  # by position
    assert moved[p] > 0.2 * float(sound.std())  # at the seam, at once
    assert moved[p:].max() > 5 * np.abs(control - sound).max()


def _ctx(qwen, ops, **counters):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(config=qwen), device_kind="TPU v5 lite",
        reduction={"ops": ops}, counters=counters,
        family=lambda *needs: loader.load_family("qwen3_next", needs=needs))


def _readers():
    return loader.load_module(
        os.path.join(loader.ROOT, "metrics", "qwen3_next_readers.py"),
        "metric reader module")


def test_readers_on_a_recorded_reduction(family, qwen):
    """Two traced decode steps and one traced prefill, as the compiler
    names the calls: each share is the need over the time, by hand."""
    from torchdistx_tpu.serve.metrics import ServeMetrics

    readers, c = _readers(), family.counts
    pallas = "pallas custom-call f32[1]"
    ops = (
        [[f"tdx_gated_delta_update.{i}", 0, 900_000, pallas] for i in range(12)]
        + [[f"tdx_decode_attention.{i}", 0, 800_000, pallas] for i in range(4)]
        # a prefill's kernel may come fused with the write of its state
        # into the slab, under the call's own name
        + [[f"tdx_gated_delta_chunk.{i}", 0, 1_500_000,
            "fusion (f32[128,32,128,128], bf16[1,32,1024,128])"] for i in range(6)]
        + [[f"tdx_grouped_matmul.{i}", 0, 600_000, pallas] for i in range(48)]
        + [["tdx_flash_forward.1", 0, 100_000, pallas],
           ["gated_delta_epilogue.9", 0, 999, "fusion f32[1]"]])  # another op
    m = ServeMetrics(num_slots=2)  # the latest: what the readers find
    m.counters.update({"moe_routed_rows": 1_000_000, "moe_groups": 90_000,
                       "moe_rows_elsewhere": 3_100_000,
                       "moe_routed_rows_decode": 900_000,
                       "moe_rows_elsewhere_decode": 2_700_000,
                       "moe_groups_decode": 80_000})
    ctx = _ctx(qwen, ops, **{
        "serve.decode_dispatches": 100, "serve.prefill_calls": 20,
        "serve.tokens": 12520, "serve.prompt_lens": [900] * 20,
        "serve.decode_rows_sum": 20_000_000})
    bw = 819e9
    # 125 slots decoded a step; bytes bound
    need = c.gdn_update_need(qwen, 125.0)[1] / bw
    assert readers.serve_gdn_update_roofline(ctx) == pytest.approx(
        100.0 * 12 * need / (12 * 900e-6))
    flops, nbytes = c.gdn_chunk_need(qwen, 900)
    need = max(flops / 197e12, nbytes / bw)
    assert readers.serve_gdn_chunk_roofline(ctx) == pytest.approx(
        100.0 * 6 * need / (6 * 1500e-6))
    # the window's held rows and held experts touched, over its 2 x 8 x 120
    # calls, scaled to the 48 calls traced
    flops, nbytes = c.grouped_matmul_need(qwen, 1_000_000, 90_000)
    need = max(flops / 197e12, nbytes / bw)
    assert readers.serve_expert_share_matmul_roofline(ctx) == pytest.approx(
        100.0 * need * (48 / (2 * 8 * 120)) / (48 * 600e-6))
    # 200,000 visible rows a step, K and V of 2 heads of 256 in bf16, over
    # the 4 calls traced (2 steps of 2 attention layers)
    need = 2.0 * 200_000 * 2 * 256 * 2 * 4 / bw
    assert readers.serve_gated_attn_decode_roofline(ctx) == pytest.approx(
        100.0 * need / (4 * 800e-6))
    assert readers.serve_moe_rows_held_pct(ctx) == pytest.approx(25.0)
    for name in ("serve_gdn_update_roofline", "serve_gdn_chunk_roofline",
                 "serve_expert_share_matmul_roofline",
                 "serve_gated_attn_decode_roofline", "serve_moe_rows_held_pct"):
        assert 0 < getattr(readers, name)(ctx) < 100, name


def test_readers_find_nothing_in_a_program_without_their_kernels(qwen):
    """On a program without these kernels and counters every reader
    returns None and none raises."""
    from torchdistx_tpu.serve.metrics import ServeMetrics

    ServeMetrics(num_slots=2)  # the latest: no expert counter in it
    readers = _readers()
    ctx = _ctx(qwen, [], **{
        "serve.decode_dispatches": 10, "serve.decode_rows_sum": 500,
        "serve.tokens": 100, "serve.prompt_lens": [100, 200]})
    names = ("serve_gdn_update_roofline", "serve_gdn_chunk_roofline",
             "serve_expert_share_matmul_roofline",
             "serve_gated_attn_decode_roofline", "serve_moe_rows_held_pct")
    for name in names:
        assert getattr(readers, name)(ctx) is None, name
    ctx.reduction = None  # an untraced run
    for name in names[:4]:
        assert getattr(readers, name)(ctx) is None, name

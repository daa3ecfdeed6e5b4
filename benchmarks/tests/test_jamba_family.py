"""The Jamba family in the harness: its counts at the published widths
worked by hand, what it brings to the serving driver, its rehearsal cell
on the CPU, the planted fault (a reference that loses the recurrent
state at the prefill/decode seam), and its readers on a recorded
reduction and on a program that has nothing for them to read."""

import json
import os
import types

import numpy as np
import pytest
from harness import loader

JAMBA = os.path.join(loader.ROOT, "configs", "jamba2-3b-1chip.json")
NEEDS = ("reference.ServeReference", "reference.FAULTS", "counts.serve_flops",
         "counts.selective_scan_need", "counts.state_update_need")


@pytest.fixture(scope="module")
def family():
    return loader.load_family("jamba", needs=NEEDS)


@pytest.fixture(scope="module")
def jamba():
    with open(JAMBA) as f:
        return json.load(f)


def test_model_counts_at_the_published_widths(family, jamba):
    c = family.counts
    assert c.layer_split(jamba) == (26, 2)  # attention at layers 7 and 21
    # W_in 2560 x 10240, W_x 5120 x 192, W_dt 160 x 5120, W_out 5120 x 2560
    assert c.mamba_matmul_params(jamba) == 26214400 + 983040 + 819200 + 13107200
    assert c.mamba_matmul_params(jamba) == 41123840  # 41.12 M
    # conv 5120 x 4 + bias, dt bias, A_log 5120 x 16, D, norms of 160 + 16 + 16
    assert c.mamba_other_params(jamba) == 20480 + 5120 + 5120 + 81920 + 5120 + 192
    assert c.mlp_params(jamba) == 3 * 2560 * 8192 == 62914560
    # W_q, W_o 2560 x 2560; W_k, W_v 2560 x 128 (one KV head)
    assert c.attention_params(jamba) == 2 * 6553600 + 2 * 327680 == 13762560
    mamba_layer = 41123840 + 117952 + 62914560 + 2 * 2560  # 104.16 M
    attn_layer = 13762560 + 62914560 + 2 * 2560  # 76.68 M
    assert c.total_params(jamba) == (
        65536 * 2560 + 2560 + 26 * mamba_layer + 2 * attn_layer)
    assert round(c.total_params(jamba) / 1e9, 3) == 3.029
    used = 26 * (41123840 + 62914560) + 2 * (13762560 + 62914560)
    assert c.matmul_params_used(jamba) == used
    # a decoded token over no rows: the matrices it uses and the tied head
    # (6.05 GFLOP) and 7 x 5120 x 16 a Mamba layer for the recurrence
    assert round(2.0 * (used + 65536 * 2560) / 1e9, 2) == 6.05
    assert c.serve_flops(jamba, [], [0]) == (
        2.0 * (used + 65536 * 2560) + 26 * 7 * 5120 * 16)
    # a prompt of 3 tokens and a decoded token over 4 rows: the head works
    # for the 2 tokens that are sampled; attention 20 heads of 128, QK^T
    # and PV, in the 2 attention layers
    assert c.serve_flops(jamba, [3], [4]) == (
        2.0 * used * 4 + 2.0 * 65536 * 2560 * 2 + 26 * 7 * 5120 * 16 * 4
        + 2.0 * (2 * 20 * 128) * 2 * (6 + 4))


def test_kernel_needs_at_the_published_widths(family, jamba):
    c = family.counts
    assert c.ssm_state_bytes(jamba) == 5120 * 16 * 4 == 327680
    assert c.conv_state_bytes(jamba) == 3 * 5120 * 2 == 30720
    assert c.state_slot_bytes(jamba) == 26 * (327680 + 30720) == 9318400
    coefficients = (5120 * 16 + 5120) * 2
    # 256 slots: each one's state in and out, x / dt / z in and y out in
    # bf16, B and C
    flops, nbytes = c.state_update_need(jamba, 256)
    assert flops == 7.0 * 5120 * 16 * 256
    assert nbytes == 256 * (2 * 327680 + (4 * 5120 + 32) * 2) + coefficients
    # half the slots decoded: half the state
    assert c.state_update_need(jamba, 128)[1] - coefficients == (
        nbytes - coefficients) / 2
    # a prompt of 600 true tokens, whatever its bucket
    flops, nbytes = c.selective_scan_need(jamba, 600)
    assert flops == 7.0 * 5120 * 16 * 600
    assert nbytes == 600 * (4 * 5120 + 32) * 2 + coefficients + 327680


def test_state_update_need_is_what_the_engines_slots_hold(family, jamba):
    """The bytes the decode kernel's need counts a slot are the bytes the
    program's cache holds a slot (``state_slot_bytes``), less the
    convolution's rows, which the kernel does not touch."""
    import torchdistx_tpu as tdx
    from torchdistx_tpu.serve import SlotKVCache

    model = tdx.deferred_init(family.constructor(jamba))  # no weight is made
    cache = SlotKVCache(model, num_slots=2, max_len=16)
    c = family.counts
    assert cache.state_slot_bytes == c.state_slot_bytes(jamba) == 9318400
    assert cache.kv_row_bytes == 2 * 128 * 2 == 512
    mamba_layers = c.layer_split(jamba)[0]
    one, none = (c.state_update_need(jamba, s)[1] for s in (1, 0))
    rows = (4 * 5120 + 2 * 16) * 2
    assert mamba_layers * (one - none - rows) / 2 == (
        cache.state_slot_bytes - mamba_layers * c.conv_state_bytes(jamba))


def test_the_family_brings_what_the_serving_driver_needs(family, jamba):
    assert set(family.reference.PRECISIONS) == {"f32", "bf16", "int8"}
    assert not hasattr(family.reference, "TrainReference")  # no training cell
    arch = family.reference.Arch.from_config(jamba)
    plan = family.reference.leaf_plan(arch)
    counters = [c for _, _, c in plan if c is not None]
    assert counters == list(range(len(counters)))
    # the embedding and the final norm (no head: tied); 17 leaves in a
    # Mamba block (12 of them the mixer's), 9 in an attention block
    assert len(plan) == 2 + 26 * 17 + 2 * 9
    shapes = {name: shape for name, shape, _ in plan}
    assert "lm_head.weight" not in shapes
    assert shapes["blocks.0.mixer.in_proj.weight"] == (10240, 2560)
    assert shapes["blocks.0.mixer.x_proj.weight"] == (192, 5120)
    assert shapes["blocks.0.mixer.A_log"] == (5120, 16)
    assert shapes["blocks.7.mixer.wk.weight"] == (128, 2560)
    assert shapes["blocks.21.mixer.wq.weight"] == (2560, 2560)
    assert "blocks.8.mixer.wq.weight" not in shapes
    ones = {name for name, _, c in plan if c is None}
    assert "blocks.0.mixer.D" in ones and "blocks.0.mixer.A_log" not in ones


def test_the_configuration_carries_the_catalog_rows_values(jamba):
    """Every key of the catalog row's ``config`` under its published
    name and value; nothing is reduced."""
    catalog = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
        "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
        "num_hidden_layers": 28, "num_key_value_heads": 1,
        "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
        "sliding_window": None, "tie_word_embeddings": True,
        "use_mamba_kernels": True, "vocab_size": 65536}
    assert {k for k, v in catalog.items() if jamba.get(k, "absent") != v} == set()
    assert jamba["reduced"] == [] and jamba["published"] == {}
    assert set(jamba["assumed"]) == {
        "torch_dtype", "initializer_range", "head_dim", "ssm_state_dtype",
        "conv_state_dtype", "mamba_initialisation"}
    assert jamba["deployment"].startswith("the whole model on one chip")
    bench = loader.benchmark_json()
    entry = [c for c in bench["configs"] if c["name"] == "jamba2-3b-1chip"][0]
    assert entry["reduced"] == [] and entry["source"] == jamba["source"]


def test_the_cell_is_the_issues_traffic_letter_for_letter():
    cell = loader.load_cell("jamba2-3b.batch256")
    assert cell.chips == 1 and cell.driver_kind == "serve_closed_loop"
    t = cell.traffic
    assert t["clients"] == 256 and t["temperature"] == 0.0
    assert t["engine"] == {"num_slots": 256, "max_len": 2048,
                           "prefill_buckets": [256, 512, 1024]}
    grid = {"dist": "log_uniform", "min": 128, "max": 1024, "levels": 8}
    assert t["prompt_len"] == grid and t["output_len"] == grid
    assert (t["check_requests"], t["check_width"], t["trace_seconds"]) == (
        8, 2048, 4.0)
    from harness import traffic

    lens = traffic.length_grid(grid)
    assert (lens[0], lens[-1]) == (146, 899)
    # 3 of 8 prompts in bucket 256, 2 in 512, 3 in 1024
    assert [sum(lo < n <= hi for n in lens)
            for lo, hi in ((0, 256), (256, 512), (512, 1024))] == [3, 2, 3]
    assert max(lens) * 2 < t["engine"]["max_len"] == t["check_width"]
    names = {m.name for m in cell.per_layer}
    assert {"serve.state_update_roofline", "serve.selective_scan_roofline",
            "serve.mqa_decode_attn_roofline", "serve.scan_pad_share_pct",
            "serve.step_mfu_pct", "serve.device_idle_pct"} <= names
    # their readers count num_hidden_layers attention layers: not this cell's
    assert not {"serve.decode_attn_roofline",
                "serve.flash_prefill_roofline"} & names


def test_sound_serve_run_of_the_family_is_correct(drive):
    result = drive("tiny-jamba.batch4")
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["weights_differ"]["value"] == 0
    assert result["counts"]["serve.requests_finished"] > 0


def test_altered_token_is_not_correct(drive, monkeypatch):
    from torchdistx_tpu.serve.engine import ServeEngine

    real = ServeEngine._record_first
    monkeypatch.setattr(
        ServeEngine, "_record_first",
        lambda self, req, tok, now: real(self, req, (int(tok) + 1) % 256, now))
    result = drive("tiny-jamba.batch4")
    assert result["correct"] is False
    c = result["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_the_plan_is_the_programs_construction_order(family):
    """``weights_differ`` (0 in the sound run above) holds the plan to
    the order in which the program's ``JambaMamba`` draws: the
    convolution before its bias, ``A_log`` after the dt bias, ``D`` not
    drawn at all."""
    ref = family.reference
    arch = ref.Arch.from_config(loader.load_cell(
        "tiny-jamba.batch4", rehearsal=True).config)
    plan = {name: counter for name, _, counter in ref.leaf_plan(arch)}
    assert plan["blocks.0.mixer.in_proj.weight"] == 1
    assert plan["blocks.0.mixer.conv_weight"] + 1 == plan["blocks.0.mixer.conv_bias"]
    assert plan["blocks.0.mixer.dt_proj.bias"] + 1 == plan["blocks.0.mixer.A_log"]
    assert plan["blocks.0.mixer.A_log"] + 1 == plan["blocks.0.mixer.out_proj.weight"]
    assert plan["blocks.0.mixer.D"] is None
    assert plan["blocks.1.mixer.wq.weight"] == plan["blocks.0.mlp.w_down.weight"] + 1


def test_the_planted_fault_reads_far_above_the_next_precision_down(family):
    """A reference that drops ``h`` and ``conv`` at the seam (decode
    starts from empty state) against the sound one, at hidden 128, one
    period of 14 layers, float32: the same logits before the seam to the
    bit; AT the seam the logits move by most of a standard deviation of
    a logit (read 0.77 of one: best tokens and random ones change
    places) and by ten times and more (read 19) what the control in the next precision down
    (bfloat16) moves them anywhere.  The first decoded tokens show it
    and a handful of tokens later the state has refilled (its memory is
    short: the configuration's ``assumed``), so the limit that sees this
    fault is the one on the LARGEST gap, not the one on the mean.  (The
    toy's argmax is no measure here: with tied embeddings and so few
    layers it repeats its last token whatever the state.)"""
    cfg = dict(loader.load_cell("tiny-jamba.batch4", rehearsal=True).config,
               hidden_size=128, intermediate_size=384, num_hidden_layers=14,
               attn_layer_period=14, attn_layer_offset=7, vocab_size=1024,
               mamba_d_state=16, mamba_dt_rank=8)
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
    std = float(sound.std())
    assert moved[p] > 0.5 * std  # at the seam, at once
    assert moved[p:].max() > 10 * np.abs(control - sound).max()
    # ... and it heals: a handful of tokens later the state is refilled
    assert moved[p + 8:].max() < 0.25 * moved[p]


def _ctx(jamba, ops, **counters):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(config=jamba), device_kind="TPU v5 lite",
        reduction={"ops": ops}, counters=counters,
        family=lambda *needs: loader.load_family("jamba", needs=needs))


def _readers():
    return loader.load_module(
        os.path.join(loader.ROOT, "metrics", "jamba_readers.py"),
        "metric reader module")


def test_readers_on_a_recorded_reduction(family, jamba):
    """Two traced decode steps and one traced prefill, as the compiler
    names the calls: each share is the need over the time, by hand."""
    readers, c = _readers(), family.counts
    pallas = "pallas custom-call f32[1]"
    ops = (
        [[f"tdx_selective_state_update.{i}", 0, 400_000, pallas] for i in range(52)]
        + [[f"tdx_decode_attention.{i}", 0, 500_000, pallas] for i in range(4)]
        # a prefill's scan comes fused with the write of its state into
        # the slab, under the call's own name (as the chip's trace has it)
        + [[f"tdx_selective_scan.{i}", 0, 300_000,
            "fusion (f32[256,16,5120], bf16[1,512,5120])"] for i in range(26)]
        + [["tdx_flash_forward.1", 0, 100_000, pallas],
           ["selective_scan_epilogue.9", 0, 999, "fusion f32[1]"]])  # another op
    ctx = _ctx(jamba, ops, **{
        "serve.decode_dispatches": 100, "serve.tokens": 25040,
        "serve.prompt_lens": [300] * 40, "serve.decode_rows_sum": 20_000_000})
    bw = 819e9
    # 250 slots decoded a step; bytes bound (7 x 82k x 250 FLOPs are nothing)
    need = c.state_update_need(jamba, 250.0)[1] / bw
    assert readers.serve_state_update_roofline(ctx) == pytest.approx(
        100.0 * 52 * need / (52 * 400e-6))
    need = c.selective_scan_need(jamba, 300)[1] / bw
    assert readers.serve_selective_scan_roofline(ctx) == pytest.approx(
        100.0 * 26 * need / (26 * 300e-6))
    # 200,000 visible rows a step, K and V of one head of 128 in bf16, over
    # the 4 calls traced (2 steps of 2 attention layers)
    need = 2.0 * 200_000 * 128 * 2 * 4 / bw
    assert readers.serve_mqa_decode_attn_roofline(ctx) == pytest.approx(
        100.0 * need / (4 * 500e-6))
    for name in ("serve_state_update_roofline", "serve_selective_scan_roofline",
                 "serve_mqa_decode_attn_roofline"):
        assert 0 < getattr(readers, name)(ctx) < 100, name


def test_readers_find_nothing_in_a_program_without_their_kernels(jamba):
    """On the parent of the PR that brought them (no such kernel in the
    trace) every reader returns None and none raises."""
    readers = _readers()
    ctx = _ctx(jamba, [], **{
        "serve.decode_dispatches": 10, "serve.decode_rows_sum": 500,
        "serve.tokens": 100, "serve.prompt_lens": [100, 200]})
    names = ("serve_state_update_roofline", "serve_selective_scan_roofline",
             "serve_mqa_decode_attn_roofline")
    for name in names:
        assert getattr(readers, name)(ctx) is None, name
    ctx.reduction = None  # an untraced run
    for name in names:
        assert getattr(readers, name)(ctx) is None, name


def test_pad_share_reads_the_programs_counter(jamba):
    from torchdistx_tpu.serve.metrics import ServeMetrics

    readers = _readers()
    m = ServeMetrics(num_slots=2)  # the latest: what the reader finds
    ctx = _ctx(jamba, [], **{"serve.prompt_lens": [146, 300, 899]})
    assert readers.serve_scan_pad_share_pct(ctx) is None  # nothing prefilled
    m.counters["tokens_prefilled"] = 256 + 512 + 1024  # the buckets' rows
    assert readers.serve_scan_pad_share_pct(ctx) == pytest.approx(
        100.0 * (1 - (146 + 300 + 899) / 1792))
    ctx.counters = {}
    assert readers.serve_scan_pad_share_pct(ctx) is None

"""The DeepSeek-V3 family in the harness: its counts at the published
widths worked by hand, what it brings to the serving driver, its
rehearsal cell on the CPU, its control, and its readers on a program
that has nothing for them to read."""

import json
import os
import types

import pytest
import run
from harness import loader, reference

KANANA = os.path.join(loader.ROOT, "configs", "kanana-2-30b-a3b-1chip.json")
NEEDS = ("reference.ServeReference", "counts.serve_flops",
         "counts.latent_decode_need", "counts.grouped_matmul_need",
         "counts.mla_prefill_need")


@pytest.fixture(scope="module")
def family():
    return loader.load_family("deepseek_v3", needs=NEEDS)


@pytest.fixture(scope="module")
def kanana():
    with open(KANANA) as f:
        return json.load(f)


def test_model_counts_at_the_published_widths(family, kanana):
    c = family.counts
    # W_q 2048 x 6144, W_kv_a 2048 x 576, W_kv_b 512 x 8192, W_o 4096 x 2048
    assert c.attention_params(kanana) == 12582912 + 1179648 + 4194304 + 8388608
    assert c.attention_params(kanana) == 26345472  # 26.35 M
    assert c.dense_ffn_params(kanana) == 3 * 2048 * 6144 == 37748736
    assert c.expert_params(kanana) == 3 * 2048 * 768 == 4718592
    # router 2048 x 128 + a shared expert of two widths + 6 routed: 38.0 M
    assert c.expert_layer_params_used(kanana) == 262144 + 8 * 4718592 == 38010880
    assert c.expert_layer_params_held(kanana) == 262144 + 130 * 4718592
    # 1 dense + 7 expert layers, embedding and head: 5.07 B
    assert c.total_params(kanana) == (
        8 * 26345472 + 37748736 + 7 * 613679104 + 2 * 128256 * 2048)
    assert round(c.total_params(kanana) / 1e9, 2) == 5.07
    used = 8 * 26345472 + 37748736 + 7 * 38010880
    assert c.matmul_params_used(kanana) == used
    # a prompt of 3 tokens and a decoded token over 4 rows: the head works
    # for the 2 tokens that are sampled; attention 32 x (192 + 128) a row
    assert c.serve_flops(kanana, [3], [4]) == (
        2.0 * used * 4 + 2.0 * 128256 * 2048 * 2
        + 2.0 * 32 * 320 * 8 * (6 + 4))


def test_kernel_needs_at_the_published_widths(family, kanana):
    c = family.counts
    # a latent row: 512 + 64 bf16 lanes, read once
    assert c.latent_row_bytes(kanana) == 1152
    flops, nbytes = c.latent_decode_need(kanana, 1000)
    assert nbytes == 1152000 and flops == 2.0 * 32 * (2 * 512 + 64) * 1000
    # 192 rows over 100 experts: 100 x 9.44 MB of weights, 8 KB a row
    flops, nbytes = c.grouped_matmul_need(kanana, 192, 100)
    assert flops == 2.0 * 4718592 * 192
    assert nbytes == 100 * 9437184 + 192 * 8192
    flops, nbytes = c.mla_prefill_need(kanana, 2048)
    assert flops == 2.0 * 32 * 320 * (2048 * 2049 // 2)
    assert nbytes == 2048 * 32 * 640 * 2


def test_the_family_brings_what_the_serving_driver_needs(family, kanana):
    assert set(family.reference.PRECISIONS) == {"f32", "bf16", "int8"}
    assert not hasattr(family.reference, "TrainReference")  # no training cell
    arch = family.reference.Arch.from_config(kanana)
    plan = family.reference.leaf_plan(arch)
    counters = [c for _, _, c in plan if c is not None]
    assert counters == list(range(len(counters)))
    # embedding, final norm, head; 10 leaves in the dense block, 15 in an
    # expert block
    assert len(plan) == 3 + 10 + 7 * 15
    shapes = {name: shape for name, shape, _ in plan}
    assert shapes["blocks.0.mlp.w_gate.weight"] == (6144, 2048)
    assert shapes["blocks.1.mlp.w_gate"] == (128, 2048, 768)
    assert shapes["blocks.1.mlp.e_score_correction_bias"] == (128,)
    assert shapes["blocks.7.attn.wkv_a.weight"] == (576, 2048)


def test_the_configuration_carries_the_catalog_rows_values(kanana):
    """Every key of the catalog row's ``config`` under its published
    name; only the keys in ``reduced`` differ, and ``published`` keeps
    what they were."""
    catalog = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    differ = {k for k, v in catalog.items() if kanana.get(k, "absent") != v}
    assert differ == set(kanana["reduced"]) == {
        "num_hidden_layers", "max_position_embeddings"}
    assert kanana["published"] == {k: catalog[k] for k in differ}
    assert (kanana["num_hidden_layers"], kanana["max_position_embeddings"]) == (8, 8192)


def test_sound_serve_run_of_the_family_is_correct(drive):
    result = drive("tiny-dsv3.batch4")
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["weights_differ"]["value"] == 0
    assert result["counts"]["serve.requests_finished"] > 0


def test_altered_token_is_not_correct(drive, monkeypatch):
    from torchdistx_tpu.serve.engine import ServeEngine

    real = ServeEngine._record_first
    monkeypatch.setattr(
        ServeEngine, "_record_first",
        lambda self, req, tok, now: real(self, req, (int(tok) + 1) % 256, now))
    result = drive("tiny-dsv3.batch4")
    assert result["correct"] is False
    c = result["compared"]["logit_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("seed", [3, 2**31 + 4])
def test_serve_control_is_not_correct(seed):
    """The float32 toy's served tokens stay within the cell's limits; the
    tokens its reference in the next precision down (bfloat16) puts
    first do not."""
    cell = loader.load_cell("tiny-dsv3.batch4", rehearsal=True)
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


def test_readers_find_nothing_in_a_program_without_their_kernels(kanana):
    """On the parent of the PR that brought them (no such kernel in the
    trace, no expert counters) every reader returns None and none
    raises."""
    readers = loader.load_module(
        os.path.join(loader.ROOT, "metrics", "deepseek_v3_readers.py"),
        "metric reader module")
    ctx = types.SimpleNamespace(
        cell=types.SimpleNamespace(config=kanana), device_kind="TPU v5 lite",
        reduction={"ops": []}, family=lambda *needs: loader.load_family(
            "deepseek_v3", needs=needs),
        counters={"serve.decode_dispatches": 10, "serve.decode_rows_sum": 500,
                  "serve.prefill_calls": 2, "serve.prompt_lens": [100, 200]})
    for name in ("serve_latent_decode_roofline", "serve_grouped_matmul_roofline",
                 "serve_mla_prefill_roofline"):
        assert getattr(readers, name)(ctx) is None, name
    ctx.reduction = None  # an untraced run
    assert readers.serve_latent_decode_roofline(ctx) is None


def test_rows_per_group_reads_the_programs_counters():
    from torchdistx_tpu.serve.metrics import ServeMetrics

    readers = loader.load_module(
        os.path.join(loader.ROOT, "metrics", "deepseek_v3_readers.py"),
        "metric reader module")
    m = ServeMetrics(num_slots=2)  # the latest: what the reader finds
    assert readers.serve_moe_rows_per_group(None) is None  # nothing counted
    m.counters.update(moe_routed_rows_decode=1344, moe_groups_decode=700)
    assert readers.serve_moe_rows_per_group(None) == 1344 / 700

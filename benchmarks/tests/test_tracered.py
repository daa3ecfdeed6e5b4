"""The trace reduction against a small trace recorded on the chip in PR 29
(`tests/data/train_cut.json.gz`: the first 600 ms of device operations of
a traced `dscoder-1.3b.train` run on a TPU v5e, seed 2900000077, cut by
`proof/trace_look.py --cut`, operation names compacted; the kernels carry
the `tdx_` names the program gives them since PR 27, which PR 26's cut
did not have), and against intervals worked by hand."""

import gzip
import json
import os
import types

import pytest

from harness import loader, tracered

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "train_cut.json.gz"), "rt") as f:
        return json.load(f)


def test_union_and_self_time_by_hand():
    assert tracered.union_intervals([(5, 9), (0, 3), (2, 4), (9, 10)]) == [
        [0, 4], [5, 10]]
    ops = [["while.1", 0, 100, "while"], ["fusion.1", 10, 30, "fusion f32[2]"],
           ["fusion.2", 50, 40, "fusion f32[2]"], ["k.7", 200, 50, "pallas custom-call"]]
    own = tracered.self_times(ops)
    assert own == {"fusion f32[2]": 70, "while": 30, "pallas:k": 50}
    red = tracered.reduce_events(
        {"devices": {"/device:TPU:0": ops}, "host": [["serve/decode", 90, 120]]},
        window_s=300e-9, chips=1)
    assert red["busy_s"] == pytest.approx(150e-9)
    assert red["idle_pct"] == pytest.approx(50.0)
    assert red["breakdown"]["idle_gaps"] == [["serve/decode", pytest.approx(100e-9)]]
    assert red["breakdown"]["device_ops"][0] == ["fusion f32[2]", pytest.approx(70e-9)]


def test_compact_names():
    text = ('%tdx_flash_forward.38 = (bf16[64,2048,128]{2,1,0:T(8,128)(2,1)S(1)}, '
            'f32[64,2048,128]{2,1,0:T(8,128)}) custom-call(bf16[64,2048,128]{2,1,0} %b), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    name, tag = tracered.compact(text)
    assert name == "tdx_flash_forward.38"
    assert tag == "pallas custom-call (bf16[64,2048,128], f32[64,2048,128])"
    assert tracered.compact("%fusion.5 = f32[4,8]{1,0:T(8,128)} fusion(f32[4]{0} %x), kind=kLoop") == (
        "fusion.5", "fusion f32[4,8]")
    assert tracered.base_name(name) == "tdx_flash_forward"
    assert tracered.base_name("checkpoint.62") == "checkpoint"


def test_recorded_trace_reduces(recorded):
    ops = recorded["devices"]["/device:TPU:0"]
    assert len(ops) > 1000
    red = tracered.reduce_events(recorded, window_s=0.0, chips=1)
    # a training step keeps the chip busy: the cut is one dense stretch
    assert 0.55 < red["window_s"] < 0.65
    assert 0.0 <= red["idle_pct"] < 1.0
    assert red["busy_s"] == pytest.approx(red["window_s"], rel=0.01)
    labels = [n for n, _ in red["breakdown"]["device_ops"]]
    assert len(labels) == 10 and any(n.startswith("pallas:") for n in labels)
    assert sum(t for _, t in red["breakdown"]["device_ops"]) <= red["busy_s"]


def test_flash_roofline_from_the_recorded_trace(recorded):
    red = tracered.reduce_events(recorded, window_s=0.0, chips=1)
    cell = loader.load_cell("dscoder-1.3b.train")
    ctx = types.SimpleNamespace(
        reduction=red, cell=cell, device_kind="TPU v5 lite", chips=1,
        counters={"train.batch": 4, "train.seq": 2048}, spans={})
    reader = next(m.reader for m in cell.per_layer if m.name == "train.flash_roofline")
    share = reader(ctx)
    # 1.83 ms a forward call against 0.349 ms at the peak, 3.8 ms a backward
    # pair against 0.87 ms: 22.6 over the whole traced window (my chip runs,
    # PR 26 and PR 29); the cut holds a step and a half
    assert 20.0 < share < 25.0
    idle = next(m.reader for m in cell.per_layer if m.name == "train.device_idle_pct")
    assert idle(ctx) == pytest.approx(red["idle_pct"])
    # nothing to read: no number, never 0
    ctx.reduction = None
    assert reader(ctx) is None and idle(ctx) is None


#: one call of each kernel a metric reads, 1 ms each, as the trace names them
OWN = [["tdx_flash_forward.3", 0, 1_000_000, "pallas custom-call bf16[1]"],
       ["tdx_flash_backward_dkv.4", 2_000_000, 1_000_000, "pallas custom-call bf16[1]"],
       ["tdx_flash_backward_dq.5", 4_000_000, 1_000_000, "pallas custom-call bf16[1]"],
       ["tdx_decode_attention.6", 6_000_000, 1_000_000, "pallas custom-call bf16[1]"],
       ["tdx_paged_decode_attention", 8_000_000, 1_000_000, "pallas custom-call bf16[1]"]]
#: Mosaic kernels of other names (fused cross-entropy's today, a latent
#: decode or a grouped expert matmul tomorrow), a kernel whose name only
#: starts like one that is read, and an XLA fusion that is no kernel at all
FOREIGN = [["tdx_fused_ce_forward.7", 10_000_000, 5_000_000, "pallas custom-call f32[1]"],
           ["tdx_fused_ce_backward_dw.8", 16_000_000, 5_000_000, "pallas custom-call f32[1]"],
           ["tdx_latent_decode_attention.9", 22_000_000, 5_000_000, "pallas custom-call bf16[1]"],
           ["tdx_grouped_expert_matmul.10", 28_000_000, 5_000_000, "pallas custom-call bf16[1]"],
           ["tdx_flash_forward_v2.11", 34_000_000, 5_000_000, "pallas custom-call bf16[1]"],
           ["checkpoint.12", 40_000_000, 5_000_000, "pallas custom-call bf16[1]"],
           ["tdx_flash_forward.13", 46_000_000, 5_000_000, "fusion bf16[1]"]]


def _readings(ops):
    """Every kernel metric of both cells over a trace of ``ops``."""
    red = tracered.reduce_events(
        {"devices": {"/device:TPU:0": ops}, "host": []}, window_s=0.0, chips=1)
    out = {}
    for name, counters in (
            ("dscoder-1.3b.train", {"train.batch": 4, "train.seq": 2048}),
            ("mistral-7b.batch16", {"serve.decode_dispatches": 10,
                                    "serve.decode_rows_sum": 96000,
                                    "serve.prompt_lens": [64, 1024]})):
        cell = loader.load_cell(name)
        ctx = types.SimpleNamespace(reduction=red, cell=cell, chips=1,
                                    device_kind="TPU v5 lite",
                                    counters=counters, spans={})
        for m in cell.per_layer:
            if m.source == "device_trace" and "roofline" in m.name:
                out[m.name] = m.reader(ctx)
    return out


def test_kernels_are_matched_by_the_names_they_carry():
    trace_readers = loader.load_module(
        os.path.join(loader.ROOT, "metrics", "trace_readers.py"), "reader module")
    seconds = {k: tracered.kernel_seconds(OWN + FOREIGN, trace_readers.is_kernel(k))
               for k in trace_readers.KERNELS}
    assert seconds == {"flash_fwd": (pytest.approx(1e-3), 1),
                       "flash_bwd": (pytest.approx(2e-3), 2),
                       "decode_attn": (pytest.approx(2e-3), 2)}
    alone = _readings(OWN)
    assert set(alone) == {"train.flash_roofline", "serve.decode_attn_roofline",
                          "serve.flash_prefill_roofline"}
    assert all(v is not None and v > 0 for v in alone.values())
    # a foreign kernel enters no existing metric
    assert _readings(OWN + FOREIGN) == alone
    # and alone it is nothing to read: no number, never 0
    assert set(_readings(FOREIGN).values()) == {None}

"""The trace reduction against a small trace recorded on the chip in PR 26
(`tests/data/train_cut.json.gz`: the first 600 ms of device operations of
a traced `dscoder-1.3b.train` run on a TPU v5e, cut by
`proof/trace_look.py --cut`, operation names compacted), and against
intervals worked by hand."""

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
    text = ('%jvp_jit__flash_forward__.38 = (bf16[64,2048,128]{2,1,0:T(8,128)(2,1)S(1)}, '
            'f32[64,2048,128]{2,1,0:T(8,128)}) custom-call(bf16[64,2048,128]{2,1,0} %b), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    name, tag = tracered.compact(text)
    assert name == "jvp_jit__flash_forward__.38"
    assert tag == "pallas custom-call (bf16[64,2048,128], f32[64,2048,128])"
    assert tracered.compact("%fusion.5 = f32[4,8]{1,0:T(8,128)} fusion(f32[4]{0} %x), kind=kLoop") == (
        "fusion.5", "fusion f32[4,8]")
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
    # pair against 0.87 ms: about a fifth (my chip run, PR 26)
    assert 15.0 < share < 30.0
    idle = next(m.reader for m in cell.per_layer if m.name == "train.device_idle_pct")
    assert idle(ctx) == pytest.approx(red["idle_pct"])
    # nothing to read: no number, never 0
    ctx.reduction = None
    assert reader(ctx) is None and idle(ctx) is None

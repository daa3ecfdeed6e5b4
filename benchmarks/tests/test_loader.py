"""The loader refuses what it does not know."""

import json
import os

import pytest

from harness import loader, peaks


def test_cells_of_the_benchmark_load():
    bench = loader.benchmark_json()
    for w in bench["workloads"]:
        cell = loader.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, "a cell reports at least one per-layer metric"
        for m in cell.per_layer:
            assert m.moves in {e.name for e in cell.end_to_end}
            assert callable(m.reader)
        loader.load_driver(cell.driver_kind)


def test_unknown_cell_is_refused():
    with pytest.raises(loader.BenchmarkError, match="unknown cell"):
        loader.load_cell("no-such.cell")


def test_rehearsal_takes_only_rehearsal_files():
    with pytest.raises(loader.BenchmarkError):
        loader.load_cell("dscoder-1.3b.train", rehearsal=True)
    assert loader.load_cell("tiny.train", rehearsal=True).rehearsal


def test_unknown_metric_is_refused():
    with pytest.raises(loader.BenchmarkError, match="unknown per-layer metric"):
        loader.load_metric({"name": "no.such_metric", "unit": "s"})


def test_metric_file_must_agree_with_benchmark_json():
    entry = dict(next(m for m in loader.benchmark_json()["per_layer"]
                      if m["name"] == "materialize_s"))
    entry["moves"] = "train_tokens_per_s"
    with pytest.raises(loader.BenchmarkError, match="moves"):
        loader.load_metric(entry)


def test_unknown_device_kind_is_refused():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(LookupError, match="no peaks on record"):
        peaks.peaks("cpu")


def test_every_metric_file_is_listed_and_named_as_allowed():
    import re

    listed = {m["name"] for m in loader.benchmark_json()["per_layer"]}
    files = {f[:-5] for f in os.listdir(os.path.join(loader.ROOT, "metrics"))
             if f.endswith(".json")}
    assert files == listed
    for f in files:
        meta = json.load(open(os.path.join(loader.ROOT, "metrics", f + ".json")))
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", meta["name"])
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", meta["unit"])

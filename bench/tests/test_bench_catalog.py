"""The catalog: every part found by its name, nothing needing an edit."""

import json
import pathlib
import re

import pytest

import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_named_file_exists(spec):
    cat = harness.Catalog(ROOT)
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert cat.config(c["name"])["name"] == c["name"]
    for w in spec["workloads"]:
        cell = cat.cell(w["name"])
        assert cat.kind(cell["kind"]).run
    for m in spec["per_layer"]:
        assert callable(cat.reader(m["name"]).read)


def test_names_and_keys_keep_the_format(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in spec[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}


def test_each_cell_reports_setup_another_metric_and_a_layer(spec):
    cat = harness.Catalog(ROOT)
    for w in spec["workloads"]:
        e2e = {m["name"] for m in cat.end_to_end(w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = cat.per_layer(w["name"])
        assert layers and all(m["moves"] in e2e for m in layers)


def test_new_parts_are_found_without_editing(tmp_path, tiny_root):
    """A configuration, a traffic mix, a cell and a metric reader added as
    new files, with new BENCHMARK.json entries, are picked up."""
    import shutil

    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/criteo-kaggle-5k.json").read_text())
    cfg.update(name="criteo-kaggle-50k", vocab_range=50000)
    (root / "bench/configs/criteo-kaggle-50k.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/offline-utf8-short.json").write_text(
        json.dumps({"kind": "offline_job", "params": {"chunks": 2}})
    )
    (root / "bench/cells/criteo-kaggle-50k.offline-utf8-short.json").write_text(
        json.dumps({"config": "criteo-kaggle-50k", "traffic": "offline-utf8-short", "params": {}})
    )
    (root / "bench/metrics/rows_per_chunk.py").write_text("def read(ctx):\n    return 42.0\n")
    spec["configs"].append(dict(spec["configs"][0], name="criteo-kaggle-50k", file="bench/configs/criteo-kaggle-50k.json"))
    spec["workloads"].append(
        {"name": "criteo-kaggle-50k.offline-utf8-short", "config": "criteo-kaggle-50k",
         "traffic": "offline-utf8-short", "chips": 1, "why": "a test cell"}
    )
    for m in spec["end_to_end"]:
        if m["name"] == "offline_rows_per_s":
            m["workloads"].append("criteo-kaggle-50k.offline-utf8-short")
    spec["per_layer"].append(
        {"name": "rows_per_chunk", "unit": "rows", "better": "higher", "source": "program_counter",
         "layer": "host feed", "moves": "offline_rows_per_s"}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cat = harness.Catalog(root)
    cell = cat.cell("criteo-kaggle-50k.offline-utf8-short")
    assert cell["config"]["vocab_range"] == 50000 and cell["params"] == {"chunks": 2}
    assert cell["kind"] == "offline_job"
    # without a "workloads" key the metric goes to every cell reporting what it moves
    names = [m["name"] for m in cat.per_layer("criteo-kaggle-50k.offline-utf8-short")]
    assert names == ["rows_per_chunk"]
    assert [m["name"] for m in cat.end_to_end("criteo-kaggle-50k.offline-utf8-short")] == [
        "offline_rows_per_s", "setup_s"]
    assert "rows_per_chunk" in [m["name"] for m in cat.per_layer("criteo-kaggle-5k.offline-utf8")]
    assert "rows_per_chunk" not in [m["name"] for m in cat.per_layer("criteo-kaggle-5k.serve-poisson")]
    assert cat.reader("rows_per_chunk").read({}) == 42.0


def test_cell_file_must_agree_with_the_benchmark(tmp_path, tiny_root):
    import shutil

    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    p = root / "bench/cells/criteo-kaggle-5k.offline-utf8.json"
    p.write_text(json.dumps({"config": "criteo-kaggle-1m", "traffic": "offline-utf8", "params": {}}))
    with pytest.raises(harness.BenchError):
        harness.Catalog(root).cell("criteo-kaggle-5k.offline-utf8")


def test_peaks_refuse_an_unknown_device():
    import peaks

    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("cpu")

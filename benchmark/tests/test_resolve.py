"""Cells, configurations, traffic mixes and metrics are found by name, and
a new one needs only new files and ``BENCHMARK.json`` entries."""

import json
import os
import shutil

import pytest

from benchmark import harness

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = harness.resolve(cell)
    w = {x["name"]: x for x in BENCH["workloads"]}[cell]
    assert c.chips == w["chips"]
    assert c.config["name"] == w["config"]
    assert c.traffic["name"] == w["traffic"]
    assert c.traffic["rate_metric"] in [m["name"] for m in c.end_to_end]
    assert "setup_s" in [m["name"] for m in c.end_to_end]
    assert c.per_layer, "every cell reports a per-layer metric"
    gen, ref = c.generator(), c.reference()
    assert callable(gen.generate) and callable(gen.write)
    assert callable(ref.expected) and callable(ref.compare) and callable(ref.work) and ref.CONTROLS
    assert "{input}" in c.traffic["argv"] and "{output}" in c.traffic["argv"]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    c = harness.resolve(BENCH["workloads"][0]["name"])
    assert callable(c.reader(metric))


def test_configs_are_their_files():
    for c in BENCH["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert set(c["reduced"]) <= set(cfg)


def test_a_cell_added_as_files_alone(tmp_path):
    """A new configuration, traffic mix and metric: new files plus entries,
    no edit to an existing file."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = json.loads(json.dumps(BENCH))
    cfg = json.load(open(os.path.join(ROOT, "benchmark/configs/wgs30x.json")))
    cfg["name"] = "wgs30x_short"
    cfg["read_length"] = 100
    (root / "benchmark/configs/wgs30x_short.json").write_text(json.dumps(cfg))
    traffic = json.load(open(os.path.join(ROOT, "benchmark/traffic/bgzf6.json")))
    traffic.update(name="level1", input_level=1, rate_metric="sort_records_per_s")
    (root / "benchmark/traffic/level1.json").write_text(json.dumps(traffic))
    shutil.copy(os.path.join(ROOT, "benchmark/references/coordinate_sort.py"),
                root / "benchmark/references/coordinate_sort_copy.py")
    traffic["reference"] = "coordinate_sort_copy"
    (root / "benchmark/traffic/level1.json").write_text(json.dumps(traffic))
    (root / "benchmark/metrics/records_per_job.sort.py").write_text(
        "def read(ctx):\n    return float(ctx.records)\n")
    bench["configs"].append({"name": "wgs30x_short", "source": "x", "file": "benchmark/configs/wgs30x_short.json",
                             "reduced": ["records", "read_length"], "why": "x"})
    bench["workloads"].append({"name": "wgs_short.level1", "config": "wgs30x_short", "traffic": "level1",
                               "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("wgs_short.level1")
    bench["per_layer"].append({"name": "records_per_job.sort", "unit": "records", "better": "higher",
                               "source": "program_counter", "layer": "sort pipeline",
                               "moves": "sort_records_per_s", "workloads": ["wgs_short.level1"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    c = harness.resolve("wgs_short.level1", str(root))
    assert c.config["read_length"] == 100 and c.traffic["input_level"] == 1
    assert [m["name"] for m in c.per_layer] == ["records_per_job.sort"]
    ctx = harness.Context([], 7, None, {}, {}, {})
    assert c.reader("records_per_job.sort")(ctx) == 7.0
    corpus = c.generator().generate(c.config, 10, 1)
    assert corpus.n_records == 10
    ref = c.reference()
    assert ref.__file__.endswith("coordinate_sort_copy.py")
    want = ref.expected(corpus, c.traffic)
    assert ref.compare(want.to_bam(6), want)["records_wrong"] == 0


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")

"""A configuration, a traffic mix, a per-layer metric and a cell added as
new files and entries are found by name, with no edit to the harness."""

import json
import shutil
from types import SimpleNamespace

import gen
import harness
from conftest import BENCH, ROOT


def test_new_files_are_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "bench"
    # a new configuration: an existing one under a new name, dense
    cfg = json.loads((b / "configs" / "olmo-1b.c8.int8-fused.json").read_text())
    cfg.update(name="olmo-1b.dense", quantize=None)
    cfg["model"].update(mpd_c=1, mpd_fuse=False)
    (b / "configs" / "olmo-1b.dense.json").write_text(json.dumps(cfg))
    # a new mix: chat without shared prefixes
    mix = json.loads((b / "traffic" / "chat.json").read_text())
    mix.update(name="chat-unshared", shared_prefix=None)
    (b / "traffic" / "chat-unshared.json").write_text(json.dumps(mix))
    # a new per-layer metric reader
    (b / "metrics" / "requests_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.run.recs))\n")
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "olmo-1b.dense", "source": "x",
                          "file": "bench/configs/olmo-1b.dense.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "olmo1b-dense.chat-unshared",
                            "config": "olmo-1b.dense",
                            "traffic": "chat-unshared", "chips": 1,
                            "why": "x"})
    bm["per_layer"].append({"name": "requests_in_window", "unit": "1",
                            "better": "higher", "source": "host_clock",
                            "layer": "load generator", "moves": "itl_p95_ms",
                            "workloads": ["olmo1b-dense.chat-unshared"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = harness.load_cell("olmo1b-dense.chat-unshared", root=tmp_path)
    assert cell.config["model"]["mpd_c"] == 1
    assert cell.mix["shared_prefix"] is None
    names = [m["name"] for m in cell.per_layer]
    assert "requests_in_window" in names and "prefix_hit_share" not in names
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms", "setup_s"]
    read = harness.metric_reader("requests_in_window", root=tmp_path)
    assert read(SimpleNamespace(run=SimpleNamespace(recs=[1, 2]))) == 2.0
    reqs = gen.generate(cell.mix, 3, 10, cell.config["model"]["vocab"])
    assert all(r.system == -1 for r in reqs)
    # the cells already there still load as before
    assert harness.load_cell("olmo1b-c8i8.chat", root=tmp_path).mix[
        "shared_prefix"]["n_prompts"] == 8


def test_every_listed_metric_has_a_reader():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    for w in bm["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]


def test_configs_list_what_they_change():
    """Each configuration file names the keys it changes from its source,
    as BENCHMARK.json does, with a reason for each."""
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in bm["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert cfg["name"] == entry["name"]
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
        assert sorted(cfg["reduced_why"]) == sorted(cfg["reduced"])
        assert "departures" not in cfg

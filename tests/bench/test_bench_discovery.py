"""A configuration, a traffic mix and a per-layer metric added as files,
with manifest entries, are found by name: no file of the harness changes."""
import json
import os

import harness
import traffic


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_new_files_are_found_by_name(tmp_path):
    bench = str(tmp_path)
    _write(os.path.join(bench, "configs", "new-model.json"),
           json.dumps({"name": "new-model", "family": "gpt2", "n_layer": 1}))
    mix = {"job": "serve_job", "kind": "offline_backlog", "slots": 2,
           "max_len": 64, "requests_per_stream": 5, "sampling": "greedy",
           "prompt": {"dist": "lognormal", "median": 9, "sigma": 0.3,
                      "min": 4, "max": 20},
           "output": {"dist": "lognormal", "median": 6, "sigma": 0.3,
                      "min": 2, "max": 30}}
    _write(os.path.join(bench, "traffic", "new-mix.json"), json.dumps(mix))
    _write(os.path.join(bench, "metrics", "queue_depth.serve.py"),
           "def read(run):\n    return run.depth * 2\n")
    _write(os.path.join(bench, "metrics", "silent_share.py"),
           "def read(run):\n    return None\n")

    cfg = harness.load_json(bench, "configs", "new-model")
    assert cfg["name"] == "new-model"
    loaded = harness.load_json(bench, "traffic", "new-mix")
    reqs = traffic.stream(loaded, 100, 7, 0)
    assert len(reqs) == 5 and all(r.prompt_len + r.steps <= 64
                                  for r in reqs)

    manifest = {
        "workloads": [{"name": "new-model.new-mix", "config": "new-model",
                       "traffic": "new-mix", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "queue_depth.serve", "unit": "1",
                       "moves": "tokens_per_s"},
                      {"name": "silent_share", "unit": "%",
                       "moves": "tokens_per_s"},
                      {"name": "elsewhere", "unit": "%",
                       "moves": "tokens_per_s", "workloads": ["other"]}]}
    cell = harness.cell_of(manifest, "new-model.new-mix")
    assert cell["traffic"] == "new-mix"
    metrics = harness.per_layer_of(manifest, cell["name"])
    assert [m["name"] for m in metrics] == ["queue_depth.serve",
                                            "silent_share"]

    class Run:
        depth = 21

    got = harness.read_metrics(bench, metrics, Run())
    assert got == {"queue_depth.serve": {"value": 42.0, "unit": "1"}}


def test_missing_files_are_refused(tmp_path):
    import pytest
    with pytest.raises(harness.Refused):
        harness.load_json(str(tmp_path), "configs", "absent")
    with pytest.raises(harness.Refused):
        harness.load_module(str(tmp_path), "metrics", "absent")
    with pytest.raises(harness.Refused):
        harness.cell_of({"workloads": []}, "absent")


def test_the_manifest_names_existing_files():
    from conftest import BENCH, ROOT
    manifest = harness.load_manifest(ROOT)
    for c in manifest["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for cell in manifest["workloads"]:
        cfg = harness.load_json(BENCH, "configs", cell["config"])
        harness.load_json(BENCH, "traffic", cell["traffic"])
        harness.load_module(BENCH, "families", cfg["family"])
        harness.load_module(BENCH, "references", cfg["reference"])
        for m in harness.per_layer_of(manifest, cell["name"]):
            harness.load_module(BENCH, "metrics", m["name"])

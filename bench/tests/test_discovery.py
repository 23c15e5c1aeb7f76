"""A configuration, a mix and a metric added as files (and entries of
``BENCHMARK.json``) are picked up with no edit to a file already there."""

import json
import os

from conftest import make_tiny, run_tiny

NEW_METRIC = '''"""Explorations completed in the window."""


def read(ctx):
    return float(len(ctx["explorations"])) or None
'''


def _add_cell(root):
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "hft8x10g.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "hft8x10g-low"
    cfg["trace"]["params"]["load"] = 0.2
    cfg["assumed"] = cfg["assumed"] + ["load 0.2"]
    with open(os.path.join(bench, "configs", "hft8x10g-low.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "mixes", "explore-300.json"), "w") as f:
        json.dump({"events": 300, "warm": 1, "max_rate_per_s": 400,
                   "check": 1, "trace_seconds": 1}, f)
    with open(os.path.join(bench, "metrics", "explorations.explore.py"),
              "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(bench, "limits", "hft8-low-300.json"), "w") as f:
        with open(os.path.join(bench, "limits", "hft8-nsga2.json")) as g:
            f.write(g.read())
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "hft8x10g-low", "source": "test",
                            "file": "bench/configs/hft8x10g-low.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "hft8-low-300", "config": "hft8x10g-low",
                              "traffic": "explore-300", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("hft8-low-300")
    spec["per_layer"].append({"name": "explorations.explore", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "explore_s",
                              "workloads": ["hft8-low-300"]})
    with open(path, "w") as f:
        json.dump(spec, f)


def _tree(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_added_files_are_found(tmp_path, capsys):
    root = make_tiny(str(tmp_path))
    before = _tree(os.path.join(root, "bench"))
    _add_cell(root)
    after = _tree(os.path.join(root, "bench"))
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    assert len(after) == len(before) + 4

    rc, result, err = run_tiny(root, ["--workload", "hft8-low-300", "--seed",
                                      "3", "--seconds", "1", "--trace", "1"],
                               capsys)
    assert rc == 0, err
    assert result["correct"] is True
    assert result["metrics"]["explorations.explore"]["value"] >= 1
    assert "stage4_s.explore" not in result["metrics"]   # not listed for it

    rc, result, err = run_tiny(root, ["--workload", "hft8-low-300", "--seed",
                                      "4", "--seconds", "1", "--trace", "0"],
                               capsys)
    assert rc == 0, err
    assert set(result["metrics"]) == {"explore_s", "setup_s"}

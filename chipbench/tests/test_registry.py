"""Cells, configurations, mixes, models, metrics and limits are files found by
name; BENCHMARK.json keeps to the shape the harness reads."""
import json
import re

import pytest

from chipbench import compare, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_a_new_cell_needs_only_new_files(tmp_path):
    # a throwaway model, configuration, mix, metric and limits, in a tree of
    # their own: nothing of the committed harness is edited
    (tmp_path / "models").mkdir()
    (tmp_path / "models" / "toy.py").write_text(
        "def make_init(config):\n    return lambda key: {'w': 0.0}\n"
        "def loss(p, b):\n    return 0.0\n"
        "def evaluate(p, b):\n    return 0.0, 0.0\n")
    for kind, name, body in (
            ("configs", "toy-c", {"model": "toy", "n_workers": 4}),
            ("traffic", "toy-t", {"algorithm": "dsgd_aau", "mode": "auto"}),
            ("limits", "toy-t.toy-c", {"loss_gap": 0.5})):
        (tmp_path / kind).mkdir()
        (tmp_path / kind / f"{name}.json").write_text(json.dumps(body))
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "toy_metric.py").write_text(
        "def reduce(rec):\n    return rec.events * 2.0\n")
    bench = {
        "workloads": [{"name": "toy-t.toy-c", "config": "toy-c",
                       "traffic": "toy-t", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "events_per_s", "unit": "events/s"}],
        "per_layer": [{"name": "toy_metric", "unit": "x",
                       "workloads": ["toy-t.toy-c"]},
                      {"name": "elsewhere", "unit": "x",
                       "workloads": ["other"]}]}
    cell = harness.load_cell(bench, "toy-t.toy-c", base=tmp_path)
    assert cell.config["n_workers"] == 4
    assert cell.traffic["algorithm"] == "dsgd_aau"
    assert cell.model.evaluate(None, None) == (0.0, 0.0)
    assert cell.limits == {"loss_gap": 0.5}
    assert list(cell.readers) == ["toy_metric"]

    class Rec:
        events = 21
    assert cell.readers["toy_metric"].reduce(Rec) == 42.0


@pytest.mark.parametrize("kind, name", [("configs", "nope"),
                                        ("traffic", "nope"),
                                        ("models", "nope"),
                                        ("metrics", "nope")])
def test_a_missing_file_is_named(tmp_path, kind, name):
    load = harness.load_module if kind in ("models", "metrics") \
        else harness.load_json
    with pytest.raises(FileNotFoundError, match=name):
        load(kind, name, base=tmp_path)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell(harness.load_benchmark(), "no.such-cell")


def test_benchmark_file_shape():
    bench = harness.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (harness.REPO / c["file"]).is_file()
        assert c["file"].startswith("chipbench/")
    used = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["config"] in configs and w["chips"] in (1, 4)
        used.add(w["config"])
        cell = harness.load_cell(bench, w["name"])
        assert set(cell.limits) == set(compare.NUMBERS)
        assert cell.end_to_end.keys() >= {"events_per_s", "setup_s"}
        assert cell.metrics, "every cell reports a per-layer metric"
    assert used == set(configs)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()


def test_seeds_take_any_whole_number():
    mix = {"arrival_seed": 5}
    big = harness.derive_seeds(2 ** 31 + 17, mix)
    assert big == harness.derive_seeds(2 ** 31 + 17, mix)
    other = harness.derive_seeds(2 ** 31 + 18, mix)
    assert all(0 <= v < 2 ** 31 for v in big.values())
    # weights and data follow the run's seed; arrivals follow the mix
    assert big["init"] != other["init"] and big["data"] != other["data"]
    assert (big["straggler"], big["sched"]) == (other["straggler"],
                                                other["sched"])
    moved = harness.derive_seeds(2 ** 31 + 17, {"arrival_seed": 6})
    assert moved["straggler"] != big["straggler"]

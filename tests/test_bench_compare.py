"""Bench artifact schema discipline + the --compare trend gate.

Covers the typed writer (``common.write_bench_json``: number-or-null
schema, legacy ``"unsupported"`` normalization, rejection of NaN and
non-JSON scalars), the tolerant metric reader (``common.as_metric``) and
the ``benchmarks.run --compare`` soft gate (warn >= 10%, fail >= 30% on
pinned throughput metrics, regression direction aware).
"""
import json

import numpy as np
import pytest

from benchmarks.common import as_metric, write_bench_json
from benchmarks.run import compare


class TestWriteBenchJson:
    def test_normalizes_legacy_unsupported(self, tmp_path):
        p = tmp_path / "b.json"
        write_bench_json(str(p), {
            "results": [{"n": 16, "alg": "x", "gen_horizon_eps":
                         "unsupported", "gen_eps": 10.0}]})
        row = json.loads(p.read_text())["results"][0]
        assert row["gen_horizon_eps"] is None
        assert row["gen_eps"] == 10.0

    def test_accepts_np_float64_rejects_np_float32(self, tmp_path):
        p = tmp_path / "b.json"
        write_bench_json(str(p), {"v": np.float64(1.5)})  # float subclass
        assert json.loads(p.read_text())["v"] == 1.5
        with pytest.raises(TypeError, match="float\\(\\)/int\\(\\)"):
            write_bench_json(str(p), {"v": np.float32(1.5)})
        with pytest.raises(TypeError):
            write_bench_json(str(p), {"v": np.int32(3)})

    def test_rejects_non_finite(self, tmp_path):
        p = tmp_path / "b.json"
        with pytest.raises(ValueError, match="non-finite"):
            write_bench_json(str(p), {"v": float("nan")})
        with pytest.raises(ValueError):
            write_bench_json(str(p), {"rows": [{"v": float("inf")}]})

    def test_nested_containers(self, tmp_path):
        p = tmp_path / "b.json"
        write_bench_json(str(p), {
            "results": [{"buckets": (4, 8), "occ": [{"A": 4, "fill": 0.5}],
                         "note": "unsupported"}]})
        row = json.loads(p.read_text())["results"][0]
        assert row["buckets"] == [4, 8]
        assert row["note"] is None  # normalized wherever it appears


class TestAsMetric:
    @pytest.mark.parametrize("v,expect", [
        (3, 3.0), (2.5, 2.5), ("2.5", 2.5),
        (None, None), ("unsupported", None), ("nan", None), ("inf", None),
        (True, None), ([1, 2], None), ({"a": 1}, None),
    ])
    def test_values(self, v, expect):
        assert as_metric(v) == expect


def _write(tmp_path, name, rows):
    p = tmp_path / name
    p.write_text(json.dumps({"bench": "event_stream", "results": rows}))
    return str(p)


_BASE = {"n": 16, "alg": "ad_psgd", "events": 1024,
         "gen_eps": 1000.0, "sparse_eps": 500.0,
         "telemetry_overhead": 1.05, "gen_horizon_eps": None}


class TestCompareGate:
    def test_identical_passes(self, tmp_path):
        p = _write(tmp_path, "a.json", [_BASE])
        assert compare(p, p) == 0

    def test_small_regression_warns_but_passes(self, tmp_path, capsys):
        old = _write(tmp_path, "old.json", [_BASE])
        new = _write(tmp_path, "new.json",
                     [{**_BASE, "sparse_eps": 500.0 * 0.85}])  # -15%
        assert compare(old, new) == 0
        assert "WARN" in capsys.readouterr().out

    def test_large_pinned_regression_fails(self, tmp_path, capsys):
        old = _write(tmp_path, "old.json", [_BASE])
        new = _write(tmp_path, "new.json",
                     [{**_BASE, "sparse_eps": 500.0 * 0.6}])  # -40%
        assert compare(old, new) == 1
        assert "FAIL" in capsys.readouterr().out

    def test_large_unpinned_regression_only_warns(self, tmp_path, capsys):
        old = _write(tmp_path, "old.json", [_BASE])
        # overhead ratios are not pinned: 1.05 -> 1.60 warns, never fails
        new = _write(tmp_path, "new.json",
                     [{**_BASE, "telemetry_overhead": 1.60}])
        assert compare(old, new) == 0
        assert "WARN" in capsys.readouterr().out

    def test_overhead_direction_is_lower_better(self, tmp_path, capsys):
        old = _write(tmp_path, "old.json", [_BASE])
        new = _write(tmp_path, "new.json",
                     [{**_BASE, "telemetry_overhead": 0.95}])
        assert compare(old, new) == 0
        assert "WARN" not in capsys.readouterr().out  # improvement

    def test_improvement_never_flags(self, tmp_path, capsys):
        old = _write(tmp_path, "old.json", [_BASE])
        new = _write(tmp_path, "new.json",
                     [{**_BASE, "sparse_eps": 5000.0}])
        assert compare(old, new) == 0
        out = capsys.readouterr().out
        assert "WARN" not in out and "FAIL" not in out

    def test_tolerates_legacy_and_missing(self, tmp_path):
        # legacy string sentinel on one side, null on the other, a metric
        # missing entirely, and a row present in only one file
        old = _write(tmp_path, "old.json", [
            {**_BASE, "gen_horizon_eps": "unsupported"},
            {"n": 64, "alg": "prague", "gen_eps": 1.0},
        ])
        new = _write(tmp_path, "new.json", [
            {k: v for k, v in _BASE.items() if k != "telemetry_overhead"},
            {"n": 128, "alg": "prague", "gen_eps": 1.0},
        ])
        assert compare(old, new) == 0

    def test_recorded_artifact_self_compare(self):
        assert compare("BENCH_event_stream.json",
                       "BENCH_event_stream.json") == 0


class TestRooflineArtifacts:
    def test_missing_artifact_fails_naming_the_command(self, tmp_path,
                                                       monkeypatch):
        import benchmarks.bench_roofline as roofline
        monkeypatch.setattr(roofline, "ART", str(tmp_path))
        with pytest.raises(FileNotFoundError,
                           match="python -m repro.launch.dryrun --all"):
            roofline.run()

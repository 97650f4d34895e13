"""The spread a bound is set from, over the runs ``sets.py`` records."""

import json

import pytest

import sets


def test_spread_is_quartile_distance_over_median():
    # statistics.quantiles (exclusive): q1 = 1.75, q3 = 5.25 for 1..6
    assert sets.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert sets.spread([10.0] * 6) == 0.0


def test_summary_reads_recorded_runs(tmp_path, capsys):
    path = tmp_path / "runs.jsonl"
    with open(path, "w") as f:
        for i, v in enumerate([100.0, 101.0, 99.0, 100.5]):
            line = {"correct": True, "metrics": {"tok_s": {"value": v}}}
            f.write(json.dumps({"tag": "set1", "workload": "w", "seed": i,
                                "rc": 0, "wall_s": 1.0, "line": line}) + "\n")
        f.write(json.dumps({"tag": "set1", "workload": "w", "seed": 9,
                            "rc": 2, "wall_s": 1.0, "line": None}) + "\n")
    assert sets.main(["spread", str(path)]) == 0
    out = capsys.readouterr().out
    assert "w set1: 4 runs, correct 4" in out
    assert f"spread {sets.spread([100.0, 101.0, 99.0, 100.5]):.4f}" in out

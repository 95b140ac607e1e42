"""``bench/spread.py`` on six readings with one far-off run."""

import json

import pytest

import spread

READINGS = [0.0070, 0.0071, 0.0069, 0.0072, 0.0070, 0.0091]  # the sixth is far off


def result(value):
    return {"correct": True, "metrics": {"ttfr_p50_host_s": {"value": value, "unit": "s"}}}


def test_spreads_of_six_readings_with_one_outlier():
    med = 0.00705
    assert spread.range_share(READINGS) == pytest.approx((0.0072 - 0.0069) / med)
    # statistics.quantiles' exclusive method: positions 1.75 and 5.25 of 6
    q1 = 0.0069 + 0.75 * (0.0070 - 0.0069)
    q3 = 0.0072 + 0.25 * (0.0091 - 0.0072)
    assert spread.iqr_share(READINGS) == pytest.approx((q3 - q1) / med)
    # twice 4.26% is 8.51%, so 9%; a narrow spread still gets 1%
    assert spread.bound_for(spread.range_share(READINGS)) == pytest.approx(0.09)
    assert spread.bound_for(0.001) == 0.01
    assert spread.bound_for(0.0125) == pytest.approx(0.025)


def test_range_keeps_a_run_whose_leaving_would_not_narrow_it():
    assert spread.range_share([1.0, 1.0, 2.0, 3.0, 3.0]) == pytest.approx(1.0)


def test_main_reads_result_lines_among_logs(tmp_path, capsys):
    paths = []
    for i, v in enumerate(READINGS):
        path = tmp_path / f"run{i}.out"
        path.write_text(f"cell log line\nwindow: rounds=3800\n{json.dumps(result(v))}\n")
        paths.append(str(path))
    assert spread.main(paths) == 0
    (row,) = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert row["metric"] == "ttfr_p50_host_s" and row["n"] == 6
    assert row["median"] == pytest.approx(0.00705)
    assert row["bound"] == pytest.approx(0.09)


def test_main_refuses_fewer_than_two_runs(tmp_path):
    path = tmp_path / "one.out"
    path.write_text(json.dumps(result(0.007)) + "\n")
    assert spread.main([str(path)]) == 2

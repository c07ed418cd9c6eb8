"""The benchmark's statistics, its manifest and its refusal to run
without a chip."""

import glob
import json
import os
import re
import subprocess
import sys

import pytest

from _bench_toy import ROOT, bench_run
from benchmarks.harness import stats

BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("values,q,want", [
    ([10.0], 95, 10.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 50, 3.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 95, 4.8),     # rank 3.8: 4 + 0.8 * (5 - 4)
    (list(range(1, 101)), 95, 95.05),         # rank 94.05
    ([5.0, 1.0, 3.0], 0, 1.0),
])
def test_percentile_by_hand(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 95) is None


def test_gaps_across_all_requests():
    a, b, c = [0.0, 1.0, 3.0], [10.0, 10.5], [7.0]
    assert stats.gaps(a) == [1.0, 2.0]
    assert stats.all_gaps([a, b, c]) == [1.0, 2.0, 0.5]
    assert stats.count_in(a + b + c, 1.0, 10.5) == 4     # half-open


def test_spread_is_the_contracts():
    import statistics
    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


# --- the data is tied to the manifest ----------------------------------

def test_every_cell_finds_its_files():
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    for cell in m["workloads"]:
        assert cell["config"] in configs
        _, _, config, traffic, limits = bench_run.load_cell(cell["name"])
        assert config["name"] == cell["config"]
        assert configs[cell["config"]]["file"] == \
            f"benchmarks/configs/{cell['config']}.json"
        assert config["reduced"] == configs[cell["config"]]["reduced"]
        assert os.path.exists(os.path.join(
            BENCH, "drivers", traffic["driver"] + ".py"))
        assert limits and all("limit" in v for v in limits.values())


def test_every_traffic_file_names_a_driver_that_exists():
    for path in glob.glob(os.path.join(BENCH, "traffic", "*.json")):
        with open(path) as f:
            traffic = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH, "drivers", traffic["driver"] + ".py")), path


def test_metrics_move_what_their_cells_report():
    m = manifest()
    cells = [c["name"] for c in m["workloads"]]
    end = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in end and "workloads" not in end["setup_s"]

    def reports(metric, cell):
        return cell in metric.get("workloads", cells)

    for cell in cells:
        assert sum(reports(e, cell) for e in m["end_to_end"]) >= 2, cell
        assert any(reports(p, cell) for p in m["per_layer"]), cell
    for p in m["per_layer"]:
        assert p["moves"] in end, p["name"]
        for cell in p.get("workloads", cells):
            assert cell in cells
            assert reports(end[p["moves"]], cell), (p["name"], cell)
        assert os.path.exists(os.path.join(
            BENCH, "readers", p["name"] + ".py")), p["name"]


def test_every_reader_has_an_entry():
    named = {p["name"] for p in manifest()["per_layer"]}
    for path in glob.glob(os.path.join(BENCH, "readers", "*.py")):
        name = os.path.basename(path)[:-3]
        assert name in named, f"reader {name} has no per_layer entry"


def test_names_and_units_are_of_the_allowed_characters():
    m = manifest()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        assert len(names) == len(set(names))
        for e in m[group]:
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for cell in m["workloads"]:
        assert NAME.match(cell["traffic"]) and NAME.match(cell["config"])
        assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    pairs = [(c["config"], c["traffic"]) for c in m["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_a_cell_is_added_by_adding_files_alone(tmp_path):
    """A throw-away cell: one new traffic file, one limits file and one
    manifest entry resolve through the same loader."""
    m = manifest()
    base = m["workloads"][0]
    with open(os.path.join(BENCH, "traffic", base["traffic"] + ".json")) as f:
        traffic = json.load(f)
    traffic["clients"] = 3
    extra = dict(base, name="throw_away", traffic="throw_away_mix")
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "throw_away_mix.json").write_text(
        json.dumps(traffic))
    # the loader joins HERE with (kind, file): point it at both trees
    real = bench_run.load_json

    def both(*parts):
        mine = tmp_path.joinpath(*parts)
        return json.loads(mine.read_text()) if mine.exists() else real(*parts)

    cells = {w["name"]: w for w in m["workloads"] + [extra]}
    assert both("traffic", cells["throw_away"]["traffic"] + ".json")[
        "clients"] == 3
    assert both("configs", extra["config"] + ".json")["name"] == \
        base["config"]


# --- no chip, no result -------------------------------------------------

def test_main_exits_nonzero_on_a_cpu_and_prints_no_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = manifest()["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "metrics" not in done.stdout and done.stdout.strip() == ""
    assert "needs a TPU" in done.stderr


def test_peaks_table_has_the_v5e_and_no_default():
    from benchmarks.harness import device
    peaks = device.load_peaks()
    row = peaks["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12
    assert row["int8_ops_per_s"] == 393e12
    assert row["hbm_bytes_per_s"] == 819e9 and row["hbm_bytes"] == 16e9
    assert "TPU v5e" in row["source"]
    assert "default" not in peaks

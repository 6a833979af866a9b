"""``BENCHMARK.json`` against its files: every cell finds its configuration,
traffic and entry point, every metric its reader, by name alone."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys():
    assert set(SPEC) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_files(w):
    configs = {c["name"]: c for c in SPEC["configs"]}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4)
    cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    assert (BENCH / "entries" / f"{traffic['entry']}.py").is_file()
    assert (BENCH / "checks" / f"{traffic['check']}.py").is_file()
    assert (BENCH / "generators" / f"{cfg['generator']}.py").is_file()
    assert traffic["mode"] in ("count", "pairs")
    assert set(traffic["limits"]) == {"band"}
    assert all(isinstance(v, (int, float)) and v > 0 for v in traffic["limits"].values())
    assert set(configs[w["config"]]["reduced"]) <= set(cfg["reduced"])


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(c):
    cfg = json.loads((ROOT / c["file"]).read_text())
    assert cfg["name"] == c["name"]
    assert cfg["num_points"] < cfg["published"]["num_points"]
    assert set(c["reduced"]) == set(cfg["reduced"]) == {"num_points"}
    assert c["file"].startswith("bench/configs/")


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_reader(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if m in SPEC["per_layer"]:
        e2e = {e["name"]: e for e in SPEC["end_to_end"]}
        assert m["moves"] in e2e
        # every cell the metric lists reports the end-to-end metric it moves
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads", cells))
    else:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_enough():
    for w in SPEC["workloads"]:
        def applies(m):
            return w["name"] in m.get("workloads", [w["name"]])
        e2e = [m["name"] for m in SPEC["end_to_end"] if applies(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(applies(m) for m in SPEC["per_layer"])


def test_unique_names_and_layers():
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)

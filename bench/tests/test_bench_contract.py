"""The benchmark's contract, checked on the CPU: refusal without a
chip, the shape of the last line, and the names in BENCHMARK.json."""
import json
import os
import re
import subprocess
import sys

import pytest

import _paths
import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(_paths.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_paths.BENCH, "run.py"),
         "--workload", "bmlp.interactive", "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=_paths.ROOT,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("traced", [False, True])
def test_last_line_keys(traced):
    line = run.result_line(
        correct=True, attempted=3, failed=0,
        metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1},
        checks_={"missing": {"value": 0, "limit": 0}},
        breakdown={"device_ops": [], "idle_gaps": []} if traced else None)
    keys = list(json.loads(line))
    want = list(run.RESULT_KEYS) + (["breakdown"] if traced else [])
    assert keys == want + ["checks"]


def test_names_and_units(bench):
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [w["config"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    metrics = bench["end_to_end"] + bench["per_layer"]
    names += [m["name"] for m in metrics]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for text in ([w["why"] for w in bench["workloads"]] +
                 [c["why"] for c in bench["configs"]] +
                 [m["layer"] for m in bench["per_layer"]] +
                 bench["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    all_names = [m["name"] for m in metrics]
    assert len(set(all_names)) == len(all_names)


def test_every_name_has_its_file(bench):
    for c in bench["configs"]:
        with open(os.path.join(_paths.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(
            _paths.BENCH, "reference", cfg["reference"] + ".py"))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            _paths.BENCH, "traffic", w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        assert callable(run.metric_reader(m["name"]).read), m["name"]


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        mine = {m["name"] for m in run.cell_metrics(bench, w["name"],
                                                    "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2, w["name"]
        layers = run.cell_metrics(bench, w["name"], "per_layer")
        assert layers, w["name"]
        for m in layers:
            assert m["moves"] in mine, (w["name"], m["name"])

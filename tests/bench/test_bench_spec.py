"""BENCHMARK.json is well formed, and every name in it finds its files;
a cell added as data files alone is picked up and runs."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_testkit import BENCH, REPO, run_small
from benchlib.cell import load_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in s["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in s["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        reported = [m for m in s["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
    for m in s["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
        if m["name"].endswith("_roofline") or "_roofline." in m["name"] \
                or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_name_finds_its_files():
    s = spec()
    for c in s["configs"]:
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        with open(os.path.join(REPO, c["file"])) as f:
            assert json.load(f)["source"] == c["source"]
    for w in s["workloads"]:
        cell = load_cell(w["name"])
        names = {m.name for m in cell.end_to_end + cell.per_layer}
        assert "setup_s" in names


def test_a_cell_added_as_data_files_alone_runs(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    s = spec()
    (bench / "traffic" / "tiny-burst.json").write_text(json.dumps({
        "arrival": "poisson", "rate_per_s": 4,
        "prompt_tokens": {"median": 16, "sigma": 0.3, "min": 8, "max": 40},
        "output_tokens": {"median": 8, "sigma": 0.3, "min": 4, "max": 12}}))
    (bench / "cells" / "unimo-text.tiny-burst.json").write_text(
        json.dumps({"sample_requests": 2, "logit_gap_max": 0.05}))
    s["workloads"].append({"name": "unimo-text.tiny-burst",
                           "config": "unimo-text", "traffic": "tiny-burst",
                           "chips": 1, "why": "a test cell"})
    for m in s["end_to_end"]:
        if m["name"] in ("ttft_p95_s", "itl_p95_s"):
            m["workloads"].append("unimo-text.tiny-burst")
    cell = load_cell("unimo-text.tiny-burst", bench_dir=str(bench), spec=s)
    assert cell.mix["prompt_tokens"]["max"] == 40
    res = run_small("", cell=cell, seed=77)
    assert res["correct"], res["checks"]
    assert {"ttft_p95_s", "itl_p95_s", "setup_s"} <= set(res["metrics"])


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "unimo-text.chat-short", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "TPU" in p.stderr

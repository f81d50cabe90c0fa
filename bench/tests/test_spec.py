"""BENCHMARK.json and the files it names: every cell finds its
configuration, traffic, limits and metric readers by name, and the
command refuses to run without a chip."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench.harness import cell as C, check

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WIDTH = re.compile(r"(hidden_size|intermediate_size|latent|state_size|proj|"
                   r"_dim$|_rank$|^head|expan|experts_per_tok)")


def test_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [x["name"] for x in SPEC["configs"] + SPEC["workloads"] + metrics]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in SPEC["end_to_end"])
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in SPEC["end_to_end"])
    for c in SPEC["configs"]:
        assert not any(WIDTH.search(k) for k in c["reduced"]), c["reduced"]
        assert c["file"].startswith("bench/configs/")
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files(w):
    cell = C.load_cell(w["name"])
    assert cell.chips == w["chips"] == cell.config["chips"]
    assert cell.limits is not None
    compared = [n for n in check.NUMBERS if n in cell.limits]
    assert compared
    for n in compared:
        lim = cell.limits[n]
        assert lim["lower"] < lim["limit"] < lim["upper"], (n, lim)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(C.reader(m["name"]))
    conf = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    assert set(conf["reduced"]) == set(cell.config["reduced"])
    run, pub = cell.config["run"], cell.config["published"]
    changed = {k for k in pub if run.get(k) != pub[k]}
    assert changed == set(conf["reduced"])


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr

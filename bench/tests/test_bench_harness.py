"""Whole runs of the benchmark's cell on the CPU at a small size, with its
own traffic and with the ladder mix (the generator's read and refine
sessions).

The look for a chip is skipped and the configuration shrunk; everything
else is the run the chip makes: set-up, window, the check against the
plain reference, the metric readers and the result line.  A sound run is
correct; the control and each planted fault (``bench/faults.py``) are
not."""
import copy
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import faults, harness

CELL = "isabel.compress"
#: the cell's own mix, and the generator's read/refine sessions
MIXES = ["compress", "ladder"]
ROOT = Path(harness.ROOT)


def small():
    _, _, config, _ = harness.resolve(CELL)
    config = copy.deepcopy(config)
    config["field"]["shape"] = [10, 16, 12]
    config["codec"]["chunk_elems"] = 4 * 16 * 12      # 3 chunks: 4, 4, 2
    return config


def traffic(mix):
    return harness.load_json(harness.BENCH / "traffic" / f"{mix}.json")


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """Keep the process's JAX configuration as the other tests expect it."""
    from repro import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda root: None)


def run(mix, patch=None, seed=2**31 + 11):
    return harness.run(CELL, seed, 0.2, False, time.perf_counter(),
                       require_chip=False, patch=patch,
                       config_override=small(), traffic_override=traffic(mix))


@pytest.mark.parametrize("mix", MIXES)
def test_sound_run_is_correct_and_reports_the_cell(mix):
    out = run(mix)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    want = {m["name"] for m in harness.cell_metrics(spec, CELL, False)}
    assert "setup_s" in want
    # a reader that finds nothing to read (no compress in a ladder)
    # leaves its metric out
    assert set(out["metrics"]) == (want if mix == "compress"
                                   else {"setup_s"})
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    json.dumps(out)


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("mix", MIXES)
def test_control_and_faults_are_not_correct(mix, fault):
    out = run(mix, faults.FAULTS[fault])
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("mix", MIXES)
def test_traffic_repeats_for_a_seed(mix):
    config, mixdata = small(), traffic(mix)
    driver = harness.load_module(harness.BENCH / "drivers" /
                                 f"{mixdata['driver']}.py")
    ref = harness.load_module(harness.BENCH / "references" /
                              f"{config['reference']}.py")

    def calls(seed):
        s = driver.Sessions(config, mixdata, seed, ref)
        s.setup()
        w = s.window(0.0)
        for no in range(1, 4):
            rec, _ = s.session(no)
            w.sessions.append(rec)
        seq = [(r["field"], c["call"], c.get("field_bytes"),
                c.get("bytes_read"), c.get("archive_bytes"))
               for r in w.sessions for c in r["calls"]]
        return seq, [x.tobytes() for x in s.fields]

    seq, data = calls(77)
    n = len(mixdata["session"])
    assert [c[1] for c in seq[:n]] == [s["call"] for s in mixdata["session"]]
    cycle = [c[0] for c in seq[::n]]
    pool = mixdata["fields"]
    assert cycle == [i % pool for i in range(len(cycle))]
    assert calls(77) == (seq, data)
    # every seed makes fields of its own; field i is made from seed + i
    other = calls(78)[1]
    assert other[0] != data[0]
    assert other[:pool - 1] == data[1:]


def cli(cwd, *args, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "isabel.compress",
         "--seed", "5", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_no_accelerator_exits_without_a_result():
    r = cli(ROOT)
    assert r.returncode == 2 and r.stdout == ""
    assert "no accelerator" in r.stderr


def test_benchmark_alone_exits_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = cli(tmp_path, env={"PYTHONPATH": ""})
    assert r.returncode == 2 and r.stdout == ""
    assert "not beside the benchmark" in r.stderr

"""The four-chip cell ``isabel_x4.compress``: its readers on synthetic
records of the sharded path, its declared metrics, and a whole run of it
on four virtual CPU devices at a small size."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.tests.test_bench_stages import (RECORDS, Rec, ctx_of, read,
                                           staged, summary)

ROOT = Path(harness.ROOT)


#: the sharded path: request 1's kernel I/O places its stack for 20 ns
#: (trace clock 190-210, idle 190-200) and counts 20 pad elements on 60
SHARD_RECORDS = RECORDS + [
    Rec("shard.layout", 10, 4, 1, 10090, 10110, {}, staged("shard")),
    Rec("sweep.kernel", 11, 2, 1, 10150, 10150,
        {"shard_rows": 60, "shard_pad_rows": 20}, staged("kernel_io"))]
SHARD_METRICS = {"pad_share.compress_x4": 20 / 60,
                 "idle.shard.compress_x4": 1.0}


def test_shard_readers_give_the_split(monkeypatch):
    from repro import trace

    monkeypatch.setattr(trace, "records", lambda: list(SHARD_RECORDS))
    monkeypatch.setattr(trace, "dropped", lambda: 0)
    ctx = ctx_of(summary())
    for name, want in SHARD_METRICS.items():
        assert read(name, ctx) == pytest.approx(want), name
    # what the placement idled leaves kernel I/O's share
    assert read("idle.kernel_io.compress", ctx) == pytest.approx(3.0)


def test_shard_readers_are_silent_on_the_unsharded_path(monkeypatch):
    """A program that records no sharded span or counter -- the one-chip
    path, or a program without them -- gives neither metric."""
    from repro import trace

    monkeypatch.setattr(trace, "records", lambda: list(RECORDS))
    monkeypatch.setattr(trace, "dropped", lambda: 0)
    for name in SHARD_METRICS:
        assert read(name, ctx_of(None)) is None
        assert read(name, ctx_of(summary())) is None


def test_the_four_chip_cell_reports_its_own_metrics():
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = "isabel_x4.compress"
    assert {m["name"] for m in harness.cell_metrics(spec, cell, False)} \
        == {"compress_MBps", "ratio", "setup_s"}
    assert {m["name"] for m in harness.cell_metrics(spec, cell, True)} \
        == set(SHARD_METRICS)
    assert not set(SHARD_METRICS) & {m["name"] for m in harness.cell_metrics(
        spec, "isabel.compress", True)}


X4_CHILD = r"""
import copy, json, sys, time
from bench import harness
from repro import compile_cache

compile_cache.enable = lambda root: None
_, _, config, _ = harness.resolve("isabel_x4.compress")
config = copy.deepcopy(config)
config["field"]["shape"] = [10, 16, 12]
config["codec"]["chunk_elems"] = 4 * 16 * 12   # a group of 2 in 4 slots
out = harness.run("isabel_x4.compress", 2**31 + 13, 0.2, False,
                  time.perf_counter(), require_chip=False,
                  config_override=config)
print(json.dumps(out))
"""


def test_four_chip_cell_runs_on_four_virtual_devices():
    """The four-chip cell's whole run on four CPU devices at a small size:
    its configuration's ``shard: 4`` reaches ``ExecPolicy``, the sharded
    archives pass the reference, and the line reports the cell's three
    end-to-end metrics on four devices."""
    r = subprocess.run(
        [sys.executable, "-c", X4_CHILD], cwd=ROOT, capture_output=True,
        text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4",
                 PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                             str(ROOT)])))
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"compress_MBps", "ratio", "setup_s"}
    assert out["device"]["count"] == 4

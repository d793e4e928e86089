"""The four-device writer, ``ExecPolicy(backend="jax", shard=4)``, on
SpeedX-shaped fields: the chunk layout of the ``isabel_x4`` deployment
(100 rows cut into six 16-row chunks and one 4-row tail) at a width the
CPU interpreter can run.

Four virtual CPU devices exist only in a process whose ``XLA_FLAGS``
asked for them before JAX started, so one child process compresses every
field twice (``shard=None`` and ``shard=4``, each under
``repro.trace.recording()``) and decodes the sharded archive with the
benchmark's plain reference; the tests read its report.  Pinned:

(a) the sharded archive's bytes equal the one-device archive's;
(b) the reference's full read of it lies within ``eb`` of the field;
(c) the pad counters read the layout's exact value: the six-chunk group
    fills 8 stack slots (2 pad) in every phase and every level pack, and
    the singleton tail takes the unsharded path, so ``shard_pad_rows /
    shard_rows`` is 2/6 -- and the ``shard=None`` compress records no
    pad counter and no ``shard.layout`` span.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SEEDS = (2147484011, 7)
SHAPE = (100, 24, 20)
CHUNK_ELEMS = 16 * 24 * 20           # 6 x 16 rows + 1 x 4 rows

CHILD = r"""
import json, sys
import numpy as np
from bench import fields
from bench.references import ipc3_float32 as ref
from repro import trace
from repro.api import Codec, ExecPolicy

seeds, shape, chunk = json.loads(sys.argv[1])
codec = Codec(eb=1e-6, relative=True, interp="cubic", chunk_elems=chunk,
              version=3)
out = {}
for seed in seeds:
    x = fields.generate("SpeedX", "weather", shape, seed)
    eb = 1e-6 * (float(x.max()) - float(x.min()))
    runs = {}
    for name, policy in (("one", ExecPolicy(backend="jax")),
                         ("four", ExecPolicy(backend="jax", shard=4))):
        trace.clear()
        with trace.recording():
            arc = codec.compress(x, policy)
        counts, spans = {}, set()
        for r in trace.records():
            spans.add((r.name, r.attrs.get("stage")))
            for k, v in r.counts.items():
                counts[k] = counts.get(k, 0) + v
        runs[name] = dict(buf=arc.tobytes(), counts=counts,
                          spans=sorted(map(list, spans)))
    runs["four"]["chunks"] = len(ref.header(runs["four"]["buf"])
                                 ["chunk_headers"])
    err = ref.max_error(ref.decode(runs["four"]["buf"]), x)
    out[seed] = dict(
        equal=runs["four"]["buf"] == runs["one"]["buf"], err_over_eb=err / eb,
        **{f"{k}_{n}": v for n in ("one", "four")
           for k, v in runs[n].items() if k != "buf"})
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    r = subprocess.run(
        [sys.executable, "-c", CHILD,
         json.dumps([SEEDS, SHAPE, CHUNK_ELEMS])],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return {int(k): v for k, v in json.loads(r.stdout.splitlines()[-1])
            .items()}


@pytest.mark.parametrize("seed", SEEDS)
def test_sharded_archive_bytes_equal_one_device(report, seed):
    assert report[seed]["chunks_four"] == 7
    assert report[seed]["equal"]


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_reads_sharded_archive_within_eb(report, seed):
    assert report[seed]["err_over_eb"] <= 1.0


@pytest.mark.parametrize("seed", SEEDS)
def test_pad_counters_read_the_layout(report, seed):
    four = report[seed]["counts_four"]
    assert four["shard_rows"] > 0
    assert four["shard_pad_rows"] * 3 == four["shard_rows"]
    # the padded stacks are what moves: more bytes than one device moves
    one = report[seed]["counts_one"]
    assert four["h2d_bytes"] > one["h2d_bytes"]
    assert four["d2h_bytes"] > one["d2h_bytes"]


@pytest.mark.parametrize("seed", SEEDS)
def test_shard_spans_only_on_the_sharded_path(report, seed):
    four = {tuple(s) for s in report[seed]["spans_four"]}
    one = {tuple(s) for s in report[seed]["spans_one"]}
    assert ("shard.layout", "shard") in four
    assert not any(name == "shard.layout" or stage == "shard"
                   for name, stage in one)
    assert not {"shard_rows", "shard_pad_rows"} & set(
        report[seed]["counts_one"])

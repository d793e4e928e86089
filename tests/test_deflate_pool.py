"""The encode path's deflates on the shared host pool (``bitplane.submit``).

Every occupied plane and every escape set is deflated on a worker while
the calling thread goes on to the next level and chunk; one join per
compress waits for them before the archives are written.  Pinned:

(a) an archive from the pool equals a serial reference that deflates
    each blob inline with ``zlib.compress``, and the bytes the writer
    gave before the pool existed (``GOLDEN``), on both backends, through
    the single-array path (v1), the batched group with its tail (v3) and
    the sharded chunk grid on four virtual CPU devices, at zlib levels
    0, 6 and 9, on a field whose levels hold all-zero planes;
(b) several threads compressing at once, as the checkpoint bundle's
    per-shard savers do, each get the bytes they get alone, in bounded
    time;
(c) a process forked after the pool was made deflates on a pool of its
    own.
"""
import hashlib
import json
import os
import subprocess
import sys
import threading
import zlib
from pathlib import Path

import numpy as np
import pytest

from repro.api import Codec, ExecPolicy
from repro.core import bitplane, container

ROOT = Path(__file__).resolve().parent.parent

#: 4-row chunks of a 10-row field: a batched group of two and a 2-row tail
CODECS = {"v1": Codec(eb=1e-4, relative=True),
          "v3": Codec(eb=1e-4, relative=True, chunk_elems=4 * 16 * 20,
                      version=3)}
LEVELS = (0, 6, 9)
#: sha256 of the archives of :func:`field` as the writer made them with
#: every deflate inline (the same on both backends and with ``shard=4``);
#: they depend on the zlib build as well as on the codec
GOLDEN = {
    ("v1", 0): "d7281fe735479b927d4e1b0e248cfe9b24428ab80777e7c72932bf8727c7f41f",
    ("v3", 0): "ab0f8b501980eefe1ad9d620c68a1381466becfc0d71259135cb7ea2340c2df6",
    ("v1", 6): "79a7001e4d666f6f5eb154a2c964c83cdafd9bc6b9008589252a4c09d4e1901d",
    ("v3", 6): "c85f98db846a3dbf0e7d152b9302f83fd8167c06915f831c443660f1f7e5d049",
    ("v1", 9): "24f7193d441cf12803b4dc5ad3fb099d2e682a3d9750f2212cd7120208f4d175",
    ("v3", 9): "c287655ea5e60cd7138ba5d178e5b62f8a93396882c90cbe74eff9cd76f3811c",
}


def field(shape=(10, 16, 20), seed=0):
    i, j, k = np.meshgrid(*(np.arange(n) for n in shape), indexing="ij")
    x = np.sin(i * 0.3 + seed) + np.cos(j * 0.2) * k * 0.01
    return x.astype(np.float32)


def inline_submit(level, n_in, make, *args):
    """The serial reference: each blob deflated on the calling thread."""
    return zlib.compress(make(*args), level)


def chunk_levels(buf):
    """Every chunk's level headers, one archive of any version."""
    r = container.open_reader(buf)
    metas = getattr(r.meta, "chunk_metas", [r.meta])
    return [lv for m in metas for lv in m.levels]


def digest(buf) -> str:
    return hashlib.sha256(buf).hexdigest()


CHILD = r"""
import hashlib, json, os, sys, zlib
import numpy as np
sys.path.insert(0, "tests")
from test_deflate_pool import CODECS, LEVELS, field, inline_submit
from repro.api import ExecPolicy
from repro.core import bitplane

x, policy, out = field(), ExecPolicy(backend="jax", shard=4), {}
submit = bitplane.submit
for level in LEVELS:
    os.environ["IPCOMP_ZLIB_LEVEL"] = str(level)
    for name, fn in (("pool", submit), ("serial", inline_submit)):
        bitplane.submit = fn
        buf = CODECS["v3"].compress(x, policy).tobytes()
        out[f"{name}{level}"] = hashlib.sha256(buf).hexdigest()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded():
    """Digests of the ``shard=4`` archives, pooled and serial, from a
    child process with four virtual CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env.pop(bitplane.ZLEVEL_ENV, None)
    r = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("level", LEVELS)
@pytest.mark.parametrize("path", ["numpy-v1", "numpy-v3", "jax-v1",
                                  "jax-v3", "jax-v3-shard4"])
def test_pooled_archive_equals_serial_and_golden(path, level, monkeypatch,
                                                 request):
    if path == "jax-v3-shard4":
        got = request.getfixturevalue("sharded")
        assert got[f"pool{level}"] == got[f"serial{level}"]
        assert got[f"pool{level}"] == GOLDEN[("v3", level)]
        return
    backend, layout = path.split("-")
    monkeypatch.setenv(bitplane.ZLEVEL_ENV, str(level))
    x, codec = field(), CODECS[layout]
    pooled = codec.compress(x, ExecPolicy(backend=backend)).tobytes()
    with monkeypatch.context() as m:
        m.setattr(bitplane, "submit", inline_submit)
        serial = codec.compress(x, ExecPolicy(backend=backend)).tobytes()
    assert pooled == serial
    assert digest(pooled) == GOLDEN[(layout, level)]
    # the field's levels hold all-zero planes, stored as empty blobs
    assert any(0 in lv.plane_sizes for lv in chunk_levels(pooled))


def test_all_zero_planes_are_empty_and_never_submitted(monkeypatch):
    """Every bin 0 or 32 (negabinary 0b1100000): XOR coding leaves planes
    5, 4 and 2..0 all zero.  Both level encoders store those as ``b''``
    without a job and deflate the other two to the serial bytes."""
    from repro.core import jax_backend, negabinary

    q = np.where(np.arange(300) % 3 == 0, 32, 0).astype(np.int64)
    nb = negabinary.to_negabinary(q)
    encs = [bitplane.encode_level(nb), jax_backend.encode_level(q, 1.0)[:2]]
    with monkeypatch.context() as m:
        m.setattr(bitplane, "submit", inline_submit)
        serial, nbits = bitplane.encode_level(nb)
    assert nbits == 7 and serial[1] == serial[2] == b""
    for blobs, nbits in encs:
        assert nbits == 7
        assert [isinstance(b, bitplane.Deflate) for b in blobs] == [
            True, False, False, True, False, False, False]
        assert bitplane.wait(blobs) == serial


def test_concurrent_compresses_match_alone():
    """More caller threads than CPUs, each compressing its own field
    several times at once through the shared pool, as the checkpoint
    bundle's per-shard savers do: each gets the bytes it gets alone, and
    every thread ends well inside its time limit (a deadlock fails)."""
    callers = min(2 * len(os.sched_getaffinity(0)) + 1, 33)
    xs = [field((10, 16, 20), seed=s) for s in range(callers)]
    alone = [CODECS["v3"].compress(x, ExecPolicy(backend="numpy")).tobytes()
             for x in xs]
    got = [[] for _ in xs]

    def work(k):
        for _ in range(3):
            got[k].append(CODECS["v3"].compress(
                xs[k], ExecPolicy(backend="numpy")).tobytes())

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=work, args=(k,), daemon=True)
              for k in range(callers)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    assert got == [[a] * 3 for a in alone]


FORK_CHILD = r"""
import os, threading, warnings
import numpy as np
from repro.core import bitplane

warnings.simplefilter("ignore", DeprecationWarning)   # fork with threads
nb = np.ones(1000, np.uint32)
bitplane.wait(bitplane.encode_level(nb)[0])           # the pool exists
if os.fork() == 0:
    got = []
    t = threading.Thread(daemon=True, target=lambda: got.append(
        bitplane.wait(bitplane.encode_level(nb)[0])))
    t.start()
    t.join(timeout=30)
    print("child", "hung" if t.is_alive() else len(got[0]), flush=True)
    os._exit(0)
os.wait()
"""


def test_a_forked_child_deflates_on_a_pool_of_its_own():
    """A child forked after the pool was made has none of its workers:
    its first deflate must get a fresh pool, not queue for ever."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", FORK_CHILD], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-4000:]
    assert r.stdout.split() == ["child", "1"]

"""The general traffic generator: closed-loop sessions of codec calls.

A traffic mix (``bench/traffic/<mix>.json``) names this driver and gives,
as data:

``fields``
    how many fields the writer or reader cycles through: they are made
    from ``--seed``, ``--seed + 1``, ..., each at the configuration's
    shape and with its own value range, so that every seed brings data
    (and absolute bounds) of its own.
``archive``
    true when the sessions read archives: set-up then compresses every
    field with the code under test, as a writer would have.
``session``
    the calls of one session, in order: ``{"call": "compress"}``,
    ``{"call": "open"}``, or ``{"call": "read" | "refine",
    "bound_of_range": f}`` (an absolute L-inf bound of ``f`` times the
    field's value range; ``null`` asks for full fidelity).

One client runs sessions back to back (a closed loop): the next session
starts when the previous one has returned its last answer; session ``n``
works on field ``n mod fields``.  One session per field, drawn from the
seed, has its answers checked against the reference after the window.
Each call runs inside a host span of its name
(``jax.profiler.TraceAnnotation``), which labels the device's idle gaps
in a traced run.
"""
from __future__ import annotations

import sys
import time
import traceback
from dataclasses import dataclass, field
from typing import List

import numpy as np

from bench import fields

#: sessions per field whose answers are checked after the window
CHECKED_PER_FIELD = 1

@dataclass
class Window:
    """What the window did: one record per session that returned."""
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    sessions: List[dict] = field(default_factory=list)

    def calls(self, *names) -> List[dict]:
        return [c for s in self.sessions for c in s["calls"]
                if c["call"] in names]


class Sessions:
    def __init__(self, config: dict, traffic: dict, seed: int, reference):
        from repro.api import Codec, ExecPolicy

        self.config, self.traffic, self.seed = config, traffic, seed
        self.reference = reference
        c = config["codec"]
        self.codec = Codec(eb=c["eb"], interp=c["interp"],
                           relative=c["relative"],
                           chunk_elems=c["chunk_elems"],
                           version=c["version"])
        self.policy = ExecPolicy(**config["policy"])
        self.steps = traffic["session"]
        self.rng = np.random.default_rng([seed, 0x5E55])
        self.kept: dict = {}      # field -> [(session no, answers)]
        self.archives = []

    def setup(self) -> None:
        f = self.config["field"]
        self.fields = [fields.generate(f["name"], f["kind"], f["shape"],
                                       self.seed + i)
                       for i in range(self.traffic["fields"])]
        self.ranges = [float(x.max()) - float(x.min()) for x in self.fields]
        c = self.config["codec"]
        self.ebs = [c["eb"] * r if c["relative"] else c["eb"]
                    for r in self.ranges]
        if self.traffic["archive"]:
            self.archives = [self.codec.compress(x, self.policy)
                             for x in self.fields]
        # warm-up: one session per field.  The program takes constants of
        # the data (the absolute bound, the planes a read keeps) at
        # compile time, so each field may bring programs of its own.
        for no in range(len(self.fields)):
            self.session(no)

    def session(self, no: int):
        """Run session ``no``; returns (record, answers)."""
        import jax
        from repro.api import Fidelity

        k = no % len(self.fields)
        x, vrange, eb = self.fields[k], self.ranges[k], self.ebs[k]
        calls, answers, reader = [], [], None
        t0 = time.perf_counter()
        for step in self.steps:
            name = step["call"]
            with jax.profiler.TraceAnnotation(name):
                if name == "compress":
                    arc = self.codec.compress(x, self.policy)
                elif name == "open":
                    reader = self.archives[k].open(self.policy)
                else:
                    f = step["bound_of_range"]
                    bound = eb if f is None else f * vrange
                    fid = Fidelity.full() if f is None \
                        else Fidelity.error_bound(bound)
                    y = getattr(reader, name)(fid)
            rec = {"call": name, "t": time.perf_counter() - t0}
            if name == "compress":
                rec.update(field_bytes=x.nbytes, archive_bytes=arc.nbytes)
                answers.append(("archive", arc, eb))
            elif name != "open":
                rec.update(bytes_read=reader.bytes_read,
                           archive_bytes=reader.archive.nbytes,
                           reported=reader.achieved_bound)
                answers.append(("array", y, bound, reader.achieved_bound,
                                len(answers) + 1))
            calls.append(rec)
        return {"field": k, "calls": calls}, answers

    def window(self, seconds: float) -> Window:
        """Sessions back to back until ``seconds`` have passed; the window
        closes when the last session returns."""
        import jax

        w = Window()
        seen = [0] * len(self.fields)
        keep = CHECKED_PER_FIELD
        with jax.profiler.TraceAnnotation("window"):
            t0 = time.perf_counter()
            while w.attempted == 0 or time.perf_counter() - t0 < seconds:
                no = w.attempted
                w.attempted += 1
                try:
                    rec, answers = self.session(no)
                except Exception:
                    w.failed += 1
                    traceback.print_exc(file=sys.stderr)
                    continue
                w.sessions.append(rec)
                k = rec["field"]
                # reservoir sample of `keep` sessions per field
                slot = seen[k] if seen[k] < keep else \
                    int(self.rng.integers(0, seen[k] + 1))
                seen[k] += 1
                if slot < keep:
                    kept = self.kept.setdefault(k, [])
                    if slot < len(kept):
                        kept[slot] = (no, answers)
                    else:
                        kept.append((no, answers))
            w.window_s = time.perf_counter() - t0
        return w

    def release(self):
        """The sampled answers, as plain host data; drops the program's
        objects (archives, sessions) so that the check runs without them."""
        out = []
        for k, kept in sorted(self.kept.items()):
            for _, answers in kept:
                for a in answers:
                    if a[0] == "archive":
                        out.append((k, "archive", a[1].tobytes(), a[2]))
                    else:
                        out.append((k, "array") + tuple(a[1:]))
        self.kept.clear()
        self.archives = []
        return out

    def check(self, answers) -> List[tuple]:
        """(name, reading, limit) of every number compared; each reading
        is the worst over the sampled answers.  The limits are the
        configuration's guarantees: a full read within ``eb``, and each
        read within the bound it asked for and the bound it reports."""
        ref = self.reference
        worst: dict = {}

        def note(name, v):
            v = v if np.isfinite(v) else float("inf")
            worst[name] = max(worst.get(name, 0.0), v)

        for k, kind, *rest in answers:
            x = self.fields[k]
            if kind == "archive":
                buf, eb = rest
                try:
                    err = ref.max_error(ref.decode(buf), x)
                except Exception:
                    traceback.print_exc(file=sys.stderr)
                    err = float("inf")
                note("full_read_err/eb", err / eb)
            else:
                y, bound, reported, j = rest
                err = ref.max_error(y, x)
                note(f"r{j}_err/bound", err / bound)
                note(f"r{j}_err/reported", err / reported if reported > 0
                     else (float("inf") if err > 0 else 0.0))
                note(f"r{j}_reported/bound", reported / bound)
        if not worst:
            return [("answers_missing", 1.0, 0.0)]
        return [(n, v if np.isfinite(v) else 1e308, 1.0)
                for n, v in worst.items()]

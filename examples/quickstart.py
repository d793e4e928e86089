"""Quickstart: compress a scientific field, retrieve progressively.

The object API in four moves: a ``Codec`` holds the bytes-affecting
spec, ``compress`` returns an ``Archive``, ``open()`` starts a
progressive session, and each ``read(Fidelity...)`` fetches only the
bitplanes the new target adds.

  PYTHONPATH=src python examples/quickstart.py
"""
from repro import Codec, Fidelity
from repro.configs.paper import TABLE3, generate
from repro.core import metrics


def main():
    x = generate(TABLE3[0], scale=0.12)            # Density-like field
    rng = float(x.max()) - float(x.min())

    archive = Codec(eb=1e-6, relative=True).compress(x)
    print(f"field {x.shape}  raw {x.nbytes/1e6:.1f} MB  "
          f"archive {archive.nbytes/1e6:.2f} MB  "
          f"CR={x.nbytes/archive.nbytes:.1f}")

    session = archive.open()
    ladder = [Fidelity.error_bound(e * rng) for e in (1e-2, 1e-4, 1e-6)]
    for fid, out in session.ladder(ladder):
        print(f"request L_inf <= {fid.value/rng:.0e}*range: "
              f"achieved {metrics.linf(x, out)/rng:.2e}*range, "
              f"read {session.bytes_read/1e6:.2f} MB "
              f"({100*session.bytes_read/archive.nbytes:.0f}% of archive), "
              f"single pass")


if __name__ == "__main__":
    main()

"""The plain reference decoder reads the program's archives exactly as the
program does, and holds a full read to the archive's bound."""
import numpy as np
import pytest

from bench import fields
from bench.references import ipc3_float32 as ref
from repro.api import Codec, ExecPolicy, Fidelity

NUMPY = ExecPolicy(backend="numpy")


@pytest.mark.parametrize("shape,chunk,interp,rel", [
    ((20, 37, 33), 3000, "cubic", 1e-6),
    ((20, 37, 33), 3000, "cubic", 1e-3),
    ((16, 25, 25), 2000, "linear", 1e-6),
    ((9, 70, 5), 10 ** 6, "cubic", 1e-5),
])
def test_full_read_equals_the_program(shape, chunk, interp, rel):
    x = fields.generate("SpeedX", "weather", shape, 3)
    arc = Codec(eb=rel, relative=True, chunk_elems=chunk, version=3,
                interp=interp).compress(x, NUMPY)
    want = arc.open(NUMPY).read(Fidelity.full())
    got = ref.decode(arc.tobytes())
    assert got.tobytes() == want.tobytes()
    assert ref.max_error(got, x) < rel * float(np.ptp(x))


def test_escapes_are_overwritten_exactly():
    """A field with spikes far off the prediction escapes some elements;
    the reference must put their exact values back."""
    x = fields.generate("Density", "turbulence", (16, 20, 24), 4)
    x.reshape(-1)[::97] = np.float32(3e4)
    x.reshape(-1)[5::89] = np.float32(1e-39)     # subnormal
    arc = Codec(eb=1e-7, relative=True, chunk_elems=2000,
                version=3).compress(x, NUMPY)
    h = ref.header(arc.tobytes())
    assert any(lv["esc_size"] for c in h["chunk_headers"]
               for lv in c["levels"])
    got = ref.decode(arc.tobytes())
    assert got.tobytes() == arc.open(NUMPY).read(Fidelity.full()).tobytes()


def test_other_containers_and_dtypes_are_refused():
    x = np.linspace(0, 1, 64).reshape(4, 16)
    v1 = Codec(eb=1e-3).compress(x.astype(np.float32), NUMPY)
    with pytest.raises(ValueError):
        ref.decode(v1.tobytes())
    v3 = Codec(eb=1e-3, chunk_elems=32, version=3).compress(x, NUMPY)
    with pytest.raises(ValueError):
        ref.decode(v3.tobytes())


def test_max_error():
    x = np.zeros((3, 4), np.float32)
    y = x.copy()
    y[1, 2] = 0.5
    assert ref.max_error(y, x) == 0.5
    assert ref.max_error(y[:2], x) == float("inf")
    y[0, 0] = np.nan
    assert ref.max_error(y, x) == float("inf")

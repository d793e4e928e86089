"""The benchmark's field generator equals the program's Table 3 generator
bit for bit, and every seed makes a field of its own, value range
included."""
import numpy as np
import pytest

from bench import fields
from repro.configs.paper import TABLE3, generate


@pytest.mark.parametrize("ds", TABLE3, ids=lambda d: d.name)
@pytest.mark.parametrize("scale", [0.04, 0.09])
def test_separable_generator_is_bit_identical(ds, scale):
    shape = tuple(max(16, int(s * scale)) for s in ds.shape)
    want = generate(ds, scale=scale, seed=2**31 + 17)
    got = fields.generate(ds.name, ds.kind, shape, 2**31 + 17)
    assert got.dtype == np.float32 and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


def test_seed_changes_the_field_and_repeats():
    a = fields.generate("SpeedX", "weather", (16, 20, 20), 5)
    assert fields.generate("SpeedX", "weather", (16, 20, 20), 5).tobytes() \
        == a.tobytes()
    assert not np.array_equal(
        a, fields.generate("SpeedX", "weather", (16, 20, 20), 6))


def test_each_seed_has_its_own_value_range():
    """A relative bound then gives every seed its own absolute bound."""
    ranges = {float(np.ptp(fields.generate("Density", "turbulence",
                                           (16, 20, 24), seed)))
              for seed in (9, 10, 2**31 + 9)}
    assert len(ranges) == 3

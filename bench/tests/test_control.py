"""The control - the reference computed in bfloat16, the precision below the
configuration's float32 - fails each configuration's limits, on three seeds
at a size a test run can hold.  On the chip the same reading is taken at
the cells' own sizes by ``readings.py``."""

import pytest

import readings
from conftest import cell_of


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
@pytest.mark.parametrize("config, traffic, chips", [("tpch-sf1-1chip", "q3-q18", 1),
                                                    ("tpch-sf1-1chip", "scan", 1),
                                                    ("tpch-sf4-4chip", "q3-q18", 4)])
def test_control_fails_the_limits(config, traffic, chips, seed):
    c = cell_of(config, traffic, chips, 0.02)
    got = readings.control_reading(c, seed)
    limits = c.config["limits"]
    assert got["rel_err"] > limits["rel_err"] or got["wrong"] > limits["wrong"], got

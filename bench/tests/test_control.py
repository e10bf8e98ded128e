"""The control fails: the plain reference one precision down
(``reference.CONTROL``: its GP and its progress and usage samples rounded
to bfloat16) put in the program's place comes out not correct against
each configuration's limits, where the program itself comes out correct.
At a small size on the CPU; ``bench/control.py`` reads the same numbers on
the chip at each cell's own size."""
import time

import jax
import pytest
from conftest import tiny

from harness import cell


@pytest.mark.parametrize("name", ["paper-baseline.google-c8", "paper-gp.google-c8"])
def test_the_control_fails_where_the_program_passes(tmp_path, name):
    c = tiny(name)
    res = cell.run(c, 2**31 + 99, 0.2, False, jax.devices()[:1], time.perf_counter(),
                   tmp_path, with_control=True)
    assert res["correct"] is True, res["checks"]
    limits = c.spec["limits"]
    failed = [n for n, v in res["control"].items() if n in limits and v > limits[n]]
    assert failed, (res["control"], limits)

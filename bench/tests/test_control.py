"""The control fails the limits: on the chip only.

The control is the reference put in the program's place and computed one
precision step below what the configuration states (``high``, three bf16
passes, for the kernels' float32 at HIGHEST; ``bfloat16`` for the rest).
On the CPU every matmul precision is full float32, so there is nothing to
lower and the test skips. On a TPU:

    python3 -m pytest bench/tests/test_control.py -q
"""

import pytest

from bench import run
from bench.lib import check
from bench.tests.tiny import tiny_cell


@pytest.fixture(scope="module")
def device():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        pytest.skip("the control needs a TPU's lower-precision matmuls")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": 1}


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_control_fails_a_limit_and_the_program_passes(device, kind):
    cell = tiny_cell(kind)
    res = run.run_cell(cell, 2**31 + 101, 3.0, False, device, grace_s=30.0,
                       controls=("bfloat16",))
    limits = cell.config["limits"]
    assert res["correct"], res["checks"]
    ctl = res["controls"]["bfloat16"]
    assert any(ctl[k] > limits[k] for k in check.POLICY_NUMBERS), ctl

"""The trace reduction, on a small trace recorded on one v5e chip.

``data/small_trace.xplane.pb`` was written by ``bench/tools/record_trace.py``:
the Gram (512 x 2560, 30 features), a triangular solve (256 x 2560) and a
rank-1 update (256 x 256), three calls each, the second round inside a
``bench.policy`` host span.
"""

import pathlib

import pytest

from bench import trace_reduce
from bench.lib import cells

TRACE = pathlib.Path(__file__).parent / "data" / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData

    return trace_reduce.reduce(ProfileData.from_file(str(TRACE)), 1.0)


def test_kernels_found_with_their_launch_shapes(red):
    assert red.chips == 1 and not red.unshaped
    assert len(red.kernels["gram"]) == 3
    assert red.kernels["gram"][0].operands[:2] == [(512, 128), (2560, 128)]
    assert red.kernels["gram"][0].result == (512, 2560)
    assert len(red.kernels["tri_solve"]) == 3
    assert red.kernels["tri_solve"][0].operands == [(256, 256), (256, 2560)]
    assert len(red.kernels["cholupdate"]) == 3
    assert red.kernels["cholupdate"][0].operands == [(256, 256), (1, 256)]


def test_roofline_shares_lie_in_0_100(red):
    peaks = cells.peaks("TPU v5 lite")
    for k in ("gram", "tri_solve", "cholupdate"):
        share = red.roofline_pct(k, peaks)
        assert 0.0 < share <= 100.0, (k, share)
    assert red.roofline_pct("no_such_kernel", peaks) is None


def test_busy_is_the_union_of_ops_and_fits_the_window(red):
    total = sum(c.seconds for calls in red.kernels.values() for c in calls)
    assert total <= red.busy_s + 1e-9
    assert 0.0 < red.busy_s < red.window_s


def test_breakdown_names_modules_and_gaps(red):
    b = red.breakdown()
    assert b["device_ops"] and len(b["device_ops"]) <= 10
    names = [n for n, _ in b["device_ops"]]
    assert any("matern52_gram_pallas" in n for n in names)
    assert all(s >= 0 for _, s in b["idle_gaps"])
    labels = {n for n, _ in b["idle_gaps"]}
    assert labels <= {"idle", "bench.policy"}


def test_names_and_shapes_from_hlo_text():
    text = ('%tri_solve_pallas.7 = f32[256,2560]{1,0:T(8,128)S(1)} '
            'custom-call(f32[256,256]{1,0:T(8,128)S(1)} %a, '
            'f32[256,2560]{1,0:T(8,128)} %b), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={f32[256,256]{1,0}}')
    assert trace_reduce.op_name(text) == "tri_solve_pallas"
    assert trace_reduce.op_kind(text) == "tri_solve_pallas"
    assert trace_reduce.hlo_shapes(text) == ([(256, 256), (256, 2560)],
                                             (256, 2560))
    assert trace_reduce.module_key("jit__fit_step(8550253)") == "_fit_step"

"""The control of the check, on the chip, at a size a test run holds.

The configuration states float32 matmuls at HIGHEST precision for the
fingerprint chain. The control is the program with its own precision
switch one step lower, HIGH (three bf16 passes): at the paper's widths
that flips the top-K choice of some coefficients, and the check has to
call the run incorrect. The same size at HIGHEST has to be correct.
The cell is ``paper_backfill.quiet`` cut to two stations and a short
window; its numbers, limits and reference are the cell's own.

Needs a TPU (run on the chip with ``python -m pytest bench/tests``);
elsewhere it is skipped.
"""
import time

import jax
import pytest

from bench import harness, traffic

WORKLOAD = "paper_backfill.quiet"


@pytest.fixture(scope="module")
def parts():
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control runs on a TPU")
    spec = harness.load_spec()
    cell, conf = harness.cell_parts(spec, WORKLOAD)
    return spec, cell, dict(conf, stations=2), traffic.load_mix(
        cell["traffic"])


@pytest.mark.parametrize("precision,correct", [("highest", True),
                                               ("high", False)])
def test_control_fails_the_check(parts, precision, correct):
    spec, cell, conf, mix = parts
    res = harness.run_cell(cell, conf, mix, 2**31 + 4242, 3.0, False,
                           harness.metrics_of(spec, WORKLOAD, False),
                           harness.limits_for(WORKLOAD),
                           t_start=time.perf_counter(), precision=precision)
    assert res["correct"] is correct, res["checks"]

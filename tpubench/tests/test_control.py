"""The control -- the reference one precision step down (float8 encoder
and scan) in the program's place -- fails the cell's limits, at a size a
test run holds; the exact reference passes them.  ``control.py`` makes
the same readings on the chip at the cell's own size."""

import time

import pytest

from tpubench import control, harness
from tpubench.tests import tiny


def _run(cell):
    name = harness.registry.workload(cell)["config"]
    return harness.Run(cell, 0, 2.0, False, time.monotonic(),
                       config=tiny.config(name), workload=tiny.workload(cell))


def _fails(numbers: dict, limits: dict) -> bool:
    return any(numbers[k] > v for k, v in limits.items())


@pytest.mark.parametrize("cell", ["flat-serve.poisson", "ivf-serve.poisson"])
def test_serve_control_fails_and_exact_passes(cell):
    run = _run(cell)
    try:
        for seed in (3000000011, 3000000012, 3000000013):
            r = control.serve_readings(run, seed, 2.0)
            limits = run.workload["limits"]
            assert _fails(r["control"], limits), r["control"]
            assert _fails(r["altered"], limits), r["altered"]
            assert not _fails(r["exact"], limits), r["exact"]
    finally:
        run.close()


def test_encode_control_fails_and_stored_passes():
    run = _run("msmarco-encode.bulk")
    try:
        for seed in (3000000021, 3000000022, 3000000023):
            r = control.encode_readings(run, seed)
            limits = run.workload["limits"]
            assert _fails(r["control"], limits), r["control"]
            assert _fails(r["altered"], limits), r["altered"]
            assert not _fails(r["stored"], limits), r["stored"]
    finally:
        run.close()

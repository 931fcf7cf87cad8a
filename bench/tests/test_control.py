"""The control put in the program's place comes out not correct: a whole
run of the harness past its look for a chip, at a tiny size on the CPU,
whose check compares the plain reference computed from fp8 operands (and,
for training, the reference over half the batch) with the f32 reference,
against the cell's own limits."""

import pytest

from bench.drivers import serve, train
from bench.drivers.common import Clock
from bench.run import run_cell
from bench.spec import peaks
from bench.tests import tiny

CASES = [("minicpm_2b.decode", serve.Serve, "fp8"),
         ("zamba2_1p2b.train", train.Train, "fp8"),
         ("zamba2_1p2b.train", train.Train, "half_batch")]


@pytest.mark.parametrize("name,cls,control", CASES,
                         ids=[f"{n}-{c}" for n, _, c in CASES])
def test_control_is_not_correct(monkeypatch, name, cls, control):
    check = cls.check
    monkeypatch.setattr(cls, "check", lambda self: check(self, control))
    cell = (tiny.cell(name, tiny.SMALL_DENSE, batch=4, new_tokens=64,
                      check_rows=8) if cls is serve.Serve else tiny.cell(name))
    r = run_cell(cell, 23, 0.3, False, Clock(), {"platform": "cpu"},
                 peaks("TPU v5 lite"))
    assert not r["correct"], r["check"]

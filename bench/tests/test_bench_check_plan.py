"""The executed plan's comparison at a size a CPU test can hold."""

from __future__ import annotations

import pytest

from bench.paths import plan
from bench.tests.tiny import run_tiny

CELL = "minicpm-2b.plan-prefill-1x2k"


def _altered(real):
    return lambda *a, **k: real(*a, **k).at[0, 0].add(100.0)


def test_sound_run_is_correct():
    out = run_tiny(CELL)
    assert out["correct"], out["check"]
    assert set(out["check"]) == {"matmul_err", "attention_err"}


def test_control_is_not_correct():
    assert not run_tiny(CELL, control=True)["correct"]


@pytest.mark.parametrize("op", ["quantized_matmul", "flash_attention"])
def test_altered_answer_is_not_correct(monkeypatch, op):
    monkeypatch.setattr(plan, op, _altered(getattr(plan, op)))
    assert not run_tiny(CELL)["correct"]

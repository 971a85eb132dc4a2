"""The prefill cell's comparison at a size a CPU test can hold."""

from __future__ import annotations

from bench.paths import serve_prefill
from bench.tests.tiny import run_tiny

CELL = "minicpm-2b.prefill-1x2k"
REAL = serve_prefill.make_prefill_step


def _token_altered(cfg, step_cfg, shard=None):
    real = REAL(cfg, step_cfg, shard)

    def step(params, batch):
        last, caches = real(params, batch)
        return last.at[0].set(-last[0]), caches
    return step


def test_sound_run_is_correct():
    out = run_tiny(CELL)
    assert out["correct"], out["check"]


def test_control_is_not_correct():
    assert not run_tiny(CELL, control=True)["correct"]


def test_altered_token_is_not_correct(monkeypatch):
    monkeypatch.setattr(serve_prefill, "make_prefill_step", _token_altered)
    assert not run_tiny(CELL)["correct"]

"""The measured window: steps queued ahead, and how it closes."""

from __future__ import annotations

import jax
import pytest

from bench import common


@pytest.mark.parametrize("in_flight", [1, 3])
def test_window_waits_in_flight_steps_behind(monkeypatch, in_flight):
    issued, waited = [], []

    def issue():
        issued.append(len(issued))
        waited_before = len(waited)
        # never more than in_flight + 1 steps issued and not waited for
        assert len(issued) - waited_before <= in_flight + 1
        return issued[-1]

    def block(x):
        waited.extend(x if isinstance(x, list) else [x])
        return x

    monkeypatch.setattr(jax, "block_until_ready", block)
    res = common.run_window(issue, 0.0, in_flight=in_flight, min_steps=7,
                            max_steps=7)
    assert res["steps"] == 7
    # every step issued is waited for, in the order it was issued
    assert waited == issued == list(range(7))

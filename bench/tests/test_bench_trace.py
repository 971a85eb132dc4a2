"""The trace reducer on a hand-built trace, where every number is known."""

from __future__ import annotations

import os

import pytest

from bench import trace

# A 10 us window, 1-11 us on the host's clock; chip 0 runs a matmul
# kernel 1.5-4.5 us and a fusion 4-6 us (overlapping: busy 1.5-6 us) and
# a fusion 0.5-1.1 us, of which 0.1 us falls in the window, all inside a
# while loop 0.5-10 us that is no op of its own; chip 1 runs 2-3 us. The
# host issues 2-4 us and blocks 4-10 us. Events are named by their HLO
# instruction's text, as a TPU trace names them.
TEXT = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 6000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.issue" } }
  event_metadata { key: 3 value { id: 3 name: "bench.block" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 600000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 9500000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%matmul_int8.3 = f32[8,8]{1,0} custom-call(s8[8,8]{1,0} %a), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %b), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
  event_metadata { key: 4 value { id: 4 name: "%while.2 = (s32[], bf16[8]{0}) while((s32[], bf16[8]{0}) %t), condition=%c, body=%d" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 2000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.9 = f32[2]{0} fusion(f32[2]{0} %c)" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    import jax
    pd = jax.profiler.ProfileData.from_text_proto(TEXT)
    return trace.reduce_profile(pd, ["matmul_int8", "flash_attention"])


def test_window_and_busy_union(reduced):
    assert reduced["window_s"] == pytest.approx(10e-6)
    # chip 0: union of 1.5-4.5, 4-6 and 1-1.1 us = 4.6 us; chip 1: 1 us
    assert reduced["busy_s"] == pytest.approx((4.6e-6 + 1e-6) / 2)
    assert reduced["chips"] == 2


def test_kernel_families(reduced):
    assert reduced["families"] == {
        "matmul_int8": {"events": 1, "seconds": pytest.approx(3e-6)}}


def test_top_ops_and_idle_gaps(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["matmul_int8.3"] == pytest.approx(3e-6)
    assert "while.2" not in ops           # a loop is not an op
    assert ops["fusion.1"] == pytest.approx(2.1e-6)
    assert ops["fusion.9"] == pytest.approx(1e-6)
    assert "jit_step" not in ops          # modules are not ops
    gaps = reduced["idle_gaps"]
    # chip 0 idles 6-11 us (the host blocks at 8.5) and 1.1-1.5 us
    assert [g[0] for g in gaps] == ["bench.block", "host:other"]
    assert [g[1] for g in gaps] == pytest.approx([5e-6, 0.4e-6])


def test_no_window_span_raises():
    import jax
    pd = jax.profiler.ProfileData.from_text_proto(
        TEXT.replace('"bench.window"', '"other"'))
    with pytest.raises(RuntimeError):
        trace.reduce_profile(pd)


# Traces recorded on a TPU v5e by ``bench/run.py --trace 1 --keep-trace``
# and cut down with ``trim_trace.py``: the first events of the window, the
# window ending with the last of them.
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _recorded(name: str) -> dict:
    return trace.reduce(os.path.join(DATA, name),
                        ["matmul_int8", "flash_attention"])


def test_recorded_plan_trace():
    """The plan cell's first 1,500 device ops: the kernel's custom calls
    are found by name, and the eager quantize ops between them are not
    counted as the kernel's."""
    red = _recorded("plan.xplane.pb")
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(0.352444196)
    assert red["busy_s"] == pytest.approx(0.123003152)
    assert red["families"] == {
        "matmul_int8": {"events": 55, "seconds": pytest.approx(0.087719491)}}
    ops = dict(red["device_ops"])
    assert ops["matmul_int8.1"] == pytest.approx(0.087719491)
    assert "abs.1" in ops and "round.1" in ops
    assert {g[0] for g in red["idle_gaps"]} == {"bench.issue"}


def test_recorded_prefill_trace():
    """The prefill cell's first 2,500 device ops: the flash kernel's
    custom call is found inside the model's step (a loop over the
    layers, which is not counted as an op)."""
    red = _recorded("prefill.xplane.pb")
    assert red["window_s"] == pytest.approx(0.214412339)
    assert red["busy_s"] == pytest.approx(0.214363793)
    assert red["families"] == {
        "flash_attention": {"events": 46,
                            "seconds": pytest.approx(0.11643817)}}
    ops = dict(red["device_ops"])
    assert max(ops, key=ops.get) == "flash_attention_bh.3"
    assert not any(name.startswith("while") for name in ops)

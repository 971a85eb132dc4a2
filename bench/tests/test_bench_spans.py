"""The program's spans and scopes read from a hand-built trace, where every
number is known, and ``bench/run_spans.py`` around a cut-down cell."""

from __future__ import annotations

import copy
import os
import types

import pytest

from bench import common, run, run_spans, spans, trace
from bench.tests import tiny

# A 10 us window, 1-11 us. The host issues one step 2-10 us: it
# quantizes 2.5-4.5 and 6.5-8.5, runs the kernel 5-6 and 9-9.5, then
# blocks 10-11 us; a quantize span at 0.2-0.8 us lies before the window
# and an ``attn`` span on another thread 3-3.5 us is no child of the
# issue. Chip 0 runs a fusion under kv_update 3-4 us, the kernel
# 5.2-6.2 us, a fusion under attn 9.2-9.8 us, an LM-head dot 10.2-10.4
# us and an op with no op_name 10.5-10.6 us, all inside a while loop;
# chip 1 runs an MLP op 2 us. As on a TPU, each op's op_name is the
# ``tf_op`` stat of its event's metadata, with a trailing colon.
TEXT = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 8000000 }
    events { metadata_id: 3 offset_ps: 2500000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 6500000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 500000 }
    events { metadata_id: 5 offset_ps: 10000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 200000 duration_ps: 600000 }
  }
  lines { id: 2 name: "worker" timestamp_ns: 0
    events { metadata_id: 6 offset_ps: 3000000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.issue" } }
  event_metadata { key: 3 value { id: 3 name: "matmul_int8.quantize" } }
  event_metadata { key: 4 value { id: 4 name: "matmul_int8.kernel" } }
  event_metadata { key: 5 value { id: 5 name: "bench.block" } }
  event_metadata { key: 6 value { id: 6 name: "attn" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 5200000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 9200000 duration_ps: 600000 }
    events { metadata_id: 4 offset_ps: 10200000 duration_ps: 200000 }
    events { metadata_id: 6 offset_ps: 10500000 duration_ps: 100000 }
    events { metadata_id: 5 offset_ps: 1000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop"
    stats { metadata_id: 1
            str_value: "jit(step)/while/body/attn/kv_update/mul:" } } }
  event_metadata { key: 2 value { id: 2 name: "%matmul_int8.1 = f32[8,8]{1,0} custom-call(s8[8,8]{1,0} %x), custom_call_target=\\"tpu_custom_call\\""
    stats { metadata_id: 1 str_value: "jit(matmul_int8)/pallas_call:" }
    stats { metadata_id: 2 int64_value: 128 } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %b), kind=kLoop"
    stats { metadata_id: 2 int64_value: 64 }
    stats { metadata_id: 1
            str_value: "jit(step)/while/body/attn/dot_general:" } } }
  event_metadata { key: 4 value { id: 4 name: "%dot.3 = f32[8]{0} dot(bf16[8]{0} %c)"
    stats { metadata_id: 1 str_value: "jit(step)/lm_head/dot_general:" } } }
  event_metadata { key: 5 value { id: 5 name: "%while.2 = (s32[]) while((s32[]) %t), condition=%c, body=%d" } }
  event_metadata { key: 6 value { id: 6 name: "%copy.4 = bf16[8]{0} copy(bf16[8]{0} %d)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "flops" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 7 offset_ps: 2000000 duration_ps: 2000000 }
  }
  event_metadata { key: 7 value { id: 7 name: "%fusion.9 = f32[2]{0} fusion(f32[2]{0} %c)"
    stats { metadata_id: 3
            str_value: "jit(step)/while/body/mlp/dot_general:" } } }
  stat_metadata { key: 3 value { id: 3 name: "tf_op" } }
}
"""


@pytest.fixture(scope="module")
def data():
    import jax
    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(TEXT)


@pytest.fixture(scope="module")
def pd(data):
    import jax
    return jax.profiler.ProfileData.from_serialized_xspace(data)


@pytest.fixture(scope="module")
def read(pd, data):
    return spans.read(pd, spans.op_names(data))


def test_op_names_come_from_the_metadatas_stat(data):
    names = spans.op_names(data)
    assert names == {
        "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop":
            "jit(step)/while/body/attn/kv_update/mul",
        '%matmul_int8.1 = f32[8,8]{1,0} custom-call(s8[8,8]{1,0} %x), '
        'custom_call_target="tpu_custom_call"':
            "jit(matmul_int8)/pallas_call",
        "%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %b), kind=kLoop":
            "jit(step)/while/body/attn/dot_general",
        "%dot.3 = f32[8]{0} dot(bf16[8]{0} %c)":
            "jit(step)/lm_head/dot_general",
        "%fusion.9 = f32[2]{0} fusion(f32[2]{0} %c)":
            "jit(step)/while/body/mlp/dot_general",
    }


def test_window_busy_and_steps_match_the_reducer(pd, read):
    red = trace.reduce_profile(pd)
    assert read["window_s"] == pytest.approx(red["window_s"])
    assert read["busy_s"] == pytest.approx(red["busy_s"])
    # chip 0: 1 + 1 + 0.6 + 0.2 + 0.1 us; chip 1: 2 us
    assert read["busy_s"] == pytest.approx((2.9e-6 + 2e-6) / 2)
    assert read["steps"] == 1


def test_self_times(read):
    got = {k: (v["count"], v["self_s"]) for k, v in read["spans"].items()}
    assert got == {
        # 8 us less its two quantize and two kernel spans
        "bench.issue": (1, pytest.approx(2.5e-6)),
        # the span before the window is not counted
        "matmul_int8.quantize": (2, pytest.approx(4e-6)),
        "matmul_int8.kernel": (2, pytest.approx(1.5e-6)),
        "bench.block": (1, pytest.approx(1e-6)),
        # on its own thread: no child of bench.issue, and takes nothing
        # from it
        "attn": (1, pytest.approx(0.5e-6)),
    }


def test_idle_split_by_innermost_span(read):
    # chip 0 idles 1-3, 4-5.2, 6.2-9.2, 9.8-10.2, 10.4-10.5, 10.6-11 us;
    # the innermost span under way: nothing 1-2, the issue 2-2.5, a
    # quantize 2.5-3 (attn on the other thread begins at 3), ...
    assert read["idle_by_span"] == {
        "host:other": pytest.approx(1e-6),
        "bench.issue": pytest.approx(0.5e-6 + 0.5e-6 + 0.3e-6 + 0.5e-6
                                     + 0.2e-6),
        "matmul_int8.quantize": pytest.approx(0.5e-6 + 0.5e-6 + 2e-6),
        "matmul_int8.kernel": pytest.approx(0.2e-6 + 0.2e-6),
        "bench.block": pytest.approx(0.2e-6 + 0.1e-6 + 0.4e-6),
    }
    idle = sum(read["idle_by_span"].values())
    assert idle == pytest.approx(10e-6 - 2.9e-6)


def test_longest_gaps_named_by_innermost_span(pd, read):
    gaps = read["idle_gaps"]
    assert [g[0] for g in gaps[:3]] == ["matmul_int8.quantize",
                                        "bench.issue", "bench.issue"]
    assert [g[1] for g in gaps[:3]] == pytest.approx([3e-6, 2e-6, 1.2e-6])
    # the same gaps as the reducer's, which names them by bench.* only
    red = trace.reduce_profile(pd)
    assert [g[1] for g in gaps] == pytest.approx(
        [g[1] for g in red["idle_gaps"]])
    assert [g[0] for g in red["idle_gaps"][:3]] == \
        ["bench.issue"] * 3


def test_scope_seconds(read):
    # averaged over two chips; the while loop is no op
    assert read["scopes"] == {
        "kv_update": pytest.approx(1e-6 / 2),
        "none": pytest.approx((1e-6 + 0.1e-6) / 2),   # kernel, copy
        "attn": pytest.approx(0.6e-6 / 2),
        "lm_head": pytest.approx(0.2e-6 / 2),
        "mlp": pytest.approx(2e-6 / 2),
    }


def test_kernel_lead(read):
    # the one matmul_int8 op starts 0.2 us after the first kernel span
    assert read["kernel_lead_s"] == pytest.approx(-0.2e-6)


def test_readings(read):
    got = spans.readings(read)
    assert got == {
        "plan_quantize_host_ms": pytest.approx(4e-3),
        "quantize_idle_share": pytest.approx(100 * 3e-6 / 10e-6),
        "kv_update_share": pytest.approx(100 * 0.5e-6 / 2.45e-6),
    }


@pytest.mark.parametrize("calls, raises", [(2, False), (3, True),
                                           (None, False)])
def test_check_calls(read, calls, raises):
    counted = {} if calls is None else {"matmul_int8.calls": calls}
    if raises:
        with pytest.raises(RuntimeError, match="dropped|holds 2"):
            spans.check_calls(read, counted)
    else:
        spans.check_calls(read, counted)


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.mark.parametrize("name", ["plan.xplane.pb", "prefill.xplane.pb"])
def test_agrees_with_the_reducer_on_recorded_traces(name):
    """Traces recorded before the program had regions: the window, busy
    time and idle gaps are the reducer's, and every gap is named by a
    ``bench.*`` span or none."""
    import jax
    pd = jax.profiler.ProfileData.from_file(os.path.join(DATA, name))
    got, red = spans.read(pd, {}), trace.reduce_profile(pd)
    assert got["window_s"] == pytest.approx(red["window_s"], rel=1e-12)
    assert got["busy_s"] == pytest.approx(red["busy_s"], rel=1e-12)
    assert got["idle_gaps"] == [[n, pytest.approx(v, rel=1e-12)]
                                for n, v in red["idle_gaps"]]
    idle = got["window_s"] - got["busy_s"]
    assert sum(got["idle_by_span"].values()) == pytest.approx(idle)
    assert set(got["idle_by_span"]) <= {"bench.issue", "bench.block",
                                        "host:other"}
    assert set(got["scopes"]) == {"none"}
    assert spans.readings(got) == {}


# Recorded on a TPU v5e with ``bench/run_spans.py --trace 1 --keep-trace``
# and cut down with ``trim_spans.py``: the plan cell's first 1,500 device
# ops (55 matmul calls), the prefill cell's first 2,500 (ten steps), the
# decode cell's first 3,000.


def _recorded(name: str) -> dict:
    return spans.reduce(os.path.join(DATA, name))


def test_recorded_plan_spans():
    got = _recorded("plan_spans.xplane.pb")
    assert got["window_s"] == pytest.approx(0.366139253)
    assert got["busy_s"] == pytest.approx(0.123199589)
    assert got["spans"]["matmul_int8.quantize"]["count"] == 56
    assert got["spans"]["matmul_int8.kernel"]["count"] == 55
    assert spans.readings(got) == {
        "plan_quantize_host_ms": pytest.approx(337.530763),
        "quantize_idle_share": pytest.approx(64.38776969919691)}
    # the reducer names these gaps bench.issue; the program's regions
    # say the host was quantizing
    assert {g[0] for g in got["idle_gaps"]} == {"matmul_int8.quantize"}


def test_recorded_plan_clocks():
    """The k-th ``matmul_int8`` op on the chip follows the (k-1)-th
    kernel span, so the pairing is sure; but it starts 1.2 to 1.5 ms
    before the k-th span that dispatched it: in this trace the device's
    clock runs about 1.5 ms ahead of the host's (``kernel_lead_s``)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(
        os.path.join(DATA, "plan_spans.xplane.pb"))
    host = sorted((e.start_ns, e.end_ns) for p in pd.planes
                  if p.name.startswith("/host:") for line in p.lines
                  for e in line.events if e.name == "matmul_int8.kernel")
    dev = sorted(e.start_ns for p in pd.planes
                 if p.name.startswith("/device:") for line in p.lines
                 for e in line.events if e.name.startswith("%matmul_int8."))
    assert len(host) == len(dev) == 55
    assert all(d > h[1] for h, d in zip(host, dev[1:]))
    leads = [h[0] - d for h, d in zip(host, dev)]
    assert 1.2e6 < min(leads) <= max(leads) < 1.51e6
    assert _recorded("plan_spans.xplane.pb")["kernel_lead_s"] == \
        pytest.approx(max(leads) * 1e-9)


def test_recorded_prefill_scopes():
    got = _recorded("prefill_scopes.xplane.pb")
    assert got["busy_s"] == pytest.approx(0.214478234)
    assert got["scopes"] == {
        "flash_attention.kernel": pytest.approx(0.116433823),
        "mlp": pytest.approx(0.040042144),
        "attn": pytest.approx(0.03480911),
        "none": pytest.approx(0.00916299),
        "flash_attention.layout": pytest.approx(0.007608617),
        "lm_head": pytest.approx(0.006290367),
        "embed": pytest.approx(0.000131183)}
    assert spans.readings(got) == {
        "flash_layout_share": pytest.approx(3.547500768772649)}
    # the kernel's device time is the reducer's flash family's
    red = trace.reduce(os.path.join(DATA, "prefill_scopes.xplane.pb"),
                       ["flash_attention"])
    assert red["families"]["flash_attention"]["seconds"] == \
        pytest.approx(got["scopes"]["flash_attention.kernel"])


def test_recorded_decode_scopes():
    """The decode cell's first 3,000 device ops: the one-hot cache update
    is the ``kv_update`` scope; the layer scan's copies of the stacked
    caches carry no program scope."""
    got = _recorded("decode_scopes.xplane.pb")
    assert got["window_s"] == pytest.approx(0.143149418)
    assert got["busy_s"] == pytest.approx(0.133797962)
    assert got["scopes"] == {
        "none": pytest.approx(0.086882457),
        "kv_update": pytest.approx(0.023894276),
        "attn": pytest.approx(0.016188247),
        "mlp": pytest.approx(0.006077811),
        "lm_head": pytest.approx(0.000752092),
        "embed": pytest.approx(3.079e-06)}
    assert spans.readings(got) == {
        "kv_update_share": pytest.approx(17.858475303233703)}
    red = trace.reduce(os.path.join(DATA, "decode_scopes.xplane.pb"))
    ops = dict(red["device_ops"])
    assert ops["multiply_add_fusion.3"] == pytest.approx(
        got["scopes"]["kv_update"], rel=1e-3)
    assert ops["copy.47"] + ops["copy.55"] < got["scopes"]["none"]


def test_scope_of_takes_the_innermost_listed_scope():
    assert spans.scope_of("jit(step)/while/body/attn/kv_update/mul") == \
        "kv_update"
    assert spans.scope_of(
        "jit(f)/attn/flash_attention.kernel/jit(flash_attention_bh)/"
        "pallas_call") == "flash_attention.kernel"
    assert spans.scope_of("jit(step)/while/body/attention/mul") == "none"


def _one_op(pd, w0, w1):
    """A device plane with one short op at the window's start: a CPU
    trace has none."""
    ev = types.SimpleNamespace(name="%fusion.1 = f32[2]{0} fusion()")
    yield [(w0, w0 + 1000, ev)]


CELLS = ["minicpm-2b.decode-b8-1k", "minicpm-2b.plan-prefill-1x2k",
         "minicpm-2b.prefill-1x2k"]


@pytest.fixture
def instrumented(monkeypatch):
    # restored after the test: run_spans replaces both
    monkeypatch.setattr(common, "run_window", common.run_window)
    monkeypatch.setattr(trace, "reduce", trace.reduce)
    extra: dict = {}
    run_spans.instrument(extra)
    return extra


@pytest.mark.parametrize("cell", CELLS)
def test_no_compile_inside_the_window(instrumented, cell):
    out = run.run_cell(copy.deepcopy(tiny.cell(cell)), tiny.SEED, 0.05,
                       False, interpret=True, device=tiny.CPU)
    assert out["correct"], out["check"]
    assert instrumented["counted"]["jax.compiles"] == 0


def test_traced_plan_run_reads_the_programs_spans(monkeypatch):
    def fake(path, families=()):
        return {"window_s": 2.0, "busy_s": 1.5, "chips": 1,
                "families": {f: {"events": 3, "seconds": 0.5}
                             for f in families},
                "device_ops": [["fusion", 1.0]],
                "idle_gaps": [["bench.block", 0.5]]}
    monkeypatch.setattr(common, "run_window", common.run_window)
    monkeypatch.setattr(trace, "reduce", fake)
    monkeypatch.setattr(spans, "_device_ops", _one_op)
    extra: dict = {}
    run_spans.instrument(extra)
    ctx = copy.deepcopy(tiny.cell("minicpm-2b.plan-prefill-1x2k"))
    ctx.traffic["trace_steps"] = 1
    device = dict(tiny.CPU, kind="TPU v5 lite")
    out = run.run_cell(ctx, tiny.SEED, 0.0, True, interpret=True,
                       device=device)
    assert out["correct"], out["check"]
    assert out["metrics"]["idle_share.plan"]["value"] == 25.0
    counted = extra["counted"]
    assert counted["jax.compiles"] == 0
    # the check passed: a quantize span for every call counted
    assert counted["matmul_int8.calls"] > 1
    got = extra["readings"]
    assert got["plan_quantize_host_ms"] > 0
    assert 0 < got["quantize_idle_share"] < 100

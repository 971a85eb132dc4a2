"""The tensor-parallel decode cell's comparison, cut to a size a CPU test
can hold and run on four virtual CPU devices (in a child process: the
device count is fixed when JAX starts): a sound run is correct and the
float8 control is not.

The cut keeps GLM-4's block (2 KV heads of 8 query heads, a half-width
interleaved rotary, QKV bias) and a (1, 4) mesh, so the cache is split
on its sequence axis as on the chip. Its limit lies between readings at
this size over five seeds (CPU): gap 0 to 0.044 sound, 0.197 to 0.512
in float8.
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import pytest

CELL = "glm4-9b.decode-b16-8k-tp4"
MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 8, "n_kv_heads": 2,
         "head_dim": 16, "rope_dim": 8, "d_ff": 128, "vocab_size": 256}
TRAFFIC = {"batch": 4, "prompt": 60, "turn": 4, "prefill_batch": 2,
           "check_sequences": 4, "mesh": [1, 4]}
LIMITS = {"gap": 0.12}
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def runs() -> dict:
    """A sound run and the control of the cut cell, on this process's
    devices."""
    from bench import run
    from bench.tests.tiny import CPU, SEED
    ctx = run.load_cell(ROOT, CELL)
    ctx.config["model"].update(MODEL)
    ctx.traffic.update(TRAFFIC)
    ctx.limits = dict(LIMITS)
    return {name: run.run_cell(copy.deepcopy(ctx), SEED, 0.05, False,
                               control=control, interpret=True, device=CPU)
            for name, control in (("sound", False), ("control", True))}


@pytest.fixture(scope="module")
def outs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    code = ("import json; from bench.tests.test_bench_check_decode_tp "
            "import runs; print(json.dumps(runs()))")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(outs):
    out = outs["sound"]
    assert out["correct"], out["check"]
    assert out["metrics"]["decode_step_ms"]["value"] > 0


def test_control_is_not_correct(outs):
    assert not outs["control"]["correct"], outs["control"]["check"]


def test_collective_share_reads_the_named_collectives():
    from bench import run
    read = run.reader(ROOT, "collective_share")
    rec = {"work": {"kernels": {"all-reduce": [], "all-gather": []}},
           "families": {"all-reduce": {"events": 80, "seconds": 0.3},
                        "all-gather": {"events": 40, "seconds": 0.1}},
           "busy_s": 2.0, "chips": 4}
    assert read(rec) == pytest.approx(5.0)      # 0.4 of 4 x 2.0 s
    assert read(dict(rec, work={"kernels": {}})) is None
    with pytest.raises(RuntimeError):
        read(dict(rec, families={}))

"""`tools/time_dynamic_scan.py`: the count it reads off a compiled program's
text, on a text small enough to read, and its refusal to time on a CPU."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

TOOL = Path(__file__).resolve().parents[2] / "tools" / "time_dynamic_scan.py"

HLO = """\
HloModule jit_scalar

%fused_computation.1 (param_0.1: f32[16,512], param_1.1: bf16[512,1024]) -> f32[16,1024] {
  %param_0.1 = f32[16,512]{1,0} parameter(0)
  %param_1.1 = bf16[512,1024]{1,0} parameter(1)
  ROOT %convolution.1 = f32[16,1024]{1,0} convolution(%param_0.1, %param_1.1), dim_labels=bf_io->bf
}

%fused_computation.2 (param_0.2: f32[16,1024]) -> f32[16,1024] {
  %param_0.2 = f32[16,1024]{1,0} parameter(0)
  ROOT %tanh.1 = f32[16,1024]{1,0} tanh(%param_0.2)
}

%body.1 (arg: (s32[], f32[16,512], f32[512,1024])) -> (s32[], f32[16,512], f32[512,1024]) {
  %arg = (s32[], f32[16,512]{1,0}, f32[512,1024]{1,0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%arg), index=0
  %gte.1 = f32[16,512]{1,0} get-tuple-element(%arg), index=1
  %constant.1 = s32[] constant(1)
  %add.1 = s32[] add(%gte.0, %constant.1)
  %fusion.1 = f32[16,1024]{1,0} fusion(%gte.1, %gte.1), kind=kOutput, calls=%fused_computation.1
  %fusion.2 = f32[16,1024]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %bitcast.1 = f32[16,1024]{1,0} bitcast(%fusion.2)
  ROOT %tuple.1 = (s32[], f32[16,512]{1,0}, f32[512,1024]{1,0}) tuple(%add.1, %gte.1, %gte.1)
}

ENTRY %main (p: f32[16,512]) -> f32[16,512] {
  %p = f32[16,512]{1,0:T(8,128)} parameter(0)
  %while.1 = (s32[]{:T(128)}, f32[16,512]{1,0:T(8,128)S(1)}, f32[512,1024]{1,0:T(8,128)}) while(%p), condition=%cond.1, body=%body.1
  ROOT %gte.9 = f32[16,512]{1,0} get-tuple-element(%while.1), index=1
}
"""


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location("time_dynamic_scan", TOOL)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_loops_of_counts_a_body_by_what_runs(tool):
    assert tool.loops_of(HLO) == [
        # add, two fusions (one holds the product); the bitcast, the tuple's parts and the constant do not run
        {"operations": 3, "fusions": 2, "products": 1, "carried_2d": ["16,512", "512,1024"]}
    ]


def test_a_cpu_gives_no_time(tool):
    with pytest.raises(SystemExit, match="never a time"):
        tool.measure("dreamer_v3_S", [], calls=1, seed=0)

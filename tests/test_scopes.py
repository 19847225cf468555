"""The train step's layers: how an HLO ``op_name`` is classified, and how
every instruction of a compiled module is mapped (analysis/scopes.py)."""
import pytest

from repro.analysis import scopes


@pytest.mark.parametrize("op_name,layer", [
    ("jit(step_fn)/jvp(fwd)/while/body/closed_call/dot_general", "fwd"),
    ("jit(step_fn)/transpose(jvp(fwd))/while/body/closed_call/mul", "bwd"),
    ("jit(step_fn)/transpose(jvp(fwd))/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general", "remat"),
    ("jit(step_fn)/shard_map/opt/mul", "opt"),
    ("jit(step_fn)/shard_map/opt/zero1.gather/all_gather", "zero1.gather"),
    ("jit(step_fn)/shard_map/sync.encode/reshape", "sync.encode"),
    ("jit(step_fn)/shard_map/sync.exchange/all_to_all", "sync.exchange"),
    ("jit(step_fn)/shard_map/psum", "unscoped"),
    ("jit(step_fn)/jvp(fwd)/while/body/flash_fusable/exp", "fwd"),
    ("", "unscoped"),
    # the expert layer's blocks leave the layers as they were
    ("jit(step_fn)/jvp(fwd)/while/body/moe.route/sort", "fwd"),
    ("jit(step_fn)/transpose(jvp(fwd))/while/body/moe.experts/pallas_call", "bwd"),
    ("jit(step_fn)/transpose(jvp(fwd))/while/body/checkpoint/"
     "rematted_computation/moe.experts/pallas_call", "remat"),
])
def test_classify(op_name, layer):
    assert scopes.classify(op_name) == layer


@pytest.mark.parametrize("op_name,block", [
    ("jit(step_fn)/jvp(fwd)/while/body/moe.route/sort", "moe.route"),
    ("jit(step_fn)/jvp(fwd)/while/body/moe.experts/pallas_call", "moe.experts"),
    ("jit(step_fn)/transpose(jvp(fwd))/while/body/transpose(jvp(moe.experts))/"
     "pallas_call", "moe.experts"),
    ("jit(step_fn)/transpose(jvp(fwd))/while/body/checkpoint/"
     "rematted_computation/moe.route/gather", "moe.route"),
    ("jit(step_fn)/jvp(fwd)/while/body/flash_fusable/exp", "other"),
    ("jit(step_fn)/shard_map/opt/mul", "other"),
    ("", "other"),
])
def test_block_of(op_name, block):
    assert scopes.block_of(op_name) == block


def test_every_layer_is_named_once():
    assert len(set(scopes.ALL)) == len(scopes.ALL)
    assert set(scopes.NAMED) < set(scopes.ALL)
    assert scopes.UNSCOPED in scopes.ALL


HLO = """HloModule jit_step_fn, entry_computation_layout={()->f32[8]}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step_fn)/shard_map/opt/mul"}
  ROOT %convert.1 = f32[8]{0} convert(%mul.1)
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p), index=1
  %copy.3 = f32[8]{0} copy(%gte.1)
  %add.2 = f32[8]{0} add(%copy.3, %copy.3), metadata={op_name="jit(step_fn)/transpose(jvp(fwd))/while/body/add"}
  %gte.0 = s32[] get-tuple-element(%p), index=0
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %add.2)
}

%cond (q: (s32[], f32[8])) -> pred[] {
  %q = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.7 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %copy.9 = f32[8]{0} copy(%fusion.7)
  %c = s32[] constant(0)
  %t = (s32[], f32[8]{0}) tuple(%c, %copy.9)
  %while.2 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/transpose(jvp(fwd))/while"}
  %all-reduce.4 = f32[8]{0} all-reduce(%fusion.7), replica_groups={{0,1}}, to_apply=%cond, metadata={op_name="jit(step_fn)/shard_map/sync.exchange/psum"}
  ROOT %out = f32[8]{0} get-tuple-element(%while.2), index=1
}
"""


def test_instruction_scopes_fill_what_xla_left_unnamed():
    m = scopes.instruction_scopes(HLO)
    # a fusion with no op_name takes the one nearest its fused root
    assert m["fusion.7"] == "opt"
    # a copy XLA put in a loop body takes its while's layer
    assert m["copy.3"] == m["while.2"] == m["add.2"] == "bwd"
    assert m["all-reduce.4"] == "sync.exchange"
    # in the entry computation a nameless copy is unscoped
    assert m["copy.9"] == "unscoped"
    assert set(m.values()) <= set(scopes.ALL)


def test_instruction_blocks_cover_the_same_instructions():
    b = scopes.instruction_blocks(HLO.replace("/while/body/add", "/while/body/moe.experts/add"))
    assert set(b) == set(scopes.instruction_scopes(HLO))
    assert b["add.2"] == "moe.experts"
    # the loop's copy takes its while's block, which names none
    assert b["copy.3"] == b["while.2"] == b["fusion.7"] == "other"

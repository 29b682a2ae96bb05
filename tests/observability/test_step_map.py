"""The one walk over the compiled step's optimized HLO
(``observability/trace_analysis.py::step_hlo``): every instruction that is
an event of a TPU trace gets a scope, a phase and a collective class. One
case a rule, on ``op_name``s read off the cells' own HLO and on a few lines
in the form XLA:TPU prints; the vocabulary against the ``jax.named_scope``
literals of the model and step code; the file written beside a trace; and
``tools/trace_by_scope.py``'s tables over a trace made by hand."""

import ast
import glob
import json
import os

import pytest

from benchmark import xplane
from hetu_galvatron_tpu.analysis.lint import _callee
from hetu_galvatron_tpu.observability import trace_analysis
from hetu_galvatron_tpu.observability.trace_analysis import (
    SCOPES,
    hlo_counts,
    scope_and_phase,
    scope_instructions,
    step_hlo,
)
from tools import trace_by_scope

pytestmark = pytest.mark.observability

PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(
    trace_analysis.__file__)))
BODY = "jit(step)/while/body/closed_call/"


@pytest.mark.parametrize("op_name,phase", [
    # the forward pass of a microbatch
    (BODY + "jvp(jit(silu))/div", "forward"),
    (BODY + "jvp(attn/qkv_proj)/bsh,hf->bsf/dot_general", "forward"),
    # the forward made again inside the backward pass
    (BODY + "transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "bsh,hf->bsf/dot_general", "recompute"),
    (BODY + "transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "jit(silu)/exp", "recompute"),
    # the backward pass
    (BODY + "transpose(jvp(jvp()))/checkpoint/"
     "jit(flash_attention_bwd_hmajor)/flash_attention_bwd_dq/pallas_call",
     "backward"),
    (BODY + "transpose(jvp())/div", "backward"),
    # once a step, under the optimizer's scope and no transformation
    ("jit(step)/optimizer/update/mul", "update"),
    ("jit(step)/optimizer/update/jit(_where)/select_n", "update"),
    # the rest: outside the three passes
    ("jit(step)/grad/clip/reduce_sum", "other"),
    ("jit(step)/while/body/grad/accumulate/add", "other"),
    ("jit(step)/while/body/squeeze", "other"),
    ("jit(step)/param_view/convert_element_type", "other"),
    ("params['layers'][0]['mlp']['wout']", "other"),
    ("", "other"),
])
def test_an_op_name_says_its_phase(op_name, phase):
    assert scope_and_phase(op_name)[1] == phase
    assert phase in trace_analysis.PHASES


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/optimizer/update/mul", "optimizer/update"),
    # a transformation wraps the part of the name stack it was applied under
    (BODY + "jvp(mixer/mamba)/ssd/ssd_scan_fwd/pallas_call",
     "mixer/mamba/ssd"),
    (BODY + "transpose(jvp(attn))/qkv_proj/dot_general", "attn/qkv_proj"),
    (BODY + "transpose(jvp(jvp(moe/dispatch)))/checkpoint/gather",
     "moe/dispatch"),
    # the deepest name wins: the cast of a weight inside a projection
    (BODY + "jvp(attn/qkv_proj)/param_view/convert_element_type",
     "param_view"),
    (BODY + "jvp(mlp/param_view)/convert_element_type", "param_view"),
    # a block's norm, the q/k norm and a mamba block's gated norm apart
    (BODY + "jvp(norm)/rsqrt", "norm"),
    (BODY + "jvp(attn/qk_norm)/rsqrt", "attn/qk_norm"),
    (BODY + "jvp(mixer/mamba/gated_norm)/rsqrt", "mixer/mamba/gated_norm"),
    # a jitted function's name is no scope, a parameter's path neither
    ("jit(step)/jit(norm)/sqrt", None),
    (BODY + "jvp(jit(head))/mul", None),
    ("params['layers'][0]['mlp']['wout']", None),
    ("opt_state.inner_states['adam'].inner_state[1].mu['embed']['wte']",
     None),
    (BODY + "jvp()/cos", None),
])
def test_an_op_name_says_its_scope(op_name, scope):
    assert scope_and_phase(op_name)[0] == scope


# a step in the form XLA:TPU prints it: a fusion that keeps its root's
# op_name, two without one (one takes what it fuses, one fuses nothing
# named), a reduction with its applied computation, a loop
ONE_CHIP = """HloModule jit_step

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(step)/while/body/closed_call/jvp(norm)/reduce_sum"}
}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/while/body/closed_call/jvp(mlp)/mul"}
  %mul.2 = f32[8]{0} multiply(%mul.1, %p), metadata={op_name="jit(step)/while/body/closed_call/jvp(mlp)/mul"}
  ROOT %cvt.1 = f32[8]{0} convert(%mul.2), metadata={op_name="jit(step)/while/body/closed_call/jvp(mlp/param_view)/convert_element_type"}
}

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %copy.7 = f32[8]{0} copy(%p.1)
}

%body.1 (t: (f32[8])) -> (f32[8]) {
  %t = (f32[8]) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%t), index=0
  %fusion.1 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/closed_call/transpose(jvp(attn/out_proj))/dot_general"}
  %reduce.1 = f32[] reduce(%fusion.3), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(step)/while/body/closed_call/jvp(norm)/reduce_sum"}
  %flash_attention_fwd.1 = f32[8]{0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/closed_call/jvp(attn/core)/flash_attention_fwd/pallas_call"}
  ROOT %tuple.1 = (f32[8]) tuple(%flash_attention_fwd.1)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a.1 = f32[8]{0} parameter(0), metadata={op_name="params['embed']['wte']"}
  %tuple.2 = (f32[8]) tuple(%a.1)
  %while.1 = (f32[8]) while(%tuple.2), condition=%cond.1, body=%body.1
  %gte.2 = f32[8]{0} get-tuple-element(%while.1), index=0
  ROOT %fusion.4 = f32[8]{0} fusion(%gte.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/optimizer/update/add"}
}
"""


def test_a_fusion_without_op_name_takes_the_commonest_scope_inside():
    found = step_hlo(ONE_CHIP)["map"]
    ins = found["instructions"]
    # two of fused_computation.1's three instructions are under mlp
    assert ins["fusion.1"] == ("mlp", "forward", None)
    assert found["inferred"] == ["fusion.1"]
    # nothing named inside: no scope, and it is not called inferred; with
    # no op_name it is of the pass its operand is of
    assert ins["fusion.2"] == (None, "forward", None)
    assert found["tails"]["fusion.2"] == "fusion"
    # a fusion WITH an op_name is what that says, whatever it fuses
    assert ins["fusion.3"] == ("attn/out_proj", "backward", None)
    assert ins["fusion.4"] == ("optimizer/update", "update", None)
    assert ins["flash_attention_fwd.1"] == ("attn/core", "forward", None)


# what carries no name stack: a copy XLA made, and the Mosaic calls libtpu
# lowers lax.ragged_dot to, which it names itself
NAMELESS = """HloModule jit_step

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(moe/dispatch)/gather"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp())/checkpoint/rematted_computation/moe/dispatch/gather"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(moe/combine))/mul"}
  %ragged-dot-metadata.1 = s32[9]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none.1 = f32[8]{0} custom-call(%fusion.1, %a, %ragged-dot-metadata.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %ragged-dot-none.2 = f32[8]{0} custom-call(%fusion.2, %fusion.3, /*index=2*/%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %copy.1 = f32[8]{0} copy(%fusion.2)
  %copy.2 = f32[8]{0} copy(%a)
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%copy.1)
  ROOT %add.1 = f32[8]{0} add(%copy.1, %fusion.3), metadata={op_name="jit(step)/grad/accumulate/add"}
}
"""


@pytest.mark.parametrize("name,expected", [
    # the grouped matmuls: the scope by the kernel's own name, the phase the
    # latest pass among the operands
    ("ragged-dot-none.1", ("moe/experts", "forward", None)),
    ("ragged-dot-none.2", ("moe/experts", "backward", None)),
    ("ragged-dot-metadata.1", ("moe/experts", "other", None)),
    # a copy without op_name: no scope, its operand's pass
    ("copy.1", (None, "recompute", None)),
    ("copy-start.1", (None, "recompute", None)),
    ("copy.2", (None, "other", None)),
    # an op_name that IS a name stack says its phase itself
    ("add.1", ("grad/accumulate", "other", None)),
])
def test_an_instruction_without_a_name_stack_asks_its_operands(name,
                                                              expected):
    read = step_hlo(NAMELESS)
    assert read["map"]["instructions"][name] == expected
    assert read["map"]["inferred"] == []
    assert scope_and_phase("ragged-dot-none") == ("moe/experts", "other")


# the program's own grouped-matmul kernels (ops/pallas/grouped_matmul.py)
# carry the name stack they were traced under; libtpu's for lax.ragged_dot
# carry none
EXPERT_KERNELS = """HloModule jit_step

%body.1 (t: (f32[8])) -> (f32[8]) {
  %t = (f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%t), index=0
  %grouped_matmul_fwd.7 = f32[8]{0} custom-call(%gte.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp())/while/body/checkpoint/rematted_computation/moe/experts/jit(rows_call)/grouped_matmul_fwd/pallas_call"}
  ROOT %tuple.1 = (f32[8]{0}) tuple(%grouped_matmul_fwd.7)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %grouped_matmul_fwd.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(moe/experts)/moe/experts/jit(rows_call)/grouped_matmul_fwd/pallas_call"}
  %grouped_matmul_fwd.2 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp())/checkpoint/rematted_computation/moe/experts/jit(rows_call)/grouped_matmul_fwd/pallas_call"}
  %grouped_matmul_drows.3 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(moe/experts))/moe/combine/moe/experts/jit(rows_call)/grouped_matmul_drows/pallas_call"}
  %grouped_matmul_dweights.4 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(moe/experts))/moe/combine/moe/experts/jit(dweights_call)/grouped_matmul_dweights/pallas_call"}
  %ragged-dot-none.5 = f32[8]{0} custom-call(%grouped_matmul_fwd.1, %a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %flash_attention_fwd.6 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn/core)/flash_attention_fwd/pallas_call"}
  %grouped_matmul_fwd.8 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(mlp)/grouped_matmul_fwd/pallas_call"}
  %t.1 = (f32[8]{0}) tuple(%a)
  %while.1 = (f32[8]{0}) while(%t.1), condition=%cond.1, body=%body.1
  ROOT %add.1 = f32[8]{0} add(%grouped_matmul_drows.3, %grouped_matmul_dweights.4)
}
"""


@pytest.mark.parametrize("name,expected", [
    ("grouped_matmul_fwd.1", ("moe/experts", "forward", None)),
    ("grouped_matmul_fwd.2", ("moe/experts", "recompute", None)),
    # the backward rule runs under the scope the pull-back was called in
    # (the combine's) and opens the experts' itself: the deepest is taken
    ("grouped_matmul_drows.3", ("moe/experts", "backward", None)),
    ("grouped_matmul_dweights.4", ("moe/experts", "backward", None)),
    # a counted pass's body
    ("grouped_matmul_fwd.7", ("moe/experts", "recompute", None)),
    ("ragged-dot-none.5", ("moe/experts", "forward", None)),
])
def test_the_experts_own_kernels_are_placed_by_their_name_stack(name,
                                                               expected):
    assert step_hlo(EXPERT_KERNELS)["map"]["instructions"][name] == expected


def test_experts_mosaic_calls_counts_the_programs_kernels_alone():
    """``experts/mosaic_calls``: the Mosaic calls named as the program's
    grouped-matmul kernels that the map puts under ``moe/experts``, every
    phase, a loop's body once; not libtpu's ``ragged-dot`` calls (which the
    scope's time still holds, through ``KERNEL_SCOPES``), not another
    scope's kernel, not a call of the name outside the scope; 0 for a step
    that ran ``lax.ragged_dot``."""
    from hetu_galvatron_tpu.ops.pallas import grouped_matmul

    assert trace_analysis.EXPERTS_SCOPE == grouped_matmul.SCOPE
    assert trace_analysis.EXPERTS_SCOPE in SCOPES
    assert trace_analysis.EXPERTS_CALLS == grouped_matmul.CALLS
    # no kernel of the program's own is in the table of libtpu's names,
    # whose comment stays true: ragged_dot is the experts' alone
    assert set(trace_analysis.KERNEL_SCOPES) == {
        "ragged-dot-none", "ragged-dot-metadata"}
    found = step_hlo(EXPERT_KERNELS)
    assert found["mosaic_custom_calls"] == 8
    assert trace_analysis.experts_kernel_calls(found) == 5
    assert trace_analysis.experts_kernel_calls(step_hlo(NAMELESS)) == 0
    assert trace_analysis.experts_kernel_calls(step_hlo(ONE_CHIP)) == 0


def test_the_map_holds_the_events_of_a_trace_and_nothing_else():
    """Instructions of a fused computation and of a reduction's applied
    computation are no events; the entry's and a loop body's are, each
    once."""
    read = step_hlo(ONE_CHIP)
    ins = read["map"]["instructions"]
    assert set(ins) == {
        "t", "gte.1", "fusion.1", "fusion.2", "fusion.3", "reduce.1",
        "flash_attention_fwd.1", "tuple.1", "a.1", "tuple.2", "while.1",
        "gte.2", "fusion.4"}
    assert ins["reduce.1"] == ("norm", "forward", None)
    assert ins["a.1"] == (None, "other", None)
    assert read["map"]["tails"]["a.1"] == "params['embed']['wte']"
    assert read["map"]["tails"]["gte.1"] == "get-tuple-element"
    assert all(len(c) == 3 and c[1] in trace_analysis.PHASES
               and (c[0] is None or c[0] in SCOPES) for c in ins.values())
    # the keys of before, from the same walk
    assert read["mosaic_custom_calls"] == 1
    assert read["mosaic_calls"] == {"flash_attention_fwd.1"}
    assert set(ins) <= read["instructions"]
    assert "add.9" in read["instructions"] and "mul.1" not in \
        read["instructions"]


# a block's flash kernels as a step holds them: the forward in the forward
# pass, a second forward in the recomputed one (what plain jax.checkpoint
# leaves there), both backward kernels, and a kernel of another name
# recomputed beside them
_KERNEL = ('  %{name} = f32[8]{{0}} custom-call(%a), custom_call_target='
           '"tpu_custom_call", metadata={{op_name="jit(step)/while/body/'
           'closed_call/{stack}/pallas_call"}}\n')
_BACKWARD = "transpose(jvp(attn/core))/checkpoint/"
_CORES = {
    "flash_attention_fwd.1": "jvp(attn/core)/flash_attention_fwd",
    "flash_attention_fwd.2": _BACKWARD + "rematted_computation/"
                             "flash_attention_fwd",
    "flash_attention_bwd_dq.1": _BACKWARD + "flash_attention_bwd_dq",
    "flash_attention_bwd_dkv.1": _BACKWARD + "flash_attention_bwd_dkv",
    "ssd_scan_fwd.1": "transpose(jvp(mixer/mamba))/checkpoint/"
                      "rematted_computation/ssd/ssd_scan_fwd",
}


@pytest.mark.parametrize("left_out,expected", [
    ((), 1),                            # the core runs again
    (("flash_attention_fwd.2",), 0),    # its results were kept
    (tuple(_CORES), 0),                 # a step without kernels
])
def test_cores_recomputed_counts_the_flash_forwards_of_the_recompute_phase(
        left_out, expected):
    hlo = ("HloModule jit_step\n\nENTRY %main (a: f32[8]) -> f32[8] {\n"
           "  %a = f32[8]{0} parameter(0)\n"
           + "".join(_KERNEL.format(name=n, stack=s)
                     for n, s in _CORES.items() if n not in left_out)
           + "  ROOT %copy.1 = f32[8]{0} copy(%a)\n}\n")
    found = step_hlo(hlo)
    assert found["mosaic_custom_calls"] == len(_CORES) - len(left_out)
    assert trace_analysis.cores_recomputed(found) == expected


# three recurrent blocks' scan kernels as a step holds them: each forward in
# the forward pass, a second one in the recomputed forward (what plain
# jax.checkpoint leaves there), the backward by the scope its rule opens
_SCANS = {
    f"{call}_scan_{way}": stack.format(scope=scope, inner=inner, call=call)
    for call, scope, inner in (("kda", "mixer/kda", "scan"),
                               ("gdn", "mixer/gdn", "scan"),
                               ("ssd", "mixer/mamba", "ssd"))
    for way, stack in (
        ("fwd.1", "jvp({scope})/{inner}/{call}_scan_fwd"),
        ("fwd.2", "transpose(jvp({scope}))/checkpoint/rematted_computation/"
                  "{inner}/{call}_scan_fwd"),
        ("bwd.1", "transpose(jvp())/checkpoint/{scope}/{inner}/"
                  "{call}_scan_bwd"))}


@pytest.mark.parametrize("left_out,expected", [
    ((), 3),                                        # every scan runs again
    (("kda_scan_fwd.2",), 2),                       # the delta rule's kept
    (("kda_scan_fwd.2", "ssd_scan_fwd.2"), 1),      # a decay a head's is not
    (("kda_scan_fwd.2", "gdn_scan_fwd.2", "ssd_scan_fwd.2"), 0),  # all kept
    (tuple(_SCANS), 0),                             # a step without kernels
])
def test_scans_recomputed_counts_the_scan_forwards_of_the_recompute_phase(
        left_out, expected):
    """Over a hand-made map: forward, recompute, backward. A recomputed
    flash forward beside them is ``cores_recomputed``'s and not counted."""
    calls = {**_SCANS, "flash_attention_fwd.2": _CORES[
        "flash_attention_fwd.2"]}
    hlo = ("HloModule jit_step\n\nENTRY %main (a: f32[8]) -> f32[8] {\n"
           "  %a = f32[8]{0} parameter(0)\n"
           + "".join(_KERNEL.format(name=n, stack=s)
                     for n, s in calls.items() if n not in left_out)
           + "  ROOT %copy.1 = f32[8]{0} copy(%a)\n}\n")
    found = step_hlo(hlo)
    placed = found["map"]["instructions"]
    assert {placed[n][1] for n in calls if n not in left_out} <= {
        "forward", "recompute", "backward"}
    assert all(placed[n][1] == {"fwd.1": "forward", "fwd.2": "recompute",
                                "bwd.1": "backward"}[n.split("_")[-1]]
               for n in _SCANS if n not in left_out)
    assert trace_analysis.scans_recomputed(found) == expected
    assert trace_analysis.cores_recomputed(found) == 1


# four chips: a collective under its own name, XLA:TPU's three fusions of an
# asynchronous all-gather (the middle one rides a matmul), a reduce-scatter
# fused as an all-reduce and a slice, an asynchronous pair under its own names
FOUR_CHIPS = '''HloModule jit_step

%fused_computation.7 (p: bf16[8,4]) -> (bf16[8,4], bf16[8,8], u32[]) {
  %p = bf16[8,4]{1,0:T(8,128)(2,1)} parameter(0)
  %all-gather.3 = bf16[8,8]{1,0:T(8,128)(2,1)} all-gather(%p), replica_groups=[2,2]<=[4], dimensions={1}, metadata={op_name="jit(step)/while/body/closed_call/jvp(mlp)/bsh,hf->bsf/dot_general"}
  ROOT %custom-call.1 = (bf16[8,4], bf16[8,8], u32[]) custom-call(%all-gather.3), custom_call_target="AsyncCollectiveStart"
}

%async_collective_fusion.9 (p: bf16[8,4]) -> bf16[8,8] {
  %p.1 = bf16[8,4]{1,0} parameter(0)
  ROOT %all-gather.4 = bf16[8,8]{1,0} all-gather(%p.1), replica_groups=[2,2]<=[4], dimensions={1}
}

%fused_computation.8 (p: bf16[8,8]) -> bf16[8,8] {
  %p.2 = bf16[8,8]{1,0} parameter(0)
  %all-gather.5 = bf16[8,8]{1,0} all-gather(%p.2), replica_groups=[2,2]<=[4], dimensions={1}
  ROOT %custom-call.2 = bf16[8,8] custom-call(%all-gather.5), custom_call_target="AsyncCollectiveDone"
}

%all-reduce-scatter.2.clone (p: f32[8,8]) -> f32[4,8] {
  %p.3 = f32[8,8]{1,0} parameter(0)
  %all-reduce.6 = f32[8,8]{1,0} all-reduce(%p.3), replica_groups={{0,2},{1,3}}, to_apply=%add
  ROOT %dynamic-slice.1 = f32[4,8]{1,0} dynamic-slice(%all-reduce.6)
}

%body.1 (t: (bf16[8,4], f32[8,8])) -> (bf16[8,4], f32[8,8]) {
  %t = (bf16[8,4], f32[8,8]) parameter(0)
  %async-collective-start.1 = (bf16[8,4], bf16[8,8], u32[]) fusion(%t), kind=kCustom, calls=%fused_computation.7
  %fusion.12 = bf16[8,8]{1,0} fusion(%async-collective-start.1), kind=kOutput, calls=%async_collective_fusion.9, metadata={op_name="jit(step)/while/body/closed_call/jvp(mlp)/bsh,hf->bsf/dot_general"}
  %async-collective-done.1 = bf16[8,8]{1,0} fusion(%fusion.12), kind=kCustom, calls=%fused_computation.8, metadata={op_name="jit(step)/while/body/closed_call/jvp(mlp)/bsh,hf->bsf/dot_general"}
  %fusion.13 = f32[4,8]{1,0} fusion(%async-collective-done.1), kind=kCustom, calls=%all-reduce-scatter.2.clone, metadata={op_name="jit(step)/while/body/closed_call/transpose(jvp(attn/qkv_proj))/dot_general"}
  %all-to-all.2 = bf16[2,4,4]{2,1,0} all-to-all(%fusion.13), replica_groups=[2,2]<=[4], metadata={op_name="jit(step)/while/body/closed_call/jvp()/split"}
  %collective-permute-start.1 = (bf16[8,4], bf16[8,4]) collective-permute-start(%all-to-all.2), source_target_pairs={{0,1}}
  %collective-permute-done.1 = bf16[8,4] collective-permute-done(%collective-permute-start.1)
  ROOT %tuple = (bf16[8,4], f32[8,8]) tuple(%t)
}
'''


@pytest.mark.parametrize("name,expected", [
    ("all-to-all.2", (None, "forward", "all-to-all")),
    ("fusion.13", ("attn/qkv_proj", "backward", "reduce-scatter.fused")),
    ("fusion.12", ("mlp", "forward", "overlapped")),
    # the start half has no op_name: it takes what it fuses
    ("async-collective-start.1", ("mlp", "forward", "all-gather.start")),
    ("async-collective-done.1", ("mlp", "forward", "all-gather.done")),
    # (no op_name: of the pass their operand is of)
    ("collective-permute-start.1",
     (None, "forward", "collective-permute.start")),
    ("collective-permute-done.1",
     (None, "forward", "collective-permute.done")),
    ("tuple", (None, "other", None)),
])
def test_an_instruction_says_what_it_moves_between_chips(name, expected):
    assert step_hlo(FOUR_CHIPS)["map"]["instructions"][name] == expected


def test_the_counts_of_before_come_out_of_the_same_walk():
    """``hlo_counts``: an async triple once, a fused all-reduce-scatter as
    the reduce-scatter it is, a start / done pair once; and its old answer
    on the case ``tests/core/test_aot_hlo_report.py`` keeps."""
    assert hlo_counts(FOUR_CHIPS) == {
        "mosaic_custom_calls": 0,
        "collectives": {"all-to-all": 1, "all-gather": 1, "all-reduce": 0,
                        "reduce-scatter": 1, "collective-permute": 1}}
    assert FOUR_CHIPS.count(" all-gather(") == 3
    assert hlo_counts(ONE_CHIP) == {
        "mosaic_custom_calls": 1,
        "collectives": dict.fromkeys(trace_analysis.COLLECTIVE_OPS, 0)}
    assert hlo_counts("")["collectives"] == dict.fromkeys(
        trace_analysis.COLLECTIVE_OPS, 0)


def test_scope_instructions_is_a_view_by_the_rule_of_before():
    """An instruction by its OWN op_name, under any scopes a caller names
    (here two that are no part of the vocabulary's matching: ``attn`` is a
    prefix, and the fusion that only holds ``mlp`` inside is not listed)."""
    found = scope_instructions(ONE_CHIP, ("attn", "mlp", "norm"))
    assert found["scopes"] == {
        "attn": ["fusion.3", "flash_attention_fwd.1"], "mlp": [],
        "norm": ["add.9", "reduce.1"]}
    assert found["mosaic_calls"] == {"flash_attention_fwd.1"}
    assert set(found) == {"scopes", "instructions", "mosaic_calls"}


def test_every_named_scope_of_the_step_is_in_the_vocabulary():
    """Every ``jax.named_scope`` literal of the model code and of the step
    program (found as ``analysis/lint.py`` finds them for GAL004) is a name
    of ``trace_analysis.SCOPES`` or, where scopes nest
    (``mixer/mamba`` around ``ssd``), whole parts of one; and every name of
    the vocabulary is written somewhere."""
    files = sorted(glob.glob(os.path.join(PACKAGE, "models", "*.py"))) + [
        os.path.join(PACKAGE, "runtime", "trainer.py"),
        os.path.join(PACKAGE, "parallel", "spmd.py"),
        os.path.join(PACKAGE, "ops", "pallas", "ssd.py")]
    literals = set()
    for path in files:
        with open(path) as f:
            tree = ast.parse(f.read())
        consts = {t.id: n.value.value for n in ast.walk(tree)
                  if isinstance(n, ast.Assign)
                  and isinstance(n.value, ast.Constant)
                  and isinstance(n.value.value, str)
                  for t in n.targets if isinstance(t, ast.Name)}
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args and _callee(
                    node).rsplit(".", 1)[-1] == "named_scope"):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Name):       # a module-level constant
                literals.add(consts[arg.id])
            else:
                assert isinstance(arg, ast.Constant), ast.dump(arg)
                literals.add(arg.value)
    assert len(literals) > 20
    for lit in sorted(literals):
        assert any(f"/{lit}/" in f"/{s}/" for s in SCOPES), lit
    written = lambda s: any(
        s == lit or (s.startswith(lit + "/") and s[len(lit) + 1:] in literals)
        for lit in literals)
    assert [s for s in SCOPES if not written(s)] == []


def test_the_map_is_written_beside_a_trace(tmp_path):
    before = trace_analysis.step_scopes()
    try:
        trace_analysis.record_step_scopes({})
        assert trace_analysis.write_step_map(str(tmp_path)) is None
        assert not os.listdir(tmp_path)
        trace_analysis.record_step_scopes(step_hlo(FOUR_CHIPS))
        assert trace_analysis.write_step_map("") is None
        path = trace_analysis.write_step_map(str(tmp_path / "trace"))
        assert path == str(tmp_path / "trace" / trace_analysis.STEP_MAP_FILE)
        with open(path) as f:
            kept = json.load(f)
        assert kept["vocabulary"] == list(SCOPES)
        assert kept["phases"] == list(trace_analysis.PHASES)
        assert kept["instructions"]["fusion.13"] == [
            "attn/qkv_proj", "backward", "reduce-scatter.fused"]
        assert kept["inferred"] == ["async-collective-start.1"]
        assert kept["tails"]["all-to-all.2"] == "closed_call/jvp()/split"
        # a directory that cannot be made: nothing, and no exception
        blocked = tmp_path / "file"
        blocked.write_text("")
        assert trace_analysis.write_step_map(str(blocked / "x")) is None
    finally:
        trace_analysis.record_step_scopes(before)


def test_the_map_keeps_what_each_chip_was_handed(tmp_path):
    """``chips`` of ``step_map.json``: the last logged step's
    ``moe/chip_rows`` and ``moe/chip_passes`` by layer and chip, for the
    operator's by-chip table; empty where no layer ran inside an
    exchange."""
    from hetu_galvatron_tpu.observability.registry import (
        MetricsRegistry,
        get_registry,
        set_registry,
    )

    before, kept = get_registry(), trace_analysis.step_scopes()
    reg = set_registry(MetricsRegistry())
    try:
        trace_analysis.record_step_scopes(step_hlo(FOUR_CHIPS))
        assert trace_analysis.chip_counts() == {}
        with open(trace_analysis.write_step_map(str(tmp_path / "a"))) as f:
            assert json.load(f)["chips"] == {}
        for layer, rows in (("layer1", (7.0, 9.0)), ("layer0", (8.0, 8.0))):
            for chip, n in enumerate(rows):
                reg.gauge("moe/chip_rows", layer=layer,
                          chip=str(chip)).set(n)
                reg.gauge("moe/chip_passes", layer=layer,
                          chip=str(chip)).set(float(n > 8))
        for device, chip in ((0, 0), (1, 1)):
            reg.gauge("ep/chip_of_device", device=str(device)).set(chip)
        with open(trace_analysis.write_step_map(str(tmp_path / "b"))) as f:
            assert json.load(f)["chips"] == {
                "rows": {"layer0": [8.0, 8.0], "layer1": [7.0, 9.0]},
                "passes": {"layer0": [0.0, 0.0], "layer1": [0.0, 1.0]},
                "devices": {"0": 0, "1": 1}}
    finally:
        set_registry(before)
        trace_analysis.record_step_scopes(kept)


def _reduced(rows, steps):
    ms = 1e6
    leaves = [(n, s * ms, e * ms) for n, s, e in rows]
    return xplane.Reduced(0, [(a * ms, b * ms) for a, b in steps],
                          (steps[0][0] * ms, steps[-1][0] * ms), leaves,
                          [(n, e - s) for n, s, e in leaves], [])


def test_the_tool_lays_a_trace_over_the_map(capsys):
    """Two whole periods; in each the same six leaves, a gap of half a
    millisecond between two of them and a name the map does not hold."""
    step_map = json.loads(json.dumps(step_hlo(FOUR_CHIPS)["map"]))
    one = [("async-collective-start.1", 0.0, 0.1), ("fusion.12", 0.1, 1.1),
           ("async-collective-done.1", 1.1, 1.4), ("fusion.13", 1.9, 2.9),
           ("all-to-all.2", 2.9, 3.0), ("stranger.1", 3.0, 3.1)]
    rows = [(n, s + at, e + at) for at in (0.0, 10.0) for n, s, e in one]
    t = trace_by_scope.join(
        _reduced(rows, [(0.0, 5.0), (10.0, 15.0), (20.0, 25.0)]), step_map)
    assert t["periods"] == 2
    assert t["leaf_ms_a_step"] == pytest.approx(2.6)
    assert t["busy_ms_a_step"] == pytest.approx(2.6)
    by_scope = {r["scope"]: r for r in t["by_scope_and_phase"]}
    assert by_scope["mlp"]["ms"] == pytest.approx(1.4)
    assert by_scope["mlp"]["forward"] == pytest.approx(1.4)
    assert by_scope["attn/qkv_proj"]["backward"] == pytest.approx(1.0)
    assert by_scope[trace_by_scope.NO_SCOPE]["ms"] == pytest.approx(0.1)
    assert t["by_phase"] == pytest.approx({
        "forward": 1.5, "recompute": 0.0, "backward": 1.0, "update": 0.0,
        "other": 0.0})
    assert {(r["class"], r["phase"]): (r["ms"], r["events_a_step"])
            for r in t["collectives"]} == pytest.approx({
                ("all-gather.start", "forward"): (0.1, 1.0),
                ("all-gather.done", "forward"): (0.3, 1.0),
                ("overlapped", "forward"): (1.0, 1.0),
                ("reduce-scatter.fused", "backward"): (1.0, 1.0),
                ("all-to-all", "forward"): (0.1, 1.0)})
    (unnamed,) = t["unnamed"]
    assert unnamed["instruction"] == "all-to-all.2"
    assert unnamed["op_name_tail"] == "closed_call/jvp()/split"
    (gap,) = t["gaps"]      # the gap between two steps is not inside one
    assert (gap["after"], gap["before"], gap["times"]) == (
        "async-collective-done.1", "fusion.13", 2)
    assert gap["mean_ms"] == pytest.approx(0.5)
    assert gap["after_is"] == "mlp/forward/all-gather.done"
    assert gap["before_is"] == "attn/qkv_proj/backward/reduce-scatter.fused"
    assert t["not_in_the_map"] == [["stranger.1", pytest.approx(0.1)]]
    trace_by_scope.print_tables(t)
    out = capsys.readouterr().out
    assert "0.500 ms x 2  collective after async-collective-done.1" in out
    assert "NO instruction of the map" in out


def test_the_tool_says_what_is_missing(tmp_path, capsys):
    assert trace_by_scope.main([str(tmp_path)]) == 2
    assert "no step_map.json" in capsys.readouterr().err
    (tmp_path / trace_analysis.STEP_MAP_FILE).write_text("{}")
    assert trace_by_scope.main([str(tmp_path)]) == 2
    assert "no .xplane.pb" in capsys.readouterr().err


def test_relayouts_are_the_passes_left_outside_fusions():
    """A reshape, a copy and a transpose of the entry and of a loop's body
    are counted by their result bytes, each once; a bitcast, the copy
    inside a fusion and a ``copy-start`` prefetch are not."""
    hlo = (
        "HloModule jit_step\n\n"
        "%fused_computation.1 (p: f32[8,16]) -> f32[16,8] {\n"
        "  %p = f32[8,16]{1,0} parameter(0)\n"
        "  ROOT %copy.9 = f32[16,8]{0,1} copy(%p)\n}\n\n"
        "%body (q: f32[4,4096,512]) -> f32[4,4096,512] {\n"
        "  %q = f32[4,4096,512]{2,1,0} parameter(0)\n"
        "  ROOT %transpose.3 = bf16[4096,4,512]{2,1,0} transpose(%q), "
        "dimensions={1,0,2}\n}\n\n"
        "ENTRY %main (a: f32[1,4096,512]) -> f32[8] {\n"
        "  %a = f32[1,4096,512]{2,1,0:T(8,128)} parameter(0)\n"
        "  %reshape.1455 = f32[2097152]{0:T(1024)} reshape(%a), metadata="
        '{op_name="jit(step)/transpose(jvp(head))/jit(take_along_axis)/'
        'scatter-add"}\n'
        "  %bitcast.1 = f32[4096,512]{1,0} bitcast(%a)\n"
        "  %copy.2 = (pred[16]{0}, s8[3,5]{1,0}) copy(%a)\n"
        "  %copy-start.1 = (f32[64]{0}, f32[64]{0}, u32[]) copy-start(%a)\n"
        "  %fusion.1 = f32[16,8]{0,1} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.1\n"
        "  ROOT %w = f32[4,4096,512]{2,1,0} while(%a), body=%body\n}\n")
    moved = step_hlo(hlo)["relayouts"]
    assert moved["count"] == 3
    assert moved["bytes"] == 4 * 2097152 + (16 + 15) + 2 * 4096 * 4 * 512
    assert moved["largest"] == {
        "bytes": 2 * 4096 * 4 * 512, "opcode": "transpose",
        "shape": "bf16[4096,4,512]{2,1,0}", "op_name": "(none)"}
    assert trace_analysis.result_bytes("f32[1,4096,50304]{2,1,0}") == \
        824180736


# ---------------------------------------------------------------------------
# the step's data, followed (PR 73): transfers, calls, owners, relayouts
# ---------------------------------------------------------------------------

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
_FUSION = ('  %{name} = f32[8]{{0}} fusion({operands}), kind=kLoop, '
           'calls=%fused_computation.1, metadata={{op_name="jit(step)/'
           '{stack}/dot_general"}}\n')


def _fusion(name, operands, stack):
    return _FUSION.format(name=name, operands=operands, stack=stack)


# a prefetch by copy into S(1), one in two slices under a ConcatBitcast, an
# unnamed copy between two scoped fusions, copies whose users differ, one
# nobody uses, one that goes nowhere, and a loop over the layers whose
# weight is prefetched before it and, in each trip, for the next
FLOW = (
    "HloModule jit_step\n\n"
    "%fused_computation.1 (p: f32[8]) -> f32[8] {\n"
    "  %p = f32[8]{0} parameter(0)\n"
    "  ROOT %neg.1 = f32[8]{0} negate(%p)\n}\n\n"
    "%body.1 (t: (f32[8], f32[8])) -> (f32[8], f32[8]) {\n"
    "  %t = (f32[8]{0}, f32[8]{0:S(1)}) parameter(0)\n"
    "  %gte.w = f32[8]{0:S(1)} get-tuple-element(%t), index=1\n"
    "  %gte.x = f32[8]{0} get-tuple-element(%t), index=0\n"
    + _fusion("fusion.20", "%gte.x, %gte.w", "while/body/jvp(mlp)") +
    "  %copy-start.9 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) "
    "copy-start(%gte.x)\n"
    "  %copy-done.9 = f32[8]{0:S(1)} copy-done(%copy-start.9)\n"
    "  ROOT %tuple.9 = (f32[8]{0}, f32[8]{0:S(1)}) tuple(%fusion.20, "
    "%copy-done.9)\n}\n\n"
    "ENTRY %main (a: f32[16,8], b: f32[8]) -> f32[8] {\n"
    "  %a = f32[16,8]{1,0} parameter(0)\n"
    "  %b = f32[8]{0} parameter(1)\n"
    "  %copy-start.1 = (f32[8]{0:T(8)S(1)}, f32[8]{0:T(8)}, u32[]{:S(2)}) "
    "copy-start(%b)\n"
    "  %slice-start.2 = ((f32[16,8]{1,0}), f32[8,8]{1,0:T(8,128)S(1)}, "
    "s32[]{:S(2)}) slice-start(%a), slice={[0:8], [0:8]}\n"
    "  %slice-start.3 = ((f32[16,8]{1,0}), f32[8,8]{1,0:T(8,128)S(1)}, "
    "s32[]{:S(2)}) slice-start(%a), slice={[8:16], [0:8]}\n"
    + _fusion("fusion.1", "%b", "jvp(attn/qkv_proj)") +
    "  %copy.4 = f32[8]{0} copy(%fusion.1)\n"
    + _fusion("fusion.2", "%copy.4", "jvp(attn/out_proj)") +
    "  %copy.5 = f32[8]{0} copy(%fusion.2)\n"
    + _fusion("fusion.3", "%copy.5", "jvp(norm)")
    + _fusion("fusion.4", "%copy.5", "jvp(mlp)") +
    "  %copy.6 = f32[8]{0} copy(%fusion.2)\n"
    "  %bitcast.6 = f32[8]{0} bitcast(%copy.6)\n"
    + _fusion("fusion.5", "%bitcast.6", "jvp(head)")
    + _fusion("fusion.6", "%copy.6", "jvp(mlp)") +
    "  %slice-done.2 = f32[8,8]{1,0:T(8,128)S(1)} slice-done("
    "%slice-start.2)\n"
    "  %slice-done.3 = f32[8,8]{1,0:T(8,128)S(1)} slice-done("
    "%slice-start.3)\n"
    "  %copy-done.1 = f32[8]{0:T(8)S(1)} copy-done(%copy-start.1)\n"
    "  %custom-call.7 = f32[16,8]{1,0:T(8,128)S(1)} custom-call("
    "%slice-done.2, %slice-done.3), custom_call_target=\"ConcatBitcast\"\n"
    + _fusion("fusion.7", "%custom-call.7, %copy-done.1",
              "transpose(jvp(mlp))") +
    "  %copy.8 = f32[16,8]{0,1} copy(%a)\n"
    "  %copy.12 = f32[8]{0} copy(%fusion.7)\n"
    "  %copy-start.11 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) "
    "copy-start(%fusion.6)\n"
    "  %copy-done.11 = f32[8]{0:S(1)} copy-done(%copy-start.11)\n"
    "  %tuple.10 = (f32[8]{0}, f32[8]{0:S(1)}) tuple(%fusion.7, "
    "%copy-done.11)\n"
    "  %while.1 = (f32[8]{0}, f32[8]{0:S(1)}) while(%tuple.10), "
    "condition=%cond.1, body=%body.1\n"
    "  %gte.9 = f32[8]{0} get-tuple-element(%while.1), index=0\n"
    "  %out = (f32[8]{0}, f32[16,8]{0,1}) tuple(%gte.9, %copy.8)\n"
    + _fusion("fusion.8", "%gte.9", "optimizer/update").replace(
        "  %fusion.8", "  ROOT %fusion.8") + "}\n")


@pytest.mark.parametrize("start,expected", [
    # a copy into S(1): the bytes and the space of the destination, the
    # parameter it reads, the fusion that reads the result
    ("copy-start.1", {
        "done": "copy-done.1", "kind": "prefetch", "bytes": 32,
        "space": "S(1)", "from": ("b", None, "other"),
        "feeds": ("fusion.7", "mlp", "backward")}),
    # a slice: the destination is the tuple's second part; what it feeds is
    # behind the ConcatBitcast
    ("slice-start.2", {
        "done": "slice-done.2", "kind": "prefetch", "bytes": 256,
        "space": "S(1)", "from": ("a", None, "other"),
        "feeds": ("fusion.7", "mlp", "backward")}),
    # before a loop, into its tuple: the body's fusion reads that place
    ("copy-start.11", {
        "done": "copy-done.11", "kind": "prefetch", "bytes": 32,
        "space": "S(1)", "from": ("fusion.6", "mlp", "forward"),
        "feeds": ("fusion.20", "mlp", "forward")}),
    # in a trip, for the next one: through the body's root and parameter
    ("copy-start.9", {
        "done": "copy-done.9", "kind": "prefetch", "bytes": 32,
        "space": "S(1)", "from": ("t", None, "other"),
        "feeds": ("fusion.20", "mlp", "forward")}),
])
def test_a_transfer_says_what_it_moves_and_what_it_feeds(start, expected):
    found = step_hlo(FLOW)
    assert found["map"]["transfers"][start] == expected
    assert list(found["map"]["transfers"]) == [
        "copy-start.9", "copy-start.1", "slice-start.2", "slice-start.3",
        "copy-start.11"]
    assert found["flow"] == {
        "prefetches": 5, "prefetch_bytes": 3 * 32 + 2 * 256,
        "unowned_instructions": len(found["map"]["tails"]) - len(
            found["map"]["owners"])}


@pytest.mark.parametrize("name,expected", [
    # between two scoped fusions: the one that uses it, not the one it reads
    ("copy.4", ("attn/out_proj", "forward", "user", 1)),
    # two users as near: the earlier in program order
    ("copy.5", ("norm", "forward", "user", 1)),
    # a nearer user wins over an earlier one behind a bitcast
    ("copy.6", ("mlp", "forward", "user", 1)),
    ("bitcast.6", ("head", "forward", "user", 1)),
    # no user: what made its operand
    ("copy.12", ("mlp", "backward", "operand", 1)),
    # a ConcatBitcast and the halves behind it are the reader's
    ("custom-call.7", ("mlp", "backward", "user", 1)),
    ("slice-start.3", ("mlp", "backward", "user", 3)),
    # through a loop by the place alone: the weight's, not the carry's
    ("copy-done.11", ("mlp", "forward", "user", 2)),
    # a copy of a parameter that only leaves the program: nobody's
    ("copy.8", None),
    # after a loop: what the body's root holds at the place
    ("gte.9", ("optimizer/update", "update", "user", 1)),
    ("out", ("mlp", "forward", "operand", 2)),
])
def test_an_unnamed_instruction_gets_the_scope_that_consumes_it(name,
                                                                expected):
    kept = step_hlo(FLOW)["map"]
    assert kept["instructions"][name][0] is None
    assert kept["owners"].get(name) == expected
    assert set(kept["owners"]) <= set(kept["tails"])


def test_a_custom_call_names_its_target_and_the_transfers_behind_it():
    kept = step_hlo(FLOW)["map"]
    assert kept["calls"] == {"custom-call.7": {
        "target": "ConcatBitcast",
        "transfers": ["slice-start.2", "slice-start.3"]}}
    assert kept["relayouts"] == ["copy.4", "copy.5", "copy.6", "copy.8",
                                 "copy.12"]
    assert len(kept["relayouts"]) == step_hlo(FLOW)["relayouts"]["count"]
    # four chips: the halves of XLA:TPU's three fusions are one transfer,
    # its kind the collective; an asynchronous pair under its own names
    # too; a Mosaic call is a custom call like another
    four = step_hlo(FOUR_CHIPS)["map"]
    assert {n: (t["done"], t["kind"]) for n, t in
            four["transfers"].items()} == {
        "async-collective-start.1": ("async-collective-done.1",
                                     "all-gather"),
        "collective-permute-start.1": ("collective-permute-done.1",
                                       "collective-permute")}
    # (fusion.12 rides on the transfer; fusion.13 reads what it brought)
    assert four["transfers"]["async-collective-start.1"]["feeds"] == (
        "fusion.13", "attn/qkv_proj", "backward")
    assert step_hlo(ONE_CHIP)["map"]["calls"] == {
        "flash_attention_fwd.1": {"target": "tpu_custom_call",
                                  "transfers": []}}


def _as_an_attached_chip_prints_it(hlo):
    """The slices' asynchronous pairs in the generic form: ``async-start``
    / ``async-done`` around a computation that holds the ``slice``. (An
    attached chip's ``compiled.as_text()`` prints them so where a described
    one's prints ``slice-start`` / ``slice-done``: the first chip run of
    PR 73 read ``gpt2xl_c1_b4``'s 1,088 slices as kind ``async`` and its map
    held 2,176 instructions more than the chipless compile's.)"""
    import re

    wrapped = []

    def start(m):
        n = len(wrapped)
        wrapped.append(
            f"%async_computation.{n} (p: f32[16,8]) -> f32[8,8] {{\n"
            f"  %param_0.{n} = f32[16,8]{{1,0}} parameter(0)\n"
            f"  ROOT %slice.{n} = f32[8,8]{{1,0}} slice(%param_0.{n}), "
            f"slice={m.group(2)}\n}}\n\n")
        return f" async-start({m.group(1)}), calls=%async_computation.{n}"
    hlo = re.sub(r" slice-start\(([^)]*)\), slice=(\{[^}]*\})", start, hlo)
    head, rest = hlo.replace(" slice-done(", " async-done(").split(
        "\n\n", 1)
    return head + "\n\n" + "".join(wrapped) + rest


def test_a_slice_in_the_generic_asynchronous_form_is_a_prefetch_too():
    sugared, generic = step_hlo(FLOW), step_hlo(
        _as_an_attached_chip_prints_it(FLOW))
    assert generic["map"]["transfers"] == sugared["map"]["transfers"]
    assert generic["map"]["calls"] == sugared["map"]["calls"]
    assert generic["flow"] == sugared["flow"]
    # what the start wraps is an instruction of the map (as before) and is
    # the start's
    more = set(generic["map"]["instructions"]) - set(
        sugared["map"]["instructions"])
    assert more == {"param_0.0", "slice.0", "param_0.1", "slice.1"}
    assert all(generic["map"]["owners"][n] == generic["map"]["owners"][
        "slice-start.2"] for n in more)
    assert {n: c for n, c in generic["map"]["instructions"].items()
            if n not in more} == sugared["map"]["instructions"]


def test_the_parser_reads_what_xla_tpu_prints():
    """Thirty-four lines of ``gpt2xl_c1_b4``'s optimized step as
    ``tools/aot_hlo_report.py``'s chipless compile printed them (PR 73):
    layer 0's fused projection weight prefetched in four slices of 7.68 MB
    into ``S(1)``, the ``ConcatBitcast`` of them, the fusion that reads it,
    the three small copies beside it, copies OUT of ``S(1)`` and the
    ``copy-start`` after the fusion."""
    with open(os.path.join(
            FIXTURES, "gpt2xl_c1_b4.concat_bitcast.hlo.txt")) as f:
        found = step_hlo(f.read())
    kept = found["map"]
    for n in range(648, 652):
        assert kept["transfers"][f"slice-start.{n}"] == {
            "done": f"slice-done.{n}", "kind": "prefetch",
            "bytes": 400 * 4800 * 4, "space": "S(1)", "from": None,
            "feeds": ("fusion.65", "attn/qkv_proj", "forward")}
        assert kept["owners"][f"slice-done.{n}"] == (
            "attn/qkv_proj", "forward", "user", 2)
    assert kept["calls"] == {"custom-call.164": {
        "target": "ConcatBitcast",
        "transfers": [f"slice-start.{n}" for n in range(648, 652)]}}
    # out of S(1): the destination's layout names no space
    assert kept["transfers"]["copy-start.192"] == {
        "done": "copy-done.192", "kind": "prefetch", "bytes": 4 * 1024 * 4,
        "space": None,
        "from": ("broadcast_multiply_fusion.32", "norm", "forward"),
        "feeds": None}
    assert kept["owners"]["copy-done.192"] == (
        "norm", "forward", "operand", 2)
    # (its other half lies outside the excerpt)
    assert kept["transfers"]["copy-start.1005"]["done"] is None
    assert kept["relayouts"] == ["copy.742"]
    assert kept["instructions"]["copy.742"] == ("embed", "forward", None)
    assert found["flow"]["prefetches"] == 12
    # the same thirty-four lines as the ATTACHED chip's compile printed them
    # (my chip run, PR 73, seed 2147483869: ``compiled.as_text()`` in the
    # trainer's process): the slices are ``async-start(...), calls=
    # %async_computation.648`` and ``async-done(...)``, nothing else differs
    with open(os.path.join(
            FIXTURES, "gpt2xl_c1_b4.chip.concat_bitcast.hlo.txt")) as f:
        chip = step_hlo(f.read())
    assert "async-start(%params__layers___0___attn____wqkv__.1), calls=" \
        in open(f.name).read()
    for key in ("transfers", "calls", "relayouts"):
        assert chip["map"][key] == kept[key]
    assert chip["flow"] == found["flow"]
    assert chip["map"]["owners"]["slice.1299"] == chip["map"]["owners"][
        "slice-start.648"] == ("attn/qkv_proj", "forward", "user", 3)


@pytest.mark.parametrize("text", [
    "ONE_CHIP", "NAMELESS", "EXPERT_KERNELS", "FOUR_CHIPS", "FLOW",
    "gpt2xl_c1_b4.concat_bitcast.hlo.txt",
    "gpt2xl_c1_b4.chip.concat_bitcast.hlo.txt"])
def test_the_triples_are_what_they_were_before_the_data_was_followed(text):
    """``instructions``, ``inferred`` and ``tails`` as the parent commit's
    walk (PR 72, ``a1b7eb5``) gave them for the same texts, kept in
    ``fixtures/step_map_triples.pr72.json``: every metric of
    ``step_map.py`` reads what it read."""
    with open(os.path.join(FIXTURES, "step_map_triples.pr72.json")) as f:
        before = json.load(f)[text]
    if text in globals():
        hlo = globals()[text]
    else:
        with open(os.path.join(FIXTURES, text)) as f:
            hlo = f.read()
    kept = json.loads(json.dumps(step_hlo(hlo)["map"]))
    assert {k: kept[k] for k in before} == before

"""tools/aot_hlo_report.py: the optimized HLO of a compiled step read into
cycles by stem and ``op_name`` and collectives by shape, groups and
``op_name`` — on a tiny step compiled for two CPU devices, and on a few
lines in the form XLA:TPU prints (estimated cycles, fused collectives)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hetu_galvatron_tpu.observability.trace_analysis import hlo_counts
from tools.aot_hlo_report import parse_hlo, print_report, report


@pytest.fixture(scope="module")
def tiny_hlo(cpu_devices):
    """x [8, 16] @ w [16, 4] with the contraction sharded over two devices:
    GSPMD reduces the partial products with one all-reduce."""
    mesh = Mesh(cpu_devices[:2], ("t",))

    def step(x, w):
        with jax.named_scope("proj"):
            return jnp.einsum("bh,hf->bf", x, w)

    fn = jax.jit(step,
                 in_shardings=(NamedSharding(mesh, P(None, "t")),
                               NamedSharding(mesh, P("t", None))),
                 out_shardings=NamedSharding(mesh, P()))
    return fn.lower(jnp.zeros((8, 16)), jnp.zeros((16, 4))).compile().as_text()


def test_compiled_step_collectives(tiny_hlo):
    rep = report(tiny_hlo)
    rows = [r for c in rep["computations"] for r in c["collectives"]]
    assert [(r["op"], r["count"]) for r in rows] == [("all-reduce", 1)]
    (row,) = rows
    assert row["shape"] == "f32[8,4]"
    assert row["replica_groups"] in ("{{0,1}}", "[1,2]<=[2]")
    assert row["op_name"].endswith("proj/bh,hf->bf/dot_general")
    counts = hlo_counts(tiny_hlo)
    assert counts["collectives"]["all-reduce"] == 1
    assert sum(counts["collectives"].values()) == 1
    assert counts["mosaic_custom_calls"] == 0


def test_parse_names_every_instruction_of_the_entry(tiny_hlo):
    comps = dict(parse_hlo(tiny_hlo))
    (entry,) = [c for c in comps if c.startswith("main")]
    opcodes = [i["opcode"] for i in comps[entry]]
    assert opcodes.count("parameter") == 2
    assert "all-reduce" in opcodes or "all-reduce-start" in opcodes
    assert all(i["stem"] and not i["stem"][-1].isdigit()
               for i in comps[entry])


TPU_STYLE = '''HloModule jit_step

%fused_computation.7 (p: bf16[8,4]) -> (bf16[8,4], bf16[8,8], u32[]) {
  %p = bf16[8,4]{1,0:T(8,128)(2,1)} parameter(0)
  %all-gather.3 = bf16[8,8]{1,0:T(8,128)(2,1)} all-gather(%p), replica_groups=[2,2]<=[4], dimensions={1}, metadata={op_name="jit(step)/while/body/checkpoint/bsh,hf->bsf/dot_general"}
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
  %start = (bf16[8,4], bf16[8,8], u32[]) fusion(%t), kind=kCustom, calls=%fused_computation.7
  %fusion.12 = bf16[8,8]{1,0} fusion(%start), kind=kOutput, calls=%async_collective_fusion.9, metadata={op_name="jit(step)/while/body/checkpoint/bsh,hf->bsf/dot_general"}, backend_config={"estimated_cycles":"1200"}
  %done = bf16[8,8]{1,0} fusion(%fusion.12), kind=kCustom, calls=%fused_computation.8
  %fusion.13 = f32[4,8]{1,0} fusion(%done), kind=kCustom, calls=%all-reduce-scatter.2.clone, metadata={op_name="jit(step)/while/body/transpose(jvp(bsh,hf->bsf))/dot_general"}
  %copy.4 = f32[8,8]{0,1} copy(%done), metadata={op_name="jit(step)/while/body/checkpoint/reshape"}, backend_config={"estimated_cycles":"300"}
  %all-to-all.2 = bf16[2,4,4]{2,1,0} all-to-all(%copy.4), replica_groups=[2,2]<=[4], metadata={op_name="jit(step)/while/body/jvp()/split"}
  ROOT %tuple = (bf16[8,4], f32[8,8]) tuple(%t)
}
'''


def test_fused_collectives_count_once_in_the_calling_computation():
    (body,) = report(TPU_STYLE)["computations"]
    assert body["computation"] == "body.1"
    assert body["collective_counts"] == {
        "all-gather": 1, "all-reduce@all-reduce-scatter": 1, "all-to-all": 1}
    by_op = {r["op"]: r for r in body["collectives"]}
    assert by_op["all-gather"]["shape"] == "bf16[8,8]"
    assert by_op["all-gather"]["replica_groups"] == "[2,2]<=[4]"
    # the fused all-reduce has no op_name of its own: the fusion's is used
    assert by_op["all-reduce@all-reduce-scatter"]["op_name"].endswith(
        "transpose(jvp(bsh,hf->bsf))/dot_general")
    assert by_op["all-reduce@all-reduce-scatter"]["replica_groups"] \
        == "{{0,2},{1,3}}"
    assert by_op["all-to-all"]["op_name"] == "body/jvp()/split"
    # the step report's totals (trace_analysis.hlo_counts) count as the
    # report does: the async triple once, the fused reduce-scatter as one
    assert hlo_counts(TPU_STYLE)["collectives"] == {
        "all-to-all": 1, "all-gather": 1, "all-reduce": 0,
        "reduce-scatter": 1, "collective-permute": 0}
    assert TPU_STYLE.count(" all-gather(") == 3


def test_cycles_by_stem_and_op_name(capsys):
    rep = report(TPU_STYLE)
    (body,) = rep["computations"]
    assert body["estimated_cycles"] == 1500
    assert dict(body["cycles_by_stem"]) == {"fusion": 1200, "copy": 300}
    assert dict(body["cycles_by_op_name"]) == {
        "checkpoint/bsh,hf->bsf/dot_general": 1200,
        "body/checkpoint/reshape": 300}
    print_report(rep)
    text = capsys.readouterr().out
    assert "1 x all-to-all bf16[2,4,4] [2,2]<=[4]  <- body/jvp()/split" in text
    assert "== body.1: 8 instructions" in text

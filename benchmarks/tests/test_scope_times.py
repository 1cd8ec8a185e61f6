"""``tools/scope_times.py``: an operation's scope and pass from its
``op_name``, and the sums by scope."""

from benchmarks.tools.scope_times import (
    by_scope,
    op_names,
    program_scopes,
    scope_of,
)

TEXT = """
  %fusion.7 = bf16[8192,8512]{1,0} fusion(%p.1, %p.2), kind=kOutput, calls=%fc.7, metadata={op_name="jit(multi_step)/while/body/closed_call/jvp(ssm.in_proj)/dot_general" stack_frame_id=3}
  ROOT %multiply_reduce_fusion.12 = f32[2,64,16,256]{3,2,1,0} fusion(%p.3), kind=kLoop, calls=%fc.12, metadata={op_name="jit(multi_step)/while/body/closed_call/transpose(jvp(ssm.scan))/ssm.scan.intra/mul"}
  %divide_subtract_fusion.3 = f32[2048,8192]{1,0} fusion(%p.4), kind=kOutput, calls=%fc.3, metadata={op_name="jit(multi_step)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/mlp/dot_general"}
  %copy.5 = f32[16]{0} copy(%p.5)
"""


def test_the_scopes_are_read_from_the_packages_source():
    scopes = program_scopes()
    assert {"ssm.scan", "ssm.scan.intra", "gqa.attention", "moe.experts",
            "mlp", "lm_head"} <= set(scopes)
    # longest first, so that ssm.scan.intra is not read as ssm.scan
    assert scopes.index("ssm.scan.intra") < scopes.index("ssm.scan")
    assert len(set(scopes)) == len(scopes)


def test_the_innermost_scope_and_the_pass_are_read_from_the_op_name():
    assert scope_of("a/jvp(mlp)/dot_general") == ("mlp", "fwd")
    assert scope_of("a/jvp(ssm.scan)/ssm.scan.intra/sub") == (
        "ssm.scan.intra", "fwd")
    assert scope_of("a/transpose(jvp(ssm.scan))/mul") == ("ssm.scan", "bwd")
    assert scope_of("a/transpose(jvp(lm_head))/while/body/dot_general") == (
        "lm_head", "bwd")
    # a name that merely holds a scope's letters is no scope
    assert scope_of("jit(multi_step)/while/body/mlp_like/add") == (
        "other", "fwd")
    assert scope_of("") == ("other", "fwd")


def test_times_add_up_by_scope_pass_and_stem():
    names = op_names(TEXT)
    assert set(names) == {"%fusion.7", "%multiply_reduce_fusion.12",
                          "%divide_subtract_fusion.3"}
    events = [
        ("%fusion.7 = bf16[8192,8512]{1,0} fusion(%p.1, %p.2), kind=kOutput",
         4e6, "fusion:kOutput"),
        ("%fusion.7 = bf16[8192,8512]{1,0} fusion(%p.1, %p.2), kind=kOutput",
         2e6, "fusion:kOutput"),
        ("%multiply_reduce_fusion.12 = f32[2,64,16,256]{3,2,1,0} fusion(%p.3)"
         ", kind=kLoop", 1e6, "fusion:kLoop"),
        ("%copy.5 = f32[16]{0} copy(%p.5)", 1e6, "copy"),
    ]
    scopes, stems, ops = by_scope(events, names, steps=2)
    assert dict(scopes["ssm.in_proj"]) == {"fwd": 3.0}
    assert dict(scopes["ssm.scan.intra"]) == {"bwd": 0.5}
    assert dict(scopes["other"]) == {"fwd": 0.5}
    assert dict(stems["ssm.in_proj"]) == {"fusion [fusion:kOutput]": 3.0}
    assert ops["%fusion.7 [fusion:kOutput] ssm.in_proj/fwd"] == 3.0

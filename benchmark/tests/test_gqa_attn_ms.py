"""``gqa_attn_ms`` on synthetic events: the named kernel's time inside the
decode program a decode token-step; nothing where the kernel has no name."""
import pytest

from benchmark import trace as T
from benchmark.layer_metrics.gqa_attn_ms import read

MODS = [("jit_step(1)", 0.0, 2.0), ("jit_chunk(2)", 2.0, 2.0),
        ("jit_step(1)", 4.0, 2.0)]
NAMED = ("%gqa_decode_paged.11 = (bf16[16,32,128]{2,1,0}, f32[16,32,128]"
         "{2,1,0}) custom-call(a, b)")
UNNAMED = ("%closed_call.11 = (bf16[16,32,128]{2,1,0}, f32[16,32,128]"
           "{2,1,0}) custom-call(a, b)")
OTHER = "%gqa_prefill_paged.12 = bf16[8,1024,128]{2,1,0} custom-call(a)"


def run_of(kernel):
    ops = [(kernel, 0.5, 0.25), ("%fusion.1 = bf16[4]{0} fusion(y)", 1.0, 0.5),
           (OTHER, 2.5, 0.25), (kernel, 4.5, 0.5)]
    return {"trace": T.Trace({0: ops}, {0: MODS}, [], 0.0, 6.0),
            "counters_trace": {"decode_steps": 8}}


def test_named_kernel_inside_the_decode_program_a_token_step():
    assert read(run_of(NAMED)) == pytest.approx(0.75 * 1e3 / 8)


def test_a_program_without_the_name_reports_nothing():
    assert read(run_of(UNNAMED)) is None
    assert read({"trace": None, "counters_trace": {"decode_steps": 8}}) is None
    assert read(dict(run_of(NAMED), counters_trace={})) is None

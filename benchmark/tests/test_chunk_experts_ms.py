"""``chunk_experts_ms`` on synthetic events: both grouped GEMMs' time inside
the chunk program a chunk execution; nothing where the chunk runs neither."""
import pytest

from benchmark import trace as T
from benchmark.layer_metrics.chunk_experts_ms import read

MODS = [("jit_step(1)", 0.0, 2.0), ("jit_chunk(2)", 2.0, 2.0),
        ("jit_step(1)", 4.0, 2.0), ("jit_chunk(2)", 6.0, 2.0)]
GATED = "%grouped_gemm_gated.3 = bf16[16384,1536]{1,0} custom-call(a, b, c)"
DOWN = "%grouped_gemm.4 = bf16[16384,2048]{1,0} custom-call(a, b)"
WALK = "%gqa_prefill_paged.12 = bf16[8,1024,128]{2,1,0} custom-call(a)"


def run_of(chunk_ops):
    ops = sorted([(GATED, 0.5, 0.25), (DOWN, 1.0, 0.125)] + chunk_ops,
                 key=lambda e: e[1])
    return {"trace": T.Trace({0: ops}, {0: MODS}, [], 0.0, 8.0)}


def test_both_kernels_inside_the_chunk_program_a_chunk():
    run = run_of([(GATED, 2.5, 0.5), (DOWN, 3.25, 0.25), (WALK, 3.5, 0.25),
                  (GATED, 6.5, 0.5)])
    assert read(run) == pytest.approx(1.25 * 1e3 / 2)


def test_a_chunk_without_the_kernels_reports_nothing():
    assert read(run_of([(WALK, 3.5, 0.25)])) is None
    assert read({"trace": None}) is None

"""``chunk_kv_write_ms`` on synthetic events: what yields a pool's 2-D view
inside the chunk program, a row scatter's fusions and a page-wise write's
``while`` alike, by calls x calls a whole chunk; nothing without a view."""
import pytest

from benchmark import trace as T
from benchmark.layer_metrics.chunk_kv_write_ms import read, yields_a_view

MODS = [("jit_step(1)", 0.0, 2.0), ("jit_chunk(2)", 2.0, 2.0),
        ("jit_step(1)", 4.0, 2.0), ("jit_chunk(2)", 6.0, 1.0)]
VIEW = "bf16[16515072,128]{1,0:T(8,128)(2,1)}"
SCATTER = f"%fusion.140 = {VIEW} fusion(%bitcast.155, %fusion.137), kind=kCustom"
VISITS = (f"%while.55 = (s32[]{{:T(128)}}, {VIEW}, {VIEW}, s32[3]{{0:T(128)S(1)}}, "
          "/*index=4*/pred[3,2048,1]{1,0,2:T(4,128)(4,1)S(1)}) "
          "while(%tuple.104), condition=%c, body=%b")
UPDATE = f"%dynamic_update_slice.54 = {VIEW} dynamic-update-slice(%a, %b, %c)"
SELECT = ("%bitcast_select_fusion.3 = (bf16[2048,128]{1,0:T(8,128)(2,1)S(1)}, "
          "bf16[2048,128]{1,0:T(8,128)(2,1)S(1)}) fusion(%a, %b), kind=kLoop")
LAYERS = ("%while.54 = (s32[]{:T(128)}, bf16[256,2048]{1,0}, "
          "bf16[192,42,16,128,128]{4,3,2,1,0:T(8,128)(2,1)}) while(%t)")
WALK = "%gqa_prefill_paged.12 = bf16[16,256,128]{2,1,0} custom-call(%a)"
EMBED = "%fusion.9 = bf16[261120,8192]{1,0} fusion(%a)"


def run_of(chunk_ops):
    ops = sorted([(SCATTER, 0.5, 0.25)] + chunk_ops, key=lambda e: e[1])
    return {"trace": T.Trace({0: ops}, {0: MODS}, [], 0.0, 8.0),
            "cfg": {"engine": {"page_size": 128}}}


def test_what_yields_a_view():
    assert yields_a_view(SCATTER, 128) and yields_a_view(UPDATE, 128)
    assert yields_a_view(VISITS, 128)
    for other in (SELECT, LAYERS, WALK, EMBED, "%copy.3", VIEW):
        assert not yields_a_view(other, 128), other
    # a whole number of pages
    assert not yields_a_view(SCATTER, 100)


def test_row_scatters_by_calls_a_whole_chunk():
    # two scatters a whole chunk; the second execution is cut after one
    run = run_of([(LAYERS, 2.0, 2.0), (SCATTER, 2.25, 0.25), (WALK, 2.5, 0.25),
                  (SCATTER, 3.0, 0.5), (SCATTER, 6.5, 0.25)])
    assert read(run) == pytest.approx(1e3 * (0.25 + 0.5 + 0.25) / 3 * 2)


def test_a_page_wise_write_is_its_loop_counted_once():
    run = run_of([(LAYERS, 2.0, 2.0), (VISITS, 2.25, 0.5),
                  (SELECT, 2.25, 0.125), (UPDATE, 2.375, 0.125),
                  (UPDATE, 2.5, 0.125), (WALK, 3.0, 0.25),
                  (VISITS, 3.25, 0.25), (UPDATE, 3.375, 0.125)])
    assert read(run) == pytest.approx(1e3 * 0.75)


def test_a_chunk_without_a_view_reports_nothing():
    assert read(run_of([(WALK, 3.5, 0.25), (EMBED, 2.5, 0.25)])) is None
    assert read({"trace": None}) is None

"""ISSUE 39's entries of ``BENCHMARK.json`` (the linear-attention
configuration, its many-slot backlog cell, eight per-layer metrics): present,
found by name, pinned BY MEMBERSHIP (an entry appended later breaks nothing
here), the mix a function of its file at the context its cell's engine holds,
the costs file against hand arithmetic, each new reader on events of the form
a trace holds, and the new cell and the controls walked through ``run.py`` on
the CPU at a tiny size (``-m slow``; nothing is a measurement)."""
import json
import os
import sys

import pytest

from benchmark import manifest as mf, trace as T
from conftest import ROOT
from test_rehearsal import last_line, run_py

TINY = os.path.join(ROOT, "benchmark", "tests", "rehearsal_linear_attn",
                    "BENCHMARK.json")
CELL = "qwen3-next-manyslot-backlog"
CONFIG = "qwen3-next-80b-ep16-v5e1"
NEW_METRICS = {"gdn_update_ms", "gdn_update_hbm_roofline", "gdn_state_rows",
               "gated_attn_ms", "gated_attn_decode_roofline",
               "small_experts_ms", "small_experts_hbm_roofline",
               "small_expert_rows"}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def test_the_manifest_is_clean_with_the_new_entries():
    m = mf.load()
    assert mf.check(m) == [] and mf.check(mf.load(TINY)) == []
    cfg = mf.by_name(m["configs"], CONFIG, "configuration")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["source"] == load("configs", CONFIG + ".json")["_source"]
    cell = mf.by_name(m["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "manyslot-backlog", 1)
    assert [w["name"] for w in m["workloads"] if w["config"] == CONFIG] == [
        CELL]
    e2e = {x["name"] for x in mf.metrics_of(m, CELL, "end_to_end")}
    assert e2e == {"tpot_p50_ms", "setup_s"}
    mine = {x["name"]: x for x in m["per_layer"]
            if CELL in x.get("workloads", ())}
    assert set(mine) == NEW_METRICS
    for x in mine.values():
        assert x["workloads"] == [CELL] and x["moves"] == "tpot_p50_ms"
        assert (x["unit"] == "%") == x["name"].endswith("_roofline")
    # the cell also reports every unlisted metric that moves what it reports
    layer = {x["name"] for x in mf.metrics_of(m, CELL, "per_layer")}
    unlisted = {x["name"] for x in m["per_layer"]
                if "workloads" not in x and x["moves"] in e2e}
    assert layer == NEW_METRICS | unlisted
    assert {"decode_step_ms", "active_slots_mean", "device_idle_pct"} <= layer
    # no end-to-end list names the new cell
    assert all(CELL not in x.get("workloads", ()) for x in m["end_to_end"])


def test_the_traffic_file_holds_the_issue_s_parameters():
    doc = load("workloads", "manyslot-backlog.json")
    assert doc["arrival"] == {"process": "backlog", "queue_depth": 4,
                              "pool_requests": 1024}
    assert (doc["schedule_seed"], doc["ramp_s"], doc["drain_cap_s"],
            doc["trace_s"]) == (1, 20, 60, 4)
    assert doc["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.8, "min": 256, "max": 4096}
    assert doc["output_tokens"] == {"dist": "lognormal", "median": 640,
                                    "sigma": 0.4, "min": 256, "max": 1024}
    assert doc["check"]["sample_requests"] == 3


def test_the_manyslot_mix_is_a_function_of_its_file_at_its_own_context():
    """``test_traffic.py`` generates every mix file under a 1,664-token
    context, Mistral's, which this mix exceeds by design. The same
    properties, at the 5,120 tokens the cell's engine holds a sequence."""
    from benchmark import traffic
    spec = load("workloads", "manyslot-backlog.json")
    shape = lambda arrs: [(x.section, len(x.prompt),              # noqa: E731
                           x.max_new_tokens) for x in arrs]
    a = traffic.generate(spec, 2**31 + 39, 10, 19072, 5120)
    b = traffic.generate(spec, 2**31 + 39, 10, 19072, 5120)
    c = traffic.generate(spec, 40, 10, 19072, 5120)
    assert [x.prompt.tolist() for x in a] == [x.prompt.tolist() for x in b]
    assert shape(a) == shape(c) and len(a) == 1024
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    assert all(256 <= len(x.prompt) <= 4096 and
               256 <= x.max_new_tokens <= 1024 and x.prompt.max() < 19072
               and len(x.prompt) + x.max_new_tokens <= 5120 for x in a)
    # about one prompt in six spans two chunks of 2,048, one in 25 three
    two = sum(len(x.prompt) > 2048 for x in a) / len(a)
    assert 0.12 < two < 0.26, two
    with pytest.raises(ValueError):
        traffic.generate(spec, 1, 10, 19072, 5000)


def test_the_configuration_file_keeps_every_published_width():
    c = load("configs", CONFIG + ".json")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f]
    pub = next(r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert c["_source"] == pub["source_url"]
    changed = {k for k, v in pub["config"].items() if c[k] != v}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(c["reduced"]) == changed
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                              "vocab_size": 151936}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        16, 32, 19072)
    assert c["vocab_size"] == -(-151936 // 8 // 128) * 128
    assert c["engine"] == {"num_slots": 128, "page_size": 128,
                           "pages_per_seq": 40, "num_pages": 5121,
                           "prefill_chunk": 2048, "decode_horizon": 4}
    assert c["cache"]["state_bytes_per_slot_per_linear_layer"] == (
        32 * 128 * 128 * 4 + 3 * 8192 * 2) == 2146304
    assert c["cache"]["kv_bytes_per_token_per_full_layer"] == 2 * 256 * 2 * 2
    assert c["share"] == {"first_expert": 0}
    assert "float32" in c["assumed"]["recurrent_state"]
    for key in ("assumed", "deployment", "cache", "check", "published"):
        assert c[key], key
    assert set(c["check"]["limits"]) <= {"gap_mean", "flipped_share",
                                         "gap_max"}


def test_the_cost_functions_count_a_row_a_key_and_an_expert():
    from benchmark import costs_linear_attn_moe as C
    c = load("configs", CONFIG + ".json")
    state = 32 * 128 * 128
    assert C.update_row_bytes(c) == (2 * state * 4 + 2 * 3 * 8192 * 2
                                     + 8192 * 2 + 2 * 32 * 4 + 4096 * 4)
    assert C.update_row_bytes(c) == 4325632
    assert C.update_row_flops(c) == 7 * state
    assert C.update_row_flops(c) / C.update_row_bytes(c) < 1     # FLOP a byte
    assert C.update_least_s(c, 1000, PEAKS) == 4325632e3 / 819e9  # memory
    assert C.walk_bytes(c, 1) == 2 * (256 + 256) * 2 == 2048
    assert C.walk_flops(c, 1) == 16 * 2 * 256 * 2
    assert C.walk_least_s(c, 10**6, PEAKS) == 2048e6 / 819e9
    assert C.expert_stream_bytes(c, 1) == 3 * 2048 * 512 * 2 == 6291456


# -- the readers, on events of the form a trace holds --------------------------------

MODS = [("jit_step(1)", 0.0, 2.0), ("jit_chunk(2)", 2.0, 2.0),
        ("jit_step(1)", 4.0, 2.0)]
UPDATE = ("%gdn_decode_update.10 = (f32[4096,128]{1,0}, f32[12,129,32,128,128]"
          "{4,3,2,1,0}) custom-call(a, b)")
UNNAMED = "%closed_call.10 = (f32[4096,128]{1,0}) custom-call(a, b)"
WALK = "%gqa_decode_paged.10 = (bf16[128,16,256]{2,1,0}) custom-call(a)"
GATED = "%grouped_gemm_gated.3 = (bf16[4096,512]{1,0}) custom-call(a)"
DOWN = "%grouped_gemm.4 = (bf16[4096,2048]{1,0}) custom-call(a)"
ROWS, KEYS, TOUCHED = 12 * 100 * 8, 4 * 100 * 1500 * 8, 16 * 29 * 8


def run_of(update=UPDATE, cfg=None):
    ops = [(update, 0.25, 0.25), (WALK, 0.5, 0.125), (GATED, 1.0, 0.0625),
           (DOWN, 1.5, 0.0625),
           (update, 2.5, 0.25), (WALK, 3.0, 0.5),   # the chunk program's
           (update, 4.5, 0.5), (WALK, 5.0, 0.125), (GATED, 5.25, 0.0625),
           (DOWN, 5.5, 0.0625)]
    return {"trace": T.Trace({0: ops}, {0: MODS}, [], 0.0, 6.0),
            "counters_trace": {"decode_steps": 8, "gdn_state_rows": ROWS,
                               "attn_full_keys": KEYS,
                               "moe_experts_touched": TOUCHED},
            "counters_window": {"decode_steps": 80,
                                "gdn_state_rows": 10 * ROWS,
                                "moe_local_rows": 80 * 16 * 32 * 2.5},
            "cfg": cfg or load("configs", CONFIG + ".json"), "peaks": PEAKS}


def reader(name):
    from benchmark.run import load_reader
    return load_reader(ROOT, mf.load()["paths"], name)


def test_the_update_s_time_is_the_named_kernel_s_inside_the_decode_program():
    read = reader("gdn_update_ms")
    assert read(run_of()) == pytest.approx(0.75 * 1e3 / 8)
    assert read(run_of(UNNAMED)) is None
    assert read(dict(run_of(), trace=None)) is None
    assert read(dict(run_of(), counters_trace={})) is None


def test_the_roofline_shares_count_live_work_at_its_bytes():
    run = run_of()
    assert reader("gdn_update_hbm_roofline")(run) == pytest.approx(
        100 * ROWS * 4325632 / 819e9 / 0.75)
    assert reader("gated_attn_decode_roofline")(run) == pytest.approx(
        100 * KEYS * 2048 / 819e9 / 0.25)
    assert reader("small_experts_hbm_roofline")(run) == pytest.approx(
        100 * TOUCHED * 6291456 / 819e9 / 0.25)
    assert reader("gated_attn_ms")(run) == pytest.approx(0.25 * 1e3 / 8)
    assert reader("small_experts_ms")(run) == pytest.approx(0.25 * 1e3 / 8)
    # a program without the counter, or without the kernel, or a device whose
    # peaks are not known: nothing, no raise
    for name in ("gdn_update_hbm_roofline", "gated_attn_decode_roofline",
                 "small_experts_hbm_roofline"):
        assert reader(name)(dict(run, counters_trace={"decode_steps": 8})) \
            is None, name
        assert reader(name)(dict(run, peaks=None)) is None, name
    assert reader("gdn_update_hbm_roofline")(run_of(UNNAMED)) is None


def test_rows_a_layer_and_token_step():
    assert reader("gdn_state_rows")(run_of()) == pytest.approx(100.0)
    assert reader("small_expert_rows")(run_of()) == pytest.approx(2.5)
    for name in ("gdn_state_rows", "small_expert_rows"):
        assert reader(name)(dict(
            run_of(), counters_window={"decode_steps": 80})) is None


def test_the_readers_find_nothing_in_another_family_s_run():
    """On a run of a configuration without linear-attention layers, or of a
    program without the kernel and the counters (the parent), every new
    reader returns None."""
    other = load("configs", "falcon-h1-34b-v5e1.json")
    run = run_of(UNNAMED, cfg=other)
    run["counters_trace"].pop("gdn_state_rows")
    run["counters_window"].pop("gdn_state_rows")
    for name in sorted(NEW_METRICS):
        assert reader(name)(run) is None, name


# -- the walk-throughs -----------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_walks_through_run_py(trace):
    p = run_py("--workload", "tiny-manyslot", "--seed", str(2**31 + 39),
               "--seconds", "30", "--trace", str(trace), "--rehearsal",
               "--manifest", TINY)
    assert p.returncode == 0, p.stderr[-2000:]
    res = last_line(p)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    got = {k.split(".", 1)[1] for k in res["metrics"]}
    if trace:       # no device trace on the CPU: the counters' metrics only
        assert {"gdn_state_rows", "small_expert_rows"} <= got
        rows = res["metrics"]["cpu_rehearsal.gdn_state_rows"]["value"]
        assert 0 < rows <= 3            # live rows of three slots
    else:
        assert got == {"tpot_p50_ms", "setup_s"}


def test_the_float8_control_fails_the_rehearsal_s_check():
    """The check's own comparison (``check.compare(control="fp8")``) on the
    tiny configuration's reference over 160 positions of one sequence: the
    token the float8 reference puts first lies below the float32 reference's
    best in one position in ten or more. (Through the engine the interpreter
    serves a dozen positions a run, too few to hold a tenth of them to
    anything.)"""
    import jax
    import numpy as np
    from benchmark import check as ck
    from benchmark.references import linear_attn_moe_lm as ref
    cfg = load("tests", "rehearsal_linear_attn", "configs",
               "tiny-linear-attn.json")
    w = jax.jit(lambda k: ref.init_weights(k, cfg))(jax.random.PRNGKey(1))
    seq = np.random.default_rng(0).integers(1, 256, 200).astype(np.int32)
    out = ck.compare([None], [seq[:40]], [seq[40:]], ref, w, cfg, pad_to=200,
                     control="fp8")
    assert out["positions"] == 160
    assert out["control_gap_mean"] > 0
    assert 0.05 < out["control_flipped_share"] < 0.6


@pytest.mark.slow
def test_the_controls_walk_through():
    script = [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                           "gdn_control.py")]
    controls = "none,state-reset,delta-dropped,norm-plain-weight"
    p = run_py("--workload", "tiny-manyslot", "--seeds", "5", "--seconds",
               "20", "--controls", controls, "--rehearsal", "--manifest",
               TINY, script=script)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(x) for x in p.stdout.strip().splitlines()]
    rows = {r["control"]: r for r in rows if "gdn_control" in r}
    assert set(rows) == set(controls.split(","))
    assert rows["none"]["correct"] and rows["none"]["failed"] == 0
    # (a dozen positions of a toy resolve little: the controls' readings are
    # the chip's, in the configuration's ``check.set_from``)
    assert all(r["failed"] == 0 and r["positions"] > 0
               for r in rows.values())
    assert rows["norm-plain-weight"]["gap_mean"] > 10 * max(
        rows["none"]["gap_mean"], 1e-3)

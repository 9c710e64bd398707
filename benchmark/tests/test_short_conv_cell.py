"""ISSUE 43's entries of ``BENCHMARK.json`` (the short-convolution
configuration, its tool-call backlog cell, seven per-layer metrics): present,
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

TINY = os.path.join(ROOT, "benchmark", "tests", "rehearsal_short_conv",
                    "BENCHMARK.json")
CELL = "lfm2-24b-toolcall-backlog"
CONFIG = "lfm2-24b-a2b-pp4-v5e1"
NEW_METRICS = {"whole_experts_ms", "whole_experts_hbm_roofline",
               "whole_expert_rows", "gqa64_attn_ms", "gqa64_decode_roofline",
               "gqa64_chunk_attn_ms", "conv_state_rows"}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def test_the_manifest_is_clean_with_the_new_entries():
    m = mf.load()
    assert mf.check(m) == [] and mf.check(mf.load(TINY)) == []
    cfg = mf.by_name(m["configs"], CONFIG, "configuration")
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["source"] == load("configs", CONFIG + ".json")["_source"]
    cell = mf.by_name(m["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "toolcall-backlog", 1)
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
    doc = load("workloads", "toolcall-backlog.json")
    assert doc["arrival"] == {"process": "backlog", "queue_depth": 4,
                              "pool_requests": 2048}
    assert (doc["schedule_seed"], doc["ramp_s"], doc["drain_cap_s"],
            doc["trace_s"]) == (1, 20, 45, 4)
    assert doc["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 0.7, "min": 512, "max": 8192}
    assert doc["output_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.5, "min": 128, "max": 1024}
    assert doc["check"]["sample_requests"] == 3


def test_the_toolcall_mix_is_a_function_of_its_file_at_its_own_context():
    """``test_traffic.py`` generates every mix file under a 1,664-token
    context, Mistral's, which this mix exceeds by design. The same
    properties, at the 9,216 tokens the cell's engine holds a sequence."""
    from benchmark import traffic
    spec = load("workloads", "toolcall-backlog.json")
    shape = lambda arrs: [(x.section, len(x.prompt),              # noqa: E731
                           x.max_new_tokens) for x in arrs]
    a = traffic.generate(spec, 2**31 + 43, 10, 65536, 9216)
    b = traffic.generate(spec, 2**31 + 43, 10, 65536, 9216)
    c = traffic.generate(spec, 44, 10, 65536, 9216)
    assert [x.prompt.tolist() for x in a] == [x.prompt.tolist() for x in b]
    assert shape(a) == shape(c) and len(a) == 2048
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    assert all(512 <= len(x.prompt) <= 8192 and
               128 <= x.max_new_tokens <= 1024 and x.prompt.max() < 65536
               and len(x.prompt) + x.max_new_tokens <= 9216 for x in a)
    # about half of the prompts span two chunks of 2,048 or more
    two = sum(len(x.prompt) > 2048 for x in a) / len(a)
    assert 0.4 < two < 0.6, two
    mean = sum(len(x.prompt) for x in a) / len(a)
    assert 2300 < mean < 2900, mean
    with pytest.raises(ValueError):
        traffic.generate(spec, 1, 10, 65536, 9000)


def test_the_configuration_file_keeps_every_published_width():
    c = load("configs", CONFIG + ".json")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f]
    pub = next(r for r in rows if r["name"] == "LFM2-24B-A2B")
    assert c["_source"] == pub["source_url"]
    changed = {k for k, v in pub["config"].items() if c[k] != v}
    assert changed == {"num_hidden_layers"} == set(c["reduced"])
    assert c["published"] == {"num_hidden_layers": 40}
    assert c["num_hidden_layers"] == 10
    # the pattern is kept whole; the first ten entries are what runs: the
    # two dense conv layers, then two whole periods
    assert c["layer_types"][:10] == ["conv", "conv"] + [
        "full_attention", "conv", "conv", "conv"] * 2
    assert (c["num_experts"], c["num_experts_per_tok"], c["vocab_size"]) == (
        64, 4, 65536)
    assert c["engine"]["pages_per_seq"] == 72 \
        and c["engine"]["num_pages"] == c["engine"]["num_slots"] * 72 + 1
    assert {k: c["engine"][k] for k in ("page_size", "prefill_chunk",
                                        "decode_horizon")} == {
        "page_size": 128, "prefill_chunk": 2048, "decode_horizon": 4}
    assert c["cache"]["state_bytes_per_slot_per_conv_layer"] == 2 * 2048 * 2
    assert c["cache"]["kv_bytes_per_token_per_full_layer"] == 8 * 2 * 64 * 2
    assert c["assumed"]["tie_word_embeddings"] is True
    for key in ("assumed", "deployment", "cache", "check", "published",
                "arithmetic"):
        assert c[key], key
    assert set(c["check"]["limits"]) <= {"gap_mean", "flipped_share",
                                         "gap_max"}


def test_the_cost_functions_count_an_expert_a_key_and_a_row():
    from benchmark import costs_short_conv_moe as C
    c = load("configs", CONFIG + ".json")
    assert C.expert_bytes(c) == 3 * 2048 * 1536 * 2 == 18874368
    assert C.expert_stream_bytes(c, 64 * 8) == 64 * 8 * 18874368
    assert C.expert_flops(c, 1) == 6 * 2048 * 1536
    by_bytes, by_flops = C.experts_least_s(c, 512, 84 * 4 * 8, PEAKS)
    assert by_bytes == 512 * 18874368 / 819e9 and by_flops < by_bytes / 10
    # a 2,048-row chunk: 128 rows an expert, the two bounds come near
    by_bytes, by_flops = C.experts_least_s(c, 512, 2048 * 4 * 8, PEAKS)
    assert 0.5 < by_flops / by_bytes < 1.5
    assert C.walk_bytes(c, 1) == 8 * 2 * 64 * 2 == 2048
    assert C.walk_flops(c, 1) == 32 * 2 * 64 * 2
    assert C.walk_least_s(c, 10**6, PEAKS) == 2048e6 / 819e9
    assert C.conv_row_bytes(c) == 8 * 2048 * 2


# -- the readers, on events of the form a trace holds --------------------------------

MODS = [("jit_step(1)", 0.0, 2.0), ("jit_chunk(2)", 2.0, 2.0),
        ("jit_step(1)", 4.0, 2.0)]
WALK = "%gqa_decode_paged.10 = (bf16[96,32,128]{2,1,0}) custom-call(a)"
CHUNK_WALK = "%gqa_prefill_paged.7 = (bf16[8,8192,128]{2,1,0}) custom-call(a)"
GATED = "%grouped_gemm_gated.3 = (bf16[1408,1536]{1,0}) custom-call(a)"
DOWN = "%grouped_gemm.4 = (bf16[1408,2048]{1,0}) custom-call(a)"
KEYS, TOUCHED, PICKS = 2 * 84 * 2800 * 8, 8 * 64 * 8, 8 * 84 * 4 * 8


def run_of(cfg=None, walk=WALK):
    ops = [(walk, 0.5, 0.125), (GATED, 1.0, 0.0625), (DOWN, 1.5, 0.0625),
           (CHUNK_WALK, 2.5, 0.5), (CHUNK_WALK, 3.0, 0.25),
           (GATED, 3.5, 0.25),                       # the chunk program's
           (walk, 5.0, 0.125), (GATED, 5.25, 0.0625), (DOWN, 5.5, 0.0625)]
    return {"trace": T.Trace({0: ops}, {0: MODS}, [], 0.0, 6.0),
            "counters_trace": {"decode_steps": 8, "attn_full_keys": KEYS,
                               "moe_experts_touched": TOUCHED,
                               "moe_local_rows": PICKS},
            "counters_window": {"decode_steps": 80,
                                "conv_state_rows": 80 * 8 * 84,
                                "moe_local_rows": 80 * 64 * 8 * 5.25},
            "cfg": cfg or load("configs", CONFIG + ".json"), "peaks": PEAKS}


def reader(name):
    from benchmark.run import load_reader
    return load_reader(ROOT, mf.load()["paths"], name)


def test_the_times_are_the_named_kernels_inside_their_programs():
    run = run_of()
    assert reader("whole_experts_ms")(run) == pytest.approx(0.25 * 1e3 / 8)
    assert reader("gqa64_attn_ms")(run) == pytest.approx(0.25 * 1e3 / 8)
    # the chunk walk: the chunk program's calls alone, over its executions
    assert reader("gqa64_chunk_attn_ms")(run) == pytest.approx(0.75 * 1e3)
    for name in ("whole_experts_ms", "gqa64_attn_ms", "gqa64_chunk_attn_ms"):
        assert reader(name)(dict(run, trace=None)) is None, name
    assert reader("gqa64_attn_ms")(dict(run, counters_trace={})) is None
    assert reader("gqa64_attn_ms")(
        run_of(walk="%closed_call.10 = (bf16[96]{0}) custom-call(a)")) is None


def test_the_roofline_shares_count_live_work_at_its_published_bytes(capsys):
    run = run_of()
    assert reader("gqa64_decode_roofline")(run) == pytest.approx(
        100 * KEYS * 2048 / 819e9 / 0.25)
    share = reader("whole_experts_hbm_roofline")(run)
    assert share == pytest.approx(100 * TOUCHED * 18874368 / 819e9 / 0.25)
    said = json.loads(capsys.readouterr().out)["whole_experts_roofline"]
    assert said["bound_by"] == "memory"
    assert said["hbm_share_pct"] == pytest.approx(share)
    assert said["flop_share_pct"] == pytest.approx(
        100 * PICKS * 6 * 2048 * 1536 / 197e12 / 0.25)
    assert said["flop_share_pct"] < said["hbm_share_pct"]
    # a program without the counter, or without the kernel, or a device whose
    # peaks are not known: nothing, no raise
    for name in ("gqa64_decode_roofline", "whole_experts_hbm_roofline"):
        assert reader(name)(dict(run, counters_trace={"decode_steps": 8})) \
            is None, name
        assert reader(name)(dict(run, peaks=None)) is None, name
        assert reader(name)(dict(run, trace=None)) is None, name


def test_rows_an_expert_and_a_conv_layer_see_a_token_step():
    assert reader("whole_expert_rows")(run_of()) == pytest.approx(5.25)
    assert reader("conv_state_rows")(run_of()) == pytest.approx(84.0)
    for name in ("whole_expert_rows", "conv_state_rows"):
        assert reader(name)(dict(
            run_of(), counters_window={"decode_steps": 80})) is None


def test_the_readers_find_nothing_in_another_family_s_run():
    """On a run of a configuration without short-convolution layers, or of a
    program without the counters (the parent), every new reader returns
    None."""
    other = load("configs", "qwen3-next-80b-ep16-v5e1.json")
    run = run_of(cfg=other)
    run["counters_window"].pop("conv_state_rows")
    for name in sorted(NEW_METRICS):
        assert reader(name)(run) is None, name


# -- the walk-throughs -----------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_walks_through_run_py(trace):
    p = run_py("--workload", "tiny-toolcall", "--seed", str(2**31 + 43),
               "--seconds", "30", "--trace", str(trace), "--rehearsal",
               "--manifest", TINY)
    assert p.returncode == 0, p.stderr[-2000:]
    res = last_line(p)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    got = {k.split(".", 1)[1] for k in res["metrics"]}
    if trace:       # no device trace on the CPU: the counters' metrics only
        assert {"conv_state_rows", "whole_expert_rows"} <= got
        rows = res["metrics"]["cpu_rehearsal.conv_state_rows"]["value"]
        assert 0 < rows <= 3            # live rows of three slots
    else:
        assert got == {"tpot_p50_ms", "setup_s"}


def test_the_float8_control_fails_the_rehearsal_s_check():
    """The check's own comparison (``check.compare(control="fp8")``) on the
    tiny configuration's reference over 160 positions of one sequence: the
    token the float8 reference puts first lies below the float32 reference's
    best in one position in ten or more."""
    import jax
    import numpy as np
    from benchmark import check as ck
    from benchmark.references import short_conv_moe_lm as ref
    cfg = load("tests", "rehearsal_short_conv", "configs",
               "tiny-short-conv.json")
    w = jax.jit(lambda k: ref.init_weights(k, cfg))(jax.random.PRNGKey(1))
    seq = np.random.default_rng(0).integers(1, 256, 200).astype(np.int32)
    out = ck.compare([None], [seq[:40]], [seq[40:]], ref, w, cfg, pad_to=200,
                     control="fp8")
    assert out["positions"] == 160
    assert out["control_gap_mean"] > 0
    assert out["control_flipped_share"] > 0.05


@pytest.mark.slow
def test_the_controls_walk_through():
    script = [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                           "short_conv_control.py")]
    controls = "none,taps-reversed,renorm-dropped"
    p = run_py("--workload", "tiny-toolcall", "--seeds", "5", "--seconds",
               "20", "--controls", controls, "--rehearsal", "--manifest",
               TINY, script=script)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(x) for x in p.stdout.strip().splitlines()]
    rows = {r["control"]: r for r in rows if "short_conv_control" in r}
    assert set(rows) == set(controls.split(","))
    assert rows["none"]["correct"] and rows["none"]["failed"] == 0
    # (a dozen positions of a toy resolve little: the controls' readings are
    # the chip's, in the configuration's ``check.set_from``)
    assert all(r["failed"] == 0 and r["positions"] > 0
               for r in rows.values())
    assert rows["renorm-dropped"]["gap_mean"] > 3 * max(
        rows["none"]["gap_mean"], 1e-3)

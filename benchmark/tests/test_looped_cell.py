"""ISSUE 46's entries of ``BENCHMARK.json`` (the looped configuration, its
short-reasoning backlog cell, five per-layer metrics): present, found by name,
pinned BY MEMBERSHIP (an entry appended later breaks nothing here), the mix a
function of its file at the context its cell's engine holds, the costs file
against hand arithmetic, each new reader on events of the form a trace holds,
and the new cell and the controls walked through ``run.py`` on the CPU at a
tiny size (``-m slow``; nothing is a measurement)."""
import json
import os
import sys

import pytest

from benchmark import manifest as mf, trace as T
from conftest import ROOT
from test_rehearsal import last_line, run_py

TINY = os.path.join(ROOT, "benchmark", "tests", "rehearsal_looped",
                    "BENCHMARK.json")
CELL = "ouro-2.6b-shortreason-backlog"
CONFIG = "ouro-2.6b-v5e1"
NEW_METRICS = {"loop_decode_hbm_roofline", "loop_attn_ms",
               "loop_attn_decode_roofline", "loop_chunk_attn_ms",
               "loop_ctx_keys"}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def test_the_manifest_is_clean_with_the_new_entries():
    m = mf.load()
    assert mf.check(m) == [] and mf.check(mf.load(TINY)) == []
    cfg = mf.by_name(m["configs"], CONFIG, "configuration")
    assert cfg["reduced"] == []
    assert cfg["source"] == load("configs", CONFIG + ".json")["_source"]
    cell = mf.by_name(m["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "shortreason-backlog", 1)
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
    doc = load("workloads", "shortreason-backlog.json")
    assert doc["arrival"] == {"process": "backlog", "queue_depth": 4,
                              "pool_requests": 512}
    assert (doc["schedule_seed"], doc["ramp_s"], doc["drain_cap_s"],
            doc["trace_s"]) == (1, 10, 45, 4)
    assert doc["prompt_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.5, "min": 32, "max": 256}
    assert doc["output_tokens"] == {"dist": "lognormal", "median": 224,
                                    "sigma": 0.4, "min": 64, "max": 384}
    assert doc["check"]["sample_requests"] == 3


def test_the_shortreason_mix_is_a_function_of_its_file_at_its_own_context():
    """The mix at the 640 tokens (5 pages) the cell's engine holds a
    sequence: a function of the seed, every context inside the five pages,
    and a prompt ONE chunk of 256 for every request."""
    from benchmark import traffic
    spec = load("workloads", "shortreason-backlog.json")
    shape = lambda arrs: [(x.section, len(x.prompt),              # noqa: E731
                           x.max_new_tokens) for x in arrs]
    a = traffic.generate(spec, 2**31 + 46, 10, 49152, 640)
    b = traffic.generate(spec, 2**31 + 46, 10, 49152, 640)
    c = traffic.generate(spec, 47, 10, 49152, 640)
    assert [x.prompt.tolist() for x in a] == [x.prompt.tolist() for x in b]
    assert shape(a) == shape(c) and len(a) == 512
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    assert all(32 <= len(x.prompt) <= 256 and 64 <= x.max_new_tokens <= 384
               and x.prompt.max() < 49152
               and len(x.prompt) + x.max_new_tokens <= 640 for x in a)
    # decode-heavy: answers longer than prompts
    mean_in = sum(len(x.prompt) for x in a) / len(a)
    mean_out = sum(x.max_new_tokens for x in a) / len(a)
    assert 120 < mean_in < 150 and 210 < mean_out < 250, (mean_in, mean_out)
    with pytest.raises(ValueError):
        traffic.generate(spec, 1, 10, 49152, 600)


def test_the_configuration_file_is_the_published_model_whole():
    c = load("configs", CONFIG + ".json")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f]
    pub = next(r for r in rows if r["name"] == "Ouro-2.6B")
    assert c["_source"] == pub["source_url"]
    assert {k for k, v in pub["config"].items() if c[k] != v} == set()
    assert c["reduced"].startswith("nothing")
    assert (c["num_hidden_layers"], c["total_ut_steps"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["intermediate_size"], c["vocab_size"],
            c["tie_word_embeddings"]) == (48, 4, 16, 16, 128, 5632, 49152,
                                          False)
    e = c["engine"]
    assert e["num_pages"] == e["num_slots"] * e["pages_per_seq"] + 1
    assert {k: e[k] for k in ("page_size", "pages_per_seq", "prefill_chunk",
                              "decode_horizon")} == {
        "page_size": 128, "pages_per_seq": 5, "prefill_chunk": 256,
        "decode_horizon": 4}
    assert {k: c["cache"][k] for k in (
        "planes", "kv_bytes_per_key_and_plane", "kv_bytes_per_token",
        "kv_bytes_per_page")} == {
        "planes": 192, "kv_bytes_per_key_and_plane": 8192,
        "kv_bytes_per_token": 1572864, "kv_bytes_per_page": 201326592}
    for key in ("assumed", "deployment", "cache", "check", "published",
                "arithmetic"):
        assert c[key], key
    assert set(c["check"]["limits"]) <= {"gap_mean", "flipped_share",
                                         "gap_max"}


def test_the_cost_functions_count_a_walk_s_weights_the_head_and_a_key():
    from benchmark import costs_looped as C
    c = load("configs", CONFIG + ".json")
    assert C.planes(c) == 192 and C.key_bytes(c) == 8192
    assert C.kv_bytes_per_token(c) == 1572864 == c["cache"][
        "kv_bytes_per_token"]
    assert C.layer_params(c) == 4 * 2048 * 2048 + 3 * 2048 * 5632 == 51380224
    assert C.walk_weight_bytes(c) == 48 * 51380224 * 2 == 4932501504
    assert C.head_bytes(c) == 2048 * 49152 * 2 == 201326592
    a_step = 4 * 4932501504 + 201326592
    assert C.decode_min_bytes(c, 1, 0) == a_step
    assert C.decode_min_bytes(c, 3, 1000) == 3 * a_step + 1000 * 8192
    assert C.walk_least_s(c, 10**6, PEAKS) == 8192e6 / 819e9
    # a chunk: the same weights, and the pages its blocks walked
    assert C.chunk_min_bytes(c, 192 * 4, 128) == a_step + 768 * 128 * 8192
    flops = C.chunk_flops(c, 256, 256 * 200)
    assert flops == 256 * 192 * 51380224 * 2 + 256 * 200 * 192 * 16 * 128 * 4
    # at the ridge: 5.1 TFLOP under 19.9 GB
    assert 0.9 < (flops / 197e12) / (a_step / 819e9) < 1.2


# -- the readers, on events of the form a trace holds --------------------------------

MODS = [("jit_step(1)", 0.0, 2.0), ("jit_chunk(2)", 2.0, 2.0),
        ("jit_step(1)", 4.0, 2.0)]
WALK = "%gqa_decode_paged.10 = (bf16[8,16,128]{2,1,0}) custom-call(a)"
CHUNK_WALK = "%gqa_prefill_paged.7 = (bf16[16,256,128]{2,1,0}) custom-call(a)"
KEYS, CALLS = 8 * 7 * 260 * 192, 8 * 7 * 192


def run_of(cfg=None, walk=WALK):
    ops = [(walk, 0.5, 0.125), (CHUNK_WALK, 2.5, 0.5), (CHUNK_WALK, 3.0, 0.25),
           (walk, 5.0, 0.125)]
    return {"trace": T.Trace({0: ops}, {0: MODS}, [], 0.0, 6.0),
            "counters_trace": {"decode_steps": 8, "loop_plane_keys": KEYS,
                               "loop_row_calls": CALLS},
            "counters_window": {"decode_steps": 80,
                                "loop_plane_keys": 10 * KEYS,
                                "loop_row_calls": 10 * CALLS},
            "cfg": cfg or load("configs", CONFIG + ".json"), "peaks": PEAKS}


def reader(name):
    from benchmark.run import load_reader
    return load_reader(ROOT, mf.load()["paths"], name)


def test_the_times_are_the_named_kernels_inside_their_programs():
    run = run_of()
    assert reader("loop_attn_ms")(run) == pytest.approx(0.25 * 1e3 / 8)
    # the chunk walk: the chunk program's calls alone, their mean x the 192
    # calls a chunk (an execution the trace cut short changes nothing)
    assert reader("loop_chunk_attn_ms")(run) == pytest.approx(
        0.75 * 1e3 * 192 / 2)
    for name in ("loop_attn_ms", "loop_chunk_attn_ms"):
        assert reader(name)(dict(run, trace=None)) is None, name
    assert reader("loop_attn_ms")(dict(run, counters_trace={})) is None
    assert reader("loop_attn_ms")(
        run_of(walk="%closed_call.10 = (bf16[8]{0}) custom-call(a)")) is None


def test_the_roofline_shares_count_four_walks_of_weights_and_the_keys():
    run = run_of()
    assert reader("loop_attn_decode_roofline")(run) == pytest.approx(
        100 * KEYS * 8192 / 819e9 / 0.25)
    least = 8 * (4 * 4932501504 + 201326592) + KEYS * 8192
    assert reader("loop_decode_hbm_roofline")(run) == pytest.approx(
        100 * least / 819e9 / 4.0)
    # a program without the counter, or without the kernel, or a device whose
    # peaks are not known: nothing, no raise
    for name in ("loop_attn_decode_roofline", "loop_decode_hbm_roofline"):
        assert reader(name)(dict(run, counters_trace={"decode_steps": 8})) \
            is None, name
        assert reader(name)(dict(run, peaks=None)) is None, name
        assert reader(name)(dict(run, trace=None)) is None, name


def test_the_mean_context_a_row_walks_in_a_plane():
    assert reader("loop_ctx_keys")(run_of()) == pytest.approx(260.0)
    assert reader("loop_ctx_keys")(dict(
        run_of(), counters_window={"decode_steps": 80})) is None


def test_the_readers_find_nothing_in_another_family_s_run():
    """On a run of a configuration without walks, or of a program without the
    counters (the parent), every new reader returns None."""
    other = load("configs", "mistral-7b-v5e1.json")
    run = run_of(cfg=other)
    run["counters_window"].pop("loop_row_calls")
    for name in sorted(NEW_METRICS):
        assert reader(name)(run) is None, name


# -- the walk-throughs -----------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_walks_through_run_py(trace):
    p = run_py("--workload", "tiny-shortreason", "--seed", str(2**31 + 46),
               "--seconds", "20", "--trace", str(trace), "--rehearsal",
               "--manifest", TINY)
    assert p.returncode == 0, p.stderr[-2000:]
    res = last_line(p)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    got = {k.split(".", 1)[1] for k in res["metrics"]}
    if trace:       # no device trace on the CPU: the counters' metrics only
        assert "loop_ctx_keys" in got
        keys = res["metrics"]["cpu_rehearsal.loop_ctx_keys"]["value"]
        assert 18 < keys <= 64          # a context of the tiny mix
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
    from benchmark.references import looped_lm as ref
    cfg = load("tests", "rehearsal_looped", "configs", "tiny-looped.json")
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
                                           "loop_control.py")]
    controls = "none,three-walks,rope-by-walk"
    p = run_py("--workload", "tiny-shortreason", "--seeds", "5", "--seconds",
               "15", "--controls", controls, "--rehearsal", "--manifest",
               TINY, script=script)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(x) for x in p.stdout.strip().splitlines()]
    rows = {r["control"]: r for r in rows if "loop_control" in r}
    assert set(rows) == set(controls.split(","))
    assert rows["none"]["correct"] and rows["none"]["failed"] == 0
    # (a dozen positions of a toy resolve little: the controls' readings are
    # the chip's, in the configuration's ``check.set_from``)
    assert all(r["failed"] == 0 and r["positions"] > 0
               and r["loop_early_exit_rows"] == 0 for r in rows.values())
    assert rows["three-walks"]["gap_mean"] > 3 * max(
        rows["none"]["gap_mean"], rows["rope-by-walk"]["gap_mean"], 1e-3)

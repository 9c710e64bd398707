"""ISSUE 30's entries of ``BENCHMARK.json`` (the window-and-full-attention,
routed-expert configuration, its backlog cell, eight per-layer metrics):
present, found by name, the long mix held to the context its cell's engine
holds, and the new cell walked through ``run.py`` and the window control on
the CPU at a tiny size (``-m slow``; nothing is a measurement)."""
import json
import os
import sys

import pytest

from benchmark import manifest as mf
from conftest import ROOT
from test_rehearsal import last_line, run_py

TINY = os.path.join(ROOT, "benchmark", "tests", "rehearsal_window",
                    "BENCHMARK.json")
CELL = "command-a-plus-longdoc-backlog"
NEW_METRICS = {"swa_attn_ms", "swa_decode_roofline", "full_attn_ms",
               "full_decode_roofline", "kv_window_held_pct",
               "held_experts_ms", "held_experts_hbm_roofline",
               "held_expert_rows"}


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def test_the_manifest_is_clean_with_the_new_entries():
    m = mf.load()
    assert mf.check(m) == [] and mf.check(mf.load(TINY)) == []
    cfg = mf.by_name(m["configs"], "command-a-plus-ep8-v5e1", "configuration")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    cell = mf.by_name(m["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "command-a-plus-ep8-v5e1", "longctx-backlog", 1)
    assert m["workloads"][-1] is cell and m["configs"][-1] is cfg
    e2e = {x["name"] for x in mf.metrics_of(m, CELL, "end_to_end")}
    assert e2e == {"tpot_p50_ms", "setup_s"}
    layer = {x["name"] for x in mf.metrics_of(m, CELL, "per_layer")}
    assert layer == NEW_METRICS | {"decode_step_ms", "active_slots_mean",
                                   "host_share_pct", "device_idle_pct"}
    assert [x["name"] for x in m["per_layer"][-8:]] == [
        "swa_attn_ms", "swa_decode_roofline", "full_attn_ms",
        "full_decode_roofline", "kv_window_held_pct", "held_experts_ms",
        "held_experts_hbm_roofline", "held_expert_rows"]
    for x in m["per_layer"][-8:]:
        assert x["workloads"] == [CELL] and x["moves"] == "tpot_p50_ms"
    # no list that was there names the new cell
    assert all(CELL not in x.get("workloads", ())
               for x in m["per_layer"][:-8] + m["end_to_end"])


def test_the_traffic_file_holds_the_issue_s_parameters():
    doc = load("workloads", "longctx-backlog.json")
    assert doc["arrival"] == {"process": "backlog", "queue_depth": 4,
                              "pool_requests": 512}
    assert (doc["schedule_seed"], doc["ramp_s"], doc["drain_cap_s"],
            doc["trace_s"]) == (1, 10, 45, 4)
    assert doc["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                    "sigma": 0.6, "min": 2048, "max": 24576}
    assert doc["output_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.5, "min": 128, "max": 768}
    assert doc["check"]["sample_requests"] == 3


def test_the_long_mix_is_a_function_of_its_file_at_its_own_context():
    """``test_traffic.py`` generates every mix file under a 1,664-token
    context, Mistral's, which this mix exceeds by design (its case there
    fails; that file is not this PR's to edit). The same properties, at the
    25,600 tokens the cell's engine holds a sequence."""
    from benchmark import traffic
    spec = load("workloads", "longctx-backlog.json")
    shape = lambda arrs: [(x.section, len(x.prompt),              # noqa: E731
                           x.max_new_tokens) for x in arrs]
    a = traffic.generate(spec, 2**31 + 17, 51, 32768, 25600)
    b = traffic.generate(spec, 2**31 + 17, 51, 32768, 25600)
    c = traffic.generate(spec, 18, 51, 32768, 25600)
    assert [x.prompt.tolist() for x in a] == [x.prompt.tolist() for x in b]
    assert shape(a) == shape(c) and len(a) == 512
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    assert all(2048 <= len(x.prompt) <= 24576 and
               128 <= x.max_new_tokens <= 768 and x.prompt.max() < 32768
               for x in a)
    past = sum(len(x.prompt) > 4096 for x in a) / len(a)
    assert 0.85 < past < 0.95           # nine prompts in ten pass the window
    with pytest.raises(ValueError):
        traffic.generate(spec, 1, 51, 32768, 25342)


def test_the_configuration_file_keeps_every_published_width():
    c = load("configs", "command-a-plus-ep8-v5e1.json")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(x) for x in f]
    pub = next(r for r in rows if r["name"] == "command-a-plus-05-2026")
    assert c["_source"] == pub["source_url"]
    changed = {k for k, v in pub["config"].items() if c[k] != v}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(c["reduced"]) == changed
    assert c["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                              "vocab_size": 262144}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        4, 16, 32768)
    assert c["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"]
    e = c["engine"]
    assert e == {"num_slots": 24, "page_size": 128, "pages_per_seq": 200,
                 "num_pages": 4800, "prefill_chunk": 2048,
                 "decode_horizon": 4}
    assert c["cache"]["ring_pages"] == -(-(4096 + 2048 - 1) // 128) + 1 == 49
    assert set(c["check"]["limits"]) <= {"gap_mean", "flipped_share",
                                         "gap_max"}


def test_the_cost_functions_count_a_key_and_an_expert():
    from benchmark import costs_window_moe as C
    c = load("configs", "command-a-plus-ep8-v5e1.json")
    assert C.walk_bytes(c, 1) == 4096 and C.walk_flops(c, 1) == 65536
    assert C.expert_stream_bytes(c, 1) == 3 * 4096 * 4096 * 2
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert C.walk_least_s(c, 10**6, peaks) == 4096e6 / 819e9   # memory-bound


@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_walks_through_run_py(trace):
    p = run_py("--workload", "tiny-longctx", "--seed", str(2**31 + 26),
               "--seconds", "6", "--trace", str(trace), "--rehearsal",
               "--manifest", TINY)
    assert p.returncode == 0, p.stderr[-2000:]
    res = last_line(p)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    got = {k.split(".", 1)[1] for k in res["metrics"]}
    if trace:       # no device trace on the CPU: the counters' metrics only
        assert {"kv_window_held_pct", "held_expert_rows"} <= got
        held = res["metrics"]["cpu_rehearsal.kv_window_held_pct"]["value"]
        assert 0 < held < 100           # prompts past the 7-page ring
    else:
        assert got == {"tpot_p50_ms", "setup_s"}


@pytest.mark.slow
def test_the_window_control_walks_through():
    script = [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                           "window_control.py")]
    p = run_py("--workload", "tiny-longctx", "--seeds", "5", "--seconds",
               "4", "--pages-off", "1", "--rehearsal", "--manifest", TINY,
               script=script)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(x) for x in p.stdout.strip().splitlines()]
    rows = [r for r in rows if "window_control" in r]
    assert rows and rows[0]["pages_off"] == 1 and rows[0]["failed"] == 0

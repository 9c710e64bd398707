"""ISSUE 26's entries of ``BENCHMARK.json`` (the latent-attention,
routed-expert configuration, its backlog cell, the second chat schedule, five
per-layer metrics): present, found by name, and the new cell walked through
``run.py`` on the CPU at a tiny size (``-m slow``; nothing is a measurement)."""
import json
import os
import sys

import pytest

from benchmark import manifest as mf
from conftest import ROOT
from test_rehearsal import last_line, run_py

TINY = os.path.join(ROOT, "benchmark", "tests", "rehearsal_latent",
                    "BENCHMARK.json")
NEW_METRICS = {"mla_attn_ms", "mla_decode_roofline", "moe_ffn_ms",
               "moe_ffn_hbm_roofline", "moe_rows_per_expert"}


def test_the_manifest_is_clean_with_the_new_entries():
    m = mf.load()
    assert mf.check(m) == [] and mf.check(mf.load(TINY)) == []
    cfg = mf.by_name(m["configs"], "kimi-k2-ep32-v5e1", "configuration")
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    cell = mf.by_name(m["workloads"], "kimi-k2-longdoc-backlog", "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-k2-ep32-v5e1", "longdoc-backlog", 1)
    e2e = {x["name"] for x in mf.metrics_of(m, cell["name"], "end_to_end")}
    assert e2e == {"tpot_p50_ms", "setup_s"}
    layer = {x["name"] for x in mf.metrics_of(m, cell["name"], "per_layer")}
    assert layer == NEW_METRICS | {"decode_step_ms", "active_slots_mean",
                                   "host_share_pct", "device_idle_pct"}
    for x in m["per_layer"]:
        if x["name"] in NEW_METRICS:
            assert x["workloads"] == [cell["name"]]
            assert x["moves"] == "tpot_p50_ms"
    b = mf.by_name(m["workloads"], "mistral7b-chat-steady-b", "cell")
    assert {x["name"] for x in mf.metrics_of(m, b["name"], "end_to_end")} \
        == {"tpot_p50_ms", "setup_s"}


def test_the_traffic_files_hold_the_issue_s_parameters():
    def mix(name):
        with open(os.path.join(ROOT, "benchmark", "workloads",
                               name + ".json")) as f:
            return json.load(f)
    doc = mix("longdoc-backlog")
    assert doc["arrival"] == {"process": "backlog", "queue_depth": 4,
                              "pool_requests": 512}
    assert (doc["schedule_seed"], doc["ramp_s"], doc["drain_cap_s"],
            doc["trace_s"]) == (1, 10, 45, 4)
    assert doc["prompt_tokens"] == {"dist": "lognormal", "median": 3072,
                                    "sigma": 0.5, "min": 1024, "max": 8192}
    assert doc["output_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.5, "min": 128, "max": 768}
    assert doc["check"]["sample_requests"] == 3
    a, b = mix("chat-steady"), mix("chat-steady-b")
    assert (a["schedule_seed"], b["schedule_seed"]) == (1, 2)
    for k in a:
        if k not in ("schedule_seed", "describes"):
            assert a[k] == b[k], k


def test_the_long_mix_is_a_function_of_its_file_at_its_own_context():
    """``test_traffic.py`` generates every mix file under a 1,664-token
    context, Mistral's, which this mix exceeds by design (its case there
    fails; that file is not this PR's to edit). The same properties, at the
    context the cell's engine holds."""
    from benchmark import traffic
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "longdoc-backlog.json")) as f:
        spec = json.load(f)
    shape = lambda arrs: [(x.section, len(x.prompt),              # noqa: E731
                           x.max_new_tokens) for x in arrs]
    a = traffic.generate(spec, 2**31 + 17, 51, 20480, 8960)
    b = traffic.generate(spec, 2**31 + 17, 51, 20480, 8960)
    c = traffic.generate(spec, 18, 51, 20480, 8960)
    assert [x.prompt.tolist() for x in a] == [x.prompt.tolist() for x in b]
    assert shape(a) == shape(c) and len(a) == 512
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    assert all(1024 <= len(x.prompt) <= 8192 and
               128 <= x.max_new_tokens <= 768 and x.prompt.max() < 20480
               for x in a)
    with pytest.raises(ValueError):
        traffic.generate(spec, 1, 51, 20480, 8958)


def test_the_configuration_file_keeps_every_published_width():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-k2-ep32-v5e1.json")) as f:
        c = json.load(f)
    published = {"hidden_size": 7168, "intermediate_size": 18432,
                 "moe_intermediate_size": 2048, "kv_lora_rank": 512,
                 "q_lora_rank": 1536, "qk_nope_head_dim": 128,
                 "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "num_attention_heads": 64, "num_experts_per_tok": 8,
                 "routed_scaling_factor": 2.827, "rope_theta": 50000}
    assert {k: c[k] for k in published} == published
    assert c["published"] == {"num_hidden_layers": 61,
                              "n_routed_experts": 384, "vocab_size": 163840}
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (8, 12, 20480)
    e = c["engine"]
    assert e["pages_per_seq"] * e["page_size"] == 8960
    assert e["num_pages"] == e["num_slots"] * e["pages_per_seq"]


@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_walks_through_run_py(trace):
    p = run_py("--workload", "tiny-longdoc", "--seed", str(2**31 + 26),
               "--seconds", "6", "--trace", str(trace), "--rehearsal",
               "--manifest", TINY)
    assert p.returncode == 0, p.stderr[-2000:]
    res = last_line(p)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    got = {k.split(".", 1)[1] for k in res["metrics"]}
    if trace:       # no device trace on the CPU: the counter's metric only
        assert "moe_rows_per_expert" in got
        assert 0 < res["metrics"]["cpu_rehearsal.moe_rows_per_expert"][
            "value"] <= 3 * 4 / 4
    else:
        assert got == {"tpot_p50_ms", "setup_s"}


@pytest.mark.slow
@pytest.mark.parametrize("tool,argv,key", [
    ("check_limits.py", ["--seeds", "5,6", "--seconds", "4"], "limits_of"),
    ("step_split.py", ["--seed", "5", "--seconds", "4", "--trace", "0"],
     "step_split")])
def test_the_two_tools_walk_through(tool, argv, key):
    """``check_limits.py``: a fresh system a seed, the control beside the
    sound reading; ``step_split.py``: the program's own split of a step."""
    script = [sys.executable, os.path.join(ROOT, "benchmark", "tools", tool)]
    p = run_py("--workload", "tiny-longdoc", *argv, "--rehearsal",
               "--manifest", TINY, script=script)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(x) for x in p.stdout.strip().splitlines()]
    rows = [r for r in rows if key in r]
    assert rows and all(r["correct"] is True for r in rows)
    if key == "limits_of":
        assert [r["seed"] for r in rows] == [5, 6]
        assert all({"gap_mean", "flipped_share", "control_gap_mean",
                    "control_flipped_share"} <= set(r) for r in rows)
    else:
        assert rows[0]["moe_local_rows_per_step"] > 0
        assert rows[0]["decode_wait_ms"] > 0 and rows[0]["dispatches"] > 0

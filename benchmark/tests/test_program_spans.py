"""The program's spans: interval arithmetic and the three idle readers on a
hand-made trace, the five counter readers on hand-made totals, and one CPU run
of a tiny engine under ``jax.profiler`` that finds the ``engine.*`` events on
``/host:CPU`` with their ids."""
import importlib

import pytest

from benchmark import program_spans as P, trace as T


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


def test_interval_arithmetic():
    a = P.union([(3, 4), (0, 1), (0.5, 2), (5, 5)])
    assert a == [(0, 2), (3, 4)]
    assert P.intersect(a, [(1, 3.5), (3.75, 9)]) == [(1, 2), (3, 3.5),
                                                     (3.75, 4)]
    assert P.subtract([(0, 10)], [(1, 2), (4, 5), (9, 12)]) == [
        (0, 1), (2, 4), (5, 9)]
    assert P.subtract([(0, 2), (3, 4)], [(0, 2)]) == [(3, 4)]
    assert P.length([(0, 1), (2, 4.5)]) == pytest.approx(3.5)
    assert P.parse_name("engine.chunk_wait#step=3,rid=7#") == (
        "engine.chunk_wait", {"step": 3, "rid": 7})
    assert P.parse_name("engine.step") == ("engine.step", {})


def hand_made():
    """Two steps of 10 s. Device busy 1-4 and 5-9 in the first, 11-19 in the
    second: gaps (4, 5) and (9, 11). The first straddles ``chunk_wait`` (till
    4.5) and ``grow``: it is SPLIT. The second straddles ``reconcile`` (till
    9.5), the load generator between the steps (9.5-10.25, of which
    9.75-10 asleep) and the next step's ``admit``."""
    ops = [("a", 1.0, 3.0), ("b", 5.0, 4.0), ("c", 11.0, 8.0)]
    E = lambda name, a, b, **ids: (name, ids, a, b - a)    # noqa: E731
    spans = [
        E("engine.step", 0.0, 9.5, step=0),
        E("engine.admit", 0.0, 0.5, step=0),
        E("engine.chunk_prep", 0.5, 1.0, step=0, rid=1, cursor=0),
        E("engine.chunk_wait", 1.0, 4.5, step=0, rid=1, cursor=0),
        E("engine.grow", 4.5, 5.0, step=0),
        E("engine.dispatch", 5.0, 5.25, step=0),
        E("engine.decode_wait", 5.25, 9.25, step=0),
        E("engine.reconcile", 9.25, 9.5, step=0),
        E("engine.step", 10.25, 19.5, step=1),
        E("engine.admit", 10.25, 10.75, step=1),
        E("engine.dispatch", 10.75, 11.0, step=1),
        E("engine.decode_wait", 11.0, 19.25, step=1),
        # an idle poll: a step that holds no dispatch is not counted
        E("engine.step", 19.75, 19.8, step=2),
    ]
    bench = [("bench.step", 0.0, 9.5), ("bench.sleep", 9.75, 0.25),
             ("bench.step", 10.25, 9.25)]
    return T.Trace({0: ops}, {}, bench, 1.0, 19.0), spans


def test_a_gap_that_straddles_two_phases_is_split():
    tr, spans = hand_made()
    got = P.split(tr, spans)
    assert got["steps"] == 2
    assert got["idle_s"] == pytest.approx(3.0)
    # (4, 4.5) in chunk_wait + (9, 9.25) in decode_wait
    assert got["in_wait_s"] == pytest.approx(0.75)
    # (4.5, 5) grow + (9.25, 9.5) reconcile + (10.25, 11) admit, dispatch
    assert got["host_work_s"] == pytest.approx(1.5)
    assert got["asleep_s"] == pytest.approx(0.25)
    assert got["outside_s"] == pytest.approx(0.5)
    assert sum(got[k] for k in ("in_wait_s", "host_work_s", "asleep_s",
                                "outside_s")) == pytest.approx(got["idle_s"])
    # "the span that covers the middle" would have charged each gap whole
    rows = {r["span"]: r for r in P.phase_table(tr, spans)}
    assert rows["engine.chunk_wait"]["idle_s"] == pytest.approx(0.5)
    assert rows["engine.grow"]["idle_s"] == pytest.approx(0.5)
    assert rows["engine.step"]["idle_s"] == pytest.approx(2.25)
    assert rows["engine.step"]["count"] == 3
    assert list(rows)[-1] == "engine.step"


def test_the_three_idle_readers(monkeypatch):
    tr, spans = hand_made()
    monkeypatch.setattr(P, "newest_xplane", lambda *a: "made.xplane.pb")
    calls = []
    monkeypatch.setattr(P, "load_spans",
                        lambda path: calls.append(path) or spans)
    run = {"trace": tr}
    assert reader("idle_host_work_ms")(run) == pytest.approx(750.0)
    assert reader("idle_in_wait_ms")(run) == pytest.approx(375.0)
    assert reader("idle_outside_step_ms")(run) == pytest.approx(250.0)
    assert calls == ["made.xplane.pb"]          # three readers, one parse


@pytest.mark.parametrize("name", ["idle_host_work_ms", "idle_in_wait_ms",
                                  "idle_outside_step_ms"])
def test_none_without_a_device_plane_or_a_span(monkeypatch, name):
    tr, spans = hand_made()
    monkeypatch.setattr(P, "newest_xplane", lambda *a: "made.xplane.pb")
    monkeypatch.setattr(P, "load_spans", lambda path: spans)
    assert reader(name)({"trace": None}) is None
    # the CPU rehearsal: spans, and no /device:TPU plane
    assert reader(name)({"trace": T.Trace({}, {}, tr.spans, 0.0, 0.0)}) is None
    # a parent commit: a device plane, and no engine.* span
    monkeypatch.setattr(P, "load_spans", lambda path: [])
    assert reader(name)({"trace": tr}) is None


def test_the_counter_readers_and_a_program_without_the_phases():
    c = {"step_s.total": 10.0, "step_s.count": 5,
         "phase_admit_s.total": 0.1, "phase_admit_s.count": 5,
         "phase_chunk_prep_s.total": 0.3, "phase_chunk_prep_s.count": 2,
         "phase_chunk_wait_s.total": 3.0, "phase_chunk_wait_s.count": 2,
         "phase_grow_s.total": 0.2, "phase_grow_s.count": 5,
         "phase_sync_s.total": 0.1, "phase_sync_s.count": 3,
         "phase_dispatch_s.total": 0.1, "phase_dispatch_s.count": 4,
         "phase_decode_wait_s.total": 5.8, "phase_decode_wait_s.count": 4,
         "phase_reconcile_s.total": 0.3, "phase_reconcile_s.count": 4,
         "phase_post_s.total": 0.1, "phase_post_s.count": 5}
    run = {"counters_window": c}
    assert reader("sched_admit_ms")(run) == pytest.approx(20.0)
    assert reader("chunk_prep_ms")(run) == pytest.approx(150.0)
    assert reader("grow_sync_ms")(run) == pytest.approx(100.0)
    assert reader("reconcile_ms")(run) == pytest.approx(100.0)
    assert reader("host_own_share_pct")(run) == pytest.approx(12.0)
    parent = {"counters_window": {"step_host_s.total": 1.0, "dispatches": 4}}
    for name in ("sched_admit_ms", "chunk_prep_ms", "grow_sync_ms",
                 "reconcile_ms", "host_own_share_pct"):
        assert reader(name)(parent) is None


def test_engine_spans_land_on_the_host_plane_with_their_ids(tmp_path):
    """One CPU run of a tiny engine under ``jax.profiler``: the trace holds
    ``engine.step`` and its phases on ``/host:CPU``, children inside their
    step, the chunk's spans carrying the rid they prefill."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.models.llama import LlamaConfig, init_params
    from triton_dist_tpu.serving import ServingEngine
    from triton_dist_tpu.serving.metrics import PHASES

    cfg = dataclasses.replace(LlamaConfig.tiny(n_layers=1), dtype=jnp.float32)
    eng = ServingEngine(init_params(jax.random.key(0), cfg), cfg, num_slots=2,
                        page_size=8, num_pages=16, pages_per_seq=4,
                        prefill_chunk=8, decode_horizon=2)
    eng.submit(list(range(1, 12)), 3)           # compiles both programs
    eng.run(max_steps=100)
    T.start(str(tmp_path))
    rid = eng.submit(list(range(1, 20)), 4)
    eng.run(max_steps=100)
    path = T.stop(str(tmp_path))
    assert P.newest_xplane(str(tmp_path)) == path
    spans = P.load_spans(path)
    names = {s[0] for s in spans}
    assert names == {"engine.submit", "engine.step"} | {
        "engine." + p for p in PHASES}
    steps = [s for s in spans if s[0] == "engine.step"]
    for name, ids, start, dur in spans:
        if name == "engine.submit":
            assert ids == {"rid": rid}
            continue
        mine = [s for s in steps if s[1]["step"] == ids["step"]]
        assert len(mine) == 1
        assert mine[0][2] <= start and start + dur <= mine[0][2] + mine[0][3]
    chunks = [ids for name, ids, *_ in spans if name == "engine.chunk_prep"]
    assert [(c["rid"], c["cursor"]) for c in chunks] == [(rid, 0), (rid, 8),
                                                         (rid, 16)]
    # no device plane on the CPU: the readers have nothing to say
    assert P.split(T.load(path), spans) is None


def test_idle_under_a_wait_by_where_in_the_wait():
    """Launch (before the program begins), in the program (between its
    operations), readback (after it ended): one of each."""
    ops = [("a", 1.0, 1.0), ("b", 2.5, 1.5), ("c", 6.0, 1.0)]
    mods = [("jit_chunk(1)", 1.0, 3.0), ("jit_step(2)", 6.0, 1.0)]
    spans = [("engine.chunk_wait", {}, 0.5, 4.0),
             ("engine.decode_wait", {}, 5.0, 3.0)]
    tr = T.Trace({0: ops}, {0: mods}, [], 0.0, 8.0)
    chunk, decode = P.wait_table(tr, spans)
    assert chunk == {"span": "engine.chunk_wait", "launch_s": 0.0,
                     "in_program_s": pytest.approx(0.5), "between_s": 0.0,
                     "readback_s": pytest.approx(0.5)}
    assert decode == {"span": "engine.decode_wait",
                      "launch_s": pytest.approx(1.0), "in_program_s": 0.0,
                      "between_s": 0.0, "readback_s": 0.0}

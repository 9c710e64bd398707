"""Speculative multi-token decoding (ISSUE 20): model-free draft-verify
on the fused multistep machinery, held to the SAME bitwise trace
contract as every other serving lever.

THE claim under test: the bigram prompt-lookup drafter + the exact-match
greedy accept rule change ONLY the dispatch count — a committed token is
committed because a verify row fed the identical committed prefix
produced it, so the forced-preemption trace is BIT-IDENTICAL
to ``speculate=off`` on the colocated engine and across mesh sizes
n∈{1,2,4} at K∈{1,4}. The fast tier covers the colocated K sweep plus
the two cheapest mesh corners; the slow tier fills in the cross product.
The n=1 runs replay the whole trace; the runs across chips replay its
first four requests on the ``N4_PAGES`` pool, where they preempt too
(asserted in every sharded run, as ``test_spec_preempts_mid_verify_slot``
does on the colocated engine).

Also covered: the one-decode-program compile guard stays pinned across K
and spec on/off; the EOS/limit accept edges ride plain int arrays
(accept-exactly-remaining, EOS-is-always-last-committed, EOS inside a
rejected suffix); mid-run preemption of slots holding speculative KV
(the tight 9-page pool forces it) rewinds cleanly; a PR 7-style chaos
schedule (seeded digest skew through the restore rung) replays
bit-identically with speculation on; and the ``serving_spec_k`` tuned
key is sigcheck-gated into the PR 15 registry (a broken protocol is
REFUSED admission) and consumed by ``speculate="auto"``.

Wire dtype pinned to fp8, never "auto" (same caveat as the sharded
suite: auto resolves per rank count, a pinned wire makes every run
quantize identically).
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (N4_PAGES, N4_REQUESTS, N_REQUESTS, SHARDED_KW,
                      assert_replay_identical, seeded_trace, sharded_engine)
from triton_dist_tpu.models.llama import LlamaConfig, init_params
from triton_dist_tpu.serving import ServingEngine, ngram_draft, spec_accept
from triton_dist_tpu.serving.journal import ControlJournal
from triton_dist_tpu.serving.speculate import SPEC_K_DEFAULT, resolve_spec_k
from triton_dist_tpu.shmem import FaultPlan

pytestmark = [pytest.mark.serving, pytest.mark.spec]

MAX_STEPS = 100_000
WIRE = SHARDED_KW["wire_dtype"]
EOS = 5

# exactly one compiled program per path, regardless of K or spec on/off —
# speculation must not fork the program cache (the verify program IS the
# decode program; the drafter traces into it)
ONE_OF_EACH = {"decode_compiles": 1, "prefill_chunk_compiles": 1,
               "params_relaid_bytes": 0, "params_relaid_leaves": []}


@pytest.fixture(scope="module")
def llama_model():
    """Tiny-vocab Llama: greedy decode on a small model revisits states,
    so the prompt-lookup drafter lands real hits (accept > 1/dispatch)."""
    cfg = LlamaConfig(vocab_size=128, d_model=128, n_layers=1, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128,
                      dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _coloc(llama_model, **kw):
    cfg, params = llama_model
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 9)          # tight: forces preemption
    kw.setdefault("pages_per_seq", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("eos_id", EOS)
    return ServingEngine(params, cfg, **kw)


# -- the accept rule on plain int arrays (the EOS/limit edges) ---------------

def _accept(inp, nxt, ract, eos=None):
    return np.asarray(spec_accept(jnp.asarray(inp, jnp.int32),
                                  jnp.asarray(nxt, jnp.int32),
                                  jnp.asarray(ract, bool), eos_id=eos))


def test_accept_full_and_partial_match():
    inp = [[7, 3, 4, 9]]          # col 0 = authentic last token
    nxt = [[3, 4, 9, 2]]          # every draft matched its argmax
    assert _accept(inp, nxt, [[True] * 4]) == [4]
    nxt2 = [[3, 4, 1, 2]]         # draft col 3 (9) != argmax of col 2 (1)
    assert _accept(inp, nxt2, [[True] * 4]) == [3]
    nxt3 = [[8, 4, 9, 2]]         # first draft already wrong
    assert _accept(inp, nxt3, [[True] * 4]) == [1]


def test_accept_position_zero_always_commits_on_active_row():
    # the verify row at position 0 consumed the AUTHENTIC last token, so
    # its argmax is exactly what speculate=off would have produced
    m = _accept([[7, 99, 99, 99]], [[1, 2, 3, 4]], [[True] * 4])
    assert m == [1]
    # a fully inactive row (parked slot) commits nothing
    assert _accept([[7, 1, 1, 1]], [[1, 1, 1, 1]], [[False] * 4]) == [0]


def test_accept_exactly_remaining():
    # limit clamps mid-slab: remaining=2 admits exactly 2 commits even
    # though every draft matches — an accept burst can never overshoot
    # max_new_tokens or write KV past the budget
    inp = [[7, 3, 4, 9]]
    nxt = [[3, 4, 9, 2]]
    ract = [[True, True, False, False]]
    assert _accept(inp, nxt, ract) == [2]
    # and remaining=K accepts the whole slab (the boundary case)
    assert _accept(inp, nxt, [[True] * 4]) == [4]


def test_accept_eos_is_always_last_committed():
    # EOS produced at position 1 with matching drafts beyond it: the
    # accept loop freezes AFTER the emitting position, so m == 2 and EOS
    # is the LAST committed token — never inside the accepted prefix
    inp = [[7, 3, EOS, 9]]
    nxt = [[3, EOS, 9, 2]]
    m = _accept(inp, nxt, [[True] * 4], eos=EOS)
    assert m == [2]
    assert nxt[0][m[0] - 1] == EOS


def test_accept_eos_inside_rejected_suffix_never_commits():
    # the draft chain breaks at position 1 (draft 8 != argmax 3); the
    # EOS the verify row hallucinated at position 2 sits in the REJECTED
    # suffix and must not terminate the request
    inp = [[7, 8, 4, 9]]
    nxt = [[3, 4, EOS, 2]]
    m = _accept(inp, nxt, [[True] * 4], eos=EOS)
    assert m == [1]
    assert EOS not in nxt[0][:m[0]]


# -- the drafter -------------------------------------------------------------

def _draft(hist, hist_len, n):
    return np.asarray(ngram_draft(jnp.asarray(hist, jnp.int32),
                                  jnp.asarray(hist_len, jnp.int32), n))


def test_draft_bigram_replays_most_recent_match():
    # window ... 5 6 9 | 5 6: the bigram (5,6) recurs; the drafter must
    # replay what followed the MOST RECENT earlier occurrence (9, 5, 6)
    hist = [[0, 0, 5, 6, 9, 5, 6]]
    assert _draft(hist, [5], 3).tolist() == [[9, 5, 6]]


def test_draft_unigram_fallback_and_no_match():
    # no earlier bigram, but the final token 6 appears earlier: unigram
    # fallback replays its continuation
    hist = [[0, 0, 6, 9, 4, 3, 6]]
    assert _draft(hist, [5], 2).tolist() == [[9, 4]]
    # no earlier occurrence at all: repeat the last token (a deliberately
    # wrong draft the verify pass rejects — never a correctness input)
    hist2 = [[0, 0, 1, 2, 3, 4, 6]]
    assert _draft(hist2, [5], 2).tolist() == [[6, 6]]


def test_draft_zero_len_window_and_n_zero():
    assert _draft([[0] * 8], [0], 2).shape == (1, 2)
    assert _draft([[1, 2, 3, 4]], [4], 0).shape == (1, 0)


# -- K resolution ------------------------------------------------------------

def test_resolve_spec_k_ladder():
    assert resolve_spec_k(3) == 3
    assert resolve_spec_k("auto") == SPEC_K_DEFAULT   # no registry
    with pytest.raises(TypeError):
        resolve_spec_k(True)
    with pytest.raises(AssertionError):
        resolve_spec_k(0)
    with pytest.raises(AssertionError):
        resolve_spec_k("fast")


# -- colocated bit-identity + compile guard ----------------------------------

@pytest.fixture(scope="module")
def coloc_golden(llama_model):
    eng = _coloc(llama_model)
    tokens = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(N_REQUESTS))
    return tokens, eng.compile_stats


@pytest.mark.parametrize("k", [1, 4])
def test_spec_bit_identical_colocated(llama_model, coloc_golden, k):
    gold, gold_compiles = coloc_golden
    eng = _coloc(llama_model, speculate=k)
    tokens = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(N_REQUESTS))
    assert_replay_identical(tokens, gold, N_REQUESTS)
    # the compile guard: ONE decode program, flat across K and on/off
    assert eng.compile_stats == ONE_OF_EACH == gold_compiles
    c = eng.metrics.counters
    assert c["spec_dispatches"] == c["decode_steps"] > 0
    if k > 1:
        assert c["draft_tokens"] > 0


def test_spec_preempts_mid_verify_slot(llama_model, coloc_golden):
    """The tight 9-page pool preempts slots that hold speculative KV:
    rejected-suffix rewinds (free_tail) and whole-slot evictions compose
    — and the trace STILL matches the spec-off golden bitwise."""
    gold, _ = coloc_golden
    eng = _coloc(llama_model, speculate=4)
    tokens = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(N_REQUESTS))
    assert_replay_identical(tokens, gold, N_REQUESTS)
    c = eng.metrics.counters
    assert c["preemptions"] > 0, "pool never preempted — the test lost its bite"
    assert c["spec_rewinds"] > 0, "no draft was ever rejected at K=4"


def test_spec_accept_rate_on_repetitive_trace(llama_model):
    """On a shared-prefix trace the drafter must actually pay: accepted
    tokens per dispatch strictly above the 1.0 floor, dispatches strictly
    below the spec-off count for the SAME tokens."""
    rng = np.random.RandomState(3)
    tpl = rng.randint(1, 128, size=8).tolist()
    # one wave, landing at step 0, with long decode budgets: the dispatch
    # count is decode-bound, not arrival/prefill-bound — the axis
    # speculation moves
    arrivals = [(0, tpl + rng.randint(1, 128, size=2).tolist(), 16)
                for _ in range(4)]

    def run(spec):
        eng = _coloc(llama_model, num_pages=40, pages_per_seq=8,
                     speculate=spec)
        toks = eng.run(max_steps=MAX_STEPS, arrivals=arrivals)
        return toks, eng.metrics

    toks_off, m_off = run(None)
    toks_on, m_on = run(4)
    assert toks_on == toks_off
    acc = m_on.hist["accepted_per_dispatch"]
    assert acc.mean is not None and acc.mean > 1.0
    assert m_on.counters["dispatches"] < m_off.counters["dispatches"]
    assert m_on.counters["draft_accepted"] > 0


def test_spec_rejects_bad_knobs(llama_model):
    with pytest.raises(AssertionError, match="decode_horizon"):
        _coloc(llama_model, speculate=4, decode_horizon=2)
    with pytest.raises(AssertionError, match="spec_hist"):
        _coloc(llama_model, speculate=4, spec_hist=4)
    with pytest.raises(TypeError):
        _coloc(llama_model, speculate=True)


# -- sharded bit-identity matrix ---------------------------------------------
# fast tier: the two cheapest corners; slow tier completes n∈{1,2,4} ×
# K∈{1,4} (n=1 runs the forced-preemption trace, n>1 its first four on the
# N4_PAGES pool,
# against the one spec-off n=1 golden — the cross-mesh contract makes a
# single golden serve every mesh size).

_FAST = [(1, 1, 1, 4), (1, 1, 2, 4)]
_SLOW = [(1, 1, 1, 1), (1, 1, 2, 1), (1, 2, 2, 1), (1, 2, 2, 4)]


@pytest.fixture(scope="module")
def sharded_golden(moe_model):
    eng = sharded_engine(moe_model, 1, 1, 1)
    return eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(N_REQUESTS))


def _run_matrix_case(moe_model, sharded_golden, tp, sp, ep, k):
    n, kw = N_REQUESTS, {}
    if tp * sp * ep > 1:
        n, kw = N4_REQUESTS, {"num_pages": N4_PAGES}
    eng = sharded_engine(moe_model, tp, sp, ep, speculate=k, **kw)
    tokens = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(n))
    assert_replay_identical(tokens, sharded_golden, n)
    assert eng.metrics.counters["preemptions"] >= 1
    assert eng.compile_stats == ONE_OF_EACH, eng.compile_stats
    assert eng.spec_k == k


@pytest.mark.mesh
@pytest.mark.parametrize("tp,sp,ep,k", _FAST)
def test_spec_bit_identical_sharded(moe_model, sharded_golden, tp, sp, ep, k):
    _run_matrix_case(moe_model, sharded_golden, tp, sp, ep, k)


@pytest.mark.mesh
@pytest.mark.slow
@pytest.mark.parametrize("tp,sp,ep,k", _SLOW)
def test_spec_bit_identical_sharded_full(moe_model, sharded_golden,
                                         tp, sp, ep, k):
    _run_matrix_case(moe_model, sharded_golden, tp, sp, ep, k)


# -- chaos replay with speculation on ----------------------------------------

@pytest.mark.mesh
def test_chaos_digest_skew_replay_with_spec(moe_model, sharded_golden):
    """A seeded fault schedule (transient digest skew through the PR 9
    restore rung) replayed with speculation ON across two chips (the
    trace's first four requests, preempting on the N4_PAGES pool): the
    divergence is absorbed exactly once, the restore re-seeds every
    drafter window from the replayed prompts, and the tokens still match
    the fault-free spec-off n=1 golden."""
    eng = sharded_engine(moe_model, 1, 1, 2, journal=ControlJournal(),
                         checkpoint_every=2, digest_every=1, speculate=4,
                         num_pages=N4_PAGES,
                         fault_plan=FaultPlan(seed=5, digest_skew_at=(5,)))
    toks = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(N4_REQUESTS))
    c = eng.metrics.counters
    assert c["digest_recoveries"] == 1
    assert c["faults_injected"] >= 1
    assert c["preemptions"] >= 1
    assert_replay_identical(toks, sharded_golden, N4_REQUESTS)


# -- tuned-key gate ----------------------------------------------------------

def test_spec_k_tuned_key_gated_and_consumed(moe_model):
    """The draft length is a sigcheck-gated registry key: a clean config
    admits (checked=True) and ``speculate="auto"`` consumes it; admission
    with a broken protocol runner — the seg_dropped_signal gallery
    kernel, the K-scaled EP a2a's own hazard — is REFUSED with the
    under_signal finding attached."""
    from triton_dist_tpu.analysis.gallery import GALLERY
    from triton_dist_tpu.aot.registry import (RegistryAdmissionError,
                                              TunedConfigRegistry, TunedKey,
                                              set_default_registry)

    reg = TunedConfigRegistry()
    key = TunedKey("serving_spec_k", mesh_shape=(1, 1, 1), dtype="float32",
                   shape_bucket=((2,),))
    reg.put(key, 2)                       # gate replays the 2x-row a2a
    assert reg.checked(key)

    with pytest.raises(RegistryAdmissionError) as exc:
        reg.put(TunedKey("serving_spec_k", mesh_shape=(1, 1, 2),
                         dtype="float32", shape_bucket=((2,),)), 4,
                run=GALLERY["seg_dropped_signal"].run)
    assert "under_signal" in exc.value.finding_kinds
    assert len(reg) == 1                  # the refused config never landed

    set_default_registry(reg)
    try:
        eng = sharded_engine(moe_model, 1, 1, 1, speculate="auto",
                             spec_bucket=2)
        assert eng.spec_k == 2            # the tuned K won over default 4
        eng2 = sharded_engine(moe_model, 1, 1, 1, speculate=3, spec_bucket=2)
        assert eng2.spec_k == 3           # explicit overrides the registry
        eng3 = sharded_engine(moe_model, 1, 1, 1, speculate="auto",
                              spec_bucket=0)
        assert eng3.spec_k == SPEC_K_DEFAULT   # bucket miss → default
    finally:
        set_default_registry(None)


def test_spec_bucket_of_is_pure_arithmetic():
    from triton_dist_tpu.serving.workload import (WorkloadSpec,
                                                  spec_bucket_of)
    assert spec_bucket_of(WorkloadSpec(prefixes=0)) == 0
    assert spec_bucket_of(WorkloadSpec(prefixes=4, zipf=1.1)) == 2
    assert spec_bucket_of(WorkloadSpec(prefixes=16, zipf=1.5)) == 2
    assert spec_bucket_of(WorkloadSpec(prefixes=16, zipf=1.1)) == 1

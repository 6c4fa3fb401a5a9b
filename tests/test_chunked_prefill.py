"""Stall-free chunked-prefill scheduling: the per-step prefill budget,
resumable ``PREFILLING`` cursors, policy parity, preemption/abort landing
MID-prefill, and the backlog/stall observability surfaces.

Complements tests/test_overlap.py (which proves overlap/sync byte parity
under the budgeted scheduler); here the focus is the budget mechanics
themselves and the request lifecycle around an interrupted prefill."""

import pytest

from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.engine.request import RequestStatus
from smg_tpu.models.config import tiny_test_config
from smg_tpu.protocols.sampling import SamplingParams
from smg_tpu.tokenizer import MockTokenizer

BUDGET = 64


def make_engine(overlap=False, policy="stall-free", num_pages=256,
                max_seq_len=512, prefix_cache=True, **sched_kw) -> Engine:
    cfg = EngineConfig(
        model=tiny_test_config(),
        cache=CacheConfig(page_size=16, num_pages=num_pages, auto_size=False,
                          dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=8,
            max_seq_len=max_seq_len,
            max_prefill_tokens=BUDGET,
            prefill_token_buckets=(16, 32, 64),
            decode_batch_buckets=(4, 8),
            overlap_schedule=overlap,
            prefill_mix_policy=policy,
            enable_prefix_cache=prefix_cache,
            **sched_kw,
        ),
        dtype="float32",
    )
    return Engine(cfg, tokenizer=MockTokenizer())


def greedy(max_new=8, **kw) -> SamplingParams:
    return SamplingParams(temperature=0.0, max_new_tokens=max_new,
                          ignore_eos=True, **kw)


def run_streams(engine: Engine, jobs: list) -> dict:
    chunks: dict[str, list] = {rid: [] for rid, _, _ in jobs}
    done: set[str] = set()

    def cb(out):
        chunks[out.rid].append(out)
        if out.finished:
            done.add(out.rid)

    for rid, prompt, sampling in jobs:
        engine.submit(prompt, sampling, rid=rid, on_output=cb)
    for _ in range(5000):
        if len(done) == len(jobs):
            while engine.scheduler.has_work():
                engine.step()
            break
        engine.step()
    else:
        raise TimeoutError(f"jobs stuck: {engine.loads()}")
    out = {}
    for rid, _, _ in jobs:
        toks = [t for c in chunks[rid] for t in c.new_token_ids]
        lps = [round(x, 4) for c in chunks[rid] for x in c.logprobs]
        last = chunks[rid][-1]
        out[rid] = (toks, last.finish_reason, lps)
    return out


LONG = list(range(5, 205))  # 200 tokens -> 4 chunks under the 64 budget
SHORT = list(range(300, 340))


def test_budgeted_vs_legacy_greedy_parity():
    """Per-request token streams are byte-identical between budgeted
    (stall-free) and legacy drain-the-queue scheduling at temp 0."""
    jobs = [
        ("long", LONG, greedy(8)),
        ("s0", SHORT, greedy(12)),
        ("s1", list(range(400, 425)), greedy(10)),
    ]
    a = run_streams(make_engine(policy="stall-free"), jobs)
    b = run_streams(make_engine(policy="throughput"), jobs)
    assert a == b, f"budgeted diverged from legacy:\n{a}\nvs\n{b}"


def test_per_step_budget_is_respected():
    """Stall-free: no step computes more than ``max_prefill_tokens`` of
    prefill; legacy: the long prompt's whole remainder lands in one step."""
    for policy, bound in (("stall-free", BUDGET), ("throughput", len(LONG))):
        eng = make_engine(policy=policy)
        eng.submit(LONG, greedy(4), rid="long")
        deltas = []
        last = 0
        for _ in range(40):
            eng.step()
            cur = eng.scheduler.num_prefill_tokens
            deltas.append(cur - last)
            last = cur
            if not eng.scheduler.has_work():
                break
        assert max(deltas) <= bound
        if policy == "throughput":
            assert max(deltas) == len(LONG)  # the drain really is one step
        else:
            assert sum(1 for d in deltas if d) >= 4  # spread across steps


def test_decode_runs_every_step_during_long_prefill():
    """The stall-free core property: while a long prompt chunks in, the
    running lane receives tokens EVERY step — never a multi-chunk gap."""
    eng = make_engine(policy="stall-free")
    got: list = []
    eng.submit(SHORT, greedy(40), rid="s",
               on_output=lambda o: got.append(len(o.new_token_ids)))
    eng.step()  # admit + first decode
    eng.submit(LONG, greedy(4), rid="long")
    sched = eng.scheduler
    while (req := sched.requests.get("long")) is not None \
            and req.status is not RequestStatus.RUNNING and not req.is_finished:
        n_before = len(got)
        eng.step()
        assert len(got) > n_before and got[-1] > 0, \
            "decode lane stalled during chunked prefill"
    while sched.has_work():
        eng.step()


def test_prefilling_cursor_advances_across_steps():
    eng = make_engine(policy="stall-free")
    eng.submit(LONG, greedy(4), rid="long")
    sched = eng.scheduler
    seen = []
    for _ in range(3):
        eng.step()
        req = sched.requests["long"]
        if req.status is RequestStatus.PREFILLING:
            seen.append(req.prefill_pos)
            assert req.seq_len == req.prefill_pos
            assert req.slot is not None  # holds its slot between chunks
    assert seen == [64, 128, 192]
    while sched.has_work():
        eng.step()


@pytest.mark.parametrize("overlap", [False, True])
def test_abort_mid_prefill_releases_pages_and_locks(overlap):
    eng = make_engine(overlap=overlap)
    # prime the radix with a short request so the long one holds a LOCKED
    # radix node through its prefill (the lock-release path under test)
    eng.generate(prompt_ids=LONG[:40], sampling=greedy(2))
    eng.submit(LONG, greedy(8), rid="long")
    eng.step()
    req = eng.scheduler.requests["long"]
    assert req.status is RequestStatus.PREFILLING
    assert req.radix_node is not None  # shared-prefix lock held mid-prefill
    assert eng.abort("long")
    sched = eng.scheduler
    assert all(s is None for s in sched.slots)
    # every page is either back in the pool or (unlocked) in the radix cache
    held = sched.radix.num_cached_pages
    assert sched.pool.free_count + held == eng.runner.spec.num_pages - 1
    # locks released: the idle cache can be flushed completely
    assert eng.flush_cache()
    assert sched.pool.free_count == eng.runner.spec.num_pages - 1
    # and the engine still serves
    r = eng.generate(prompt_ids=SHORT, sampling=greedy(4))
    assert len(r.token_ids) == 4


def test_abort_waiting_over_budget_request():
    """Abort a request still WAITING because the budget never reached it."""
    eng = make_engine(policy="stall-free")
    eng.submit(LONG, greedy(4), rid="long")
    eng.submit(SHORT, greedy(4), rid="w")
    eng.step()  # long takes the whole budget; w still waiting
    assert eng.scheduler.requests["w"].status is RequestStatus.WAITING
    assert eng.abort("w")
    assert "w" not in eng.scheduler.requests
    while eng.scheduler.has_work():
        eng.step()
    assert eng.scheduler.requests.get("long") is None  # long unaffected


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_preempt_mid_prefill_resumes_with_identical_stream(prefix_cache):
    """A mid-prefill preemption victim must produce the SAME final stream as
    an uninterrupted run (greedy).  With the radix cache on, readmission
    resumes from the banked cursor instead of recomputing."""
    ref = run_streams(
        make_engine(prefix_cache=prefix_cache), [("long", LONG, greedy(8))]
    )["long"]

    eng = make_engine(prefix_cache=prefix_cache)
    got: dict = {"long": []}
    eng.submit(LONG, greedy(8), rid="long",
               on_output=lambda o: got["long"].append(o))
    eng.step()
    eng.step()  # two chunks in: cursor at 128
    sched = eng.scheduler
    req = sched.requests["long"]
    assert req.status is RequestStatus.PREFILLING and req.prefill_pos == 128
    sched._preempt(req)  # the path a page-starved decode lane would take
    assert req.status is RequestStatus.PREEMPTED
    assert req.slot is None and not req.owned_pages and not req.shared_pages
    assert req.prefill_pos == 0 and req.seq_len == 0
    if prefix_cache:
        # computed chunks banked for resume: 128 tokens = 8 pages
        assert sched.radix.num_cached_pages >= 8
    while sched.has_work():
        eng.step()
    toks = [t for c in got["long"] for t in c.new_token_ids]
    lps = [round(x, 4) for c in got["long"] for x in c.logprobs]
    assert (toks, got["long"][-1].finish_reason, lps) == ref
    if prefix_cache:
        # readmission resumed from the cursor via a radix hit, not a restart
        assert eng.loads()["cached_prompt_tokens"] >= 128


def test_preemption_under_pressure_lands_mid_prefill():
    """Organic page pressure: a decode lane's growth preempts the PREFILLING
    request; it resumes and completes with a correct stream."""
    # pool sized so the long admission leaves NOTHING free (1 garbage + 3
    # for the short lane + 13 for the long prompt = 17): the short lane's
    # first page-boundary crossing must preempt the prefiller
    eng = make_engine(num_pages=17, max_seq_len=256, watermark_pages=0)
    ref = run_streams(
        make_engine(num_pages=64, max_seq_len=256), [("long", LONG, greedy(6))]
    )["long"]
    got: dict = {"long": [], "s": []}
    # 47-token prompt = 3 pages, crosses into page 4 after one decode step
    eng.submit(list(range(400, 447)), greedy(20), rid="s",
               on_output=lambda o: got["s"].append(o))
    eng.step()
    eng.submit(LONG, greedy(6), rid="long",
               on_output=lambda o: got["long"].append(o))
    sched = eng.scheduler
    saw_prefilling_preempt = False
    for _ in range(400):
        n_pre = sched.num_preemptions
        eng.step()
        # the only preemptible victim is "long" (s is the requester); a
        # preemption before long produced ANY token landed mid-prefill —
        # a RUNNING victim would already have its first sampled token
        if sched.num_preemptions > n_pre and not got["long"]:
            saw_prefilling_preempt = True
        if not sched.has_work():
            break
    assert sched.num_preemptions >= 1
    assert saw_prefilling_preempt, "preemption never landed mid-prefill"
    toks = [t for c in got["long"] for t in c.new_token_ids]
    assert toks == ref[0]
    assert [o.finished for o in got["s"]][-1]


def test_prefill_extend_keeps_its_page_table():
    """``prefill_extend`` returns before its program has run, and the CPU
    client aliases a 64-byte-aligned host array instead of copying it: a row
    of the scheduler's table that a preemption zeroes in the same step would
    send the chunk's KV to the garbage page.  This is what made the test
    above pass or fail by where malloc had put the table."""
    import numpy as np

    r = make_engine(num_pages=17, max_seq_len=256).runner
    mp = r.max_pages_per_seq
    raw = np.zeros(4 * mp + 16, np.int32)
    off = (-raw.ctypes.data % 64) // 4
    table = raw[off:off + 4 * mp].reshape(4, mp)  # rows on 64-byte boundaries
    table[1, :4] = [1, 2, 3, 4]
    r.prefill_extend(list(range(5, 69)), 0, table[1])
    table[1][:] = 0  # as ``Scheduler._preempt`` does
    k = np.asarray(r.k_cache)
    assert np.abs(k[:, 1:5]).sum() > 0, "the chunk's KV did not reach its pages"
    assert not np.abs(k[:, 5:]).any()


def test_loads_exposes_prefill_backlog():
    eng = make_engine(policy="stall-free")
    eng.submit(LONG, greedy(4), rid="long")
    eng.submit(SHORT, greedy(4), rid="w")
    eng.step()
    loads = eng.loads()
    assert loads["num_prefilling"] == 1
    assert loads["prefill_inflight_tokens"] == len(LONG) - 64
    assert loads["prefill_backlog_tokens"] == (len(LONG) - 64) + len(SHORT)
    # un-prefilled inflight tokens count as queued work for dp routing
    assert loads["queued_tokens"] >= loads["prefill_backlog_tokens"]
    while eng.scheduler.has_work():
        eng.step()
    loads = eng.loads()
    assert loads["num_prefilling"] == 0
    assert loads["prefill_inflight_tokens"] == 0
    assert loads["prefill_backlog_tokens"] == 0


def test_step_and_stall_metrics_exported():
    from prometheus_client import generate_latest

    eng = make_engine(policy="stall-free")
    run_streams(eng, [("long", LONG, greedy(6)), ("s", SHORT, greedy(16))])
    text = generate_latest(eng.metrics.registry).decode()
    assert 'smg_engine_steps_total{kind="mixed"}' in text
    assert "smg_engine_decode_stall_seconds_total" in text
    assert "smg_engine_prefill_inflight_tokens" in text
    # a long prompt admitted beside a decoding lane yields mixed steps and
    # attributes its in-step delay to the stall counter
    for line in text.splitlines():
        if line.startswith('smg_engine_steps_total{kind="mixed"}'):
            assert float(line.split()[-1]) >= 1


def test_partial_chunk_packs_leftover_budget():
    """Two prompts whose combined remainder exceeds the budget: the second
    starts with the leftover as a partial resumable chunk (not deferred)."""
    eng = make_engine(policy="stall-free")
    eng.submit(list(range(5, 53)), greedy(4), rid="a")  # 48 tokens
    eng.submit(list(range(100, 148)), greedy(4), rid="b")  # 48 tokens
    eng.step()
    sched = eng.scheduler
    ra, rb = sched.requests["a"], sched.requests["b"]
    assert ra.status is RequestStatus.RUNNING  # fit the budget, sampled
    assert rb.status is RequestStatus.PREFILLING  # packed the leftover 16
    assert rb.prefill_pos == 16
    while sched.has_work():
        eng.step()
    assert not sched.requests


def test_zero_and_overlong_heads_do_not_burn_budget():
    eng = make_engine(policy="stall-free", max_seq_len=256)
    outs = {}

    def cb(o):
        outs.setdefault(o.rid, []).append(o)

    eng.submit(list(range(5, 300)), greedy(4), rid="toolong", on_output=cb)
    eng.submit(SHORT, SamplingParams(max_new_tokens=0), rid="zero",
               on_output=cb)
    eng.submit(SHORT, greedy(4), rid="ok", on_output=cb)
    eng.step()
    assert outs["toolong"][-1].finish_reason == "error"
    assert outs["zero"][-1].finish_reason == "length"
    # the real request admitted and prefilled in the same step
    assert eng.scheduler.requests["ok"].status is RequestStatus.RUNNING
    while eng.scheduler.has_work():
        eng.step()
    assert outs["ok"][-1].finished

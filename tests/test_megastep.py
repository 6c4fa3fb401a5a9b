"""On-device megastep decode: the scan-fused K-step loop with device-side
stop detection must be BYTE-IDENTICAL to K=1 at any temperature.

The invariant chain under test:

- every megastep column folds the exact sampling key the single-step path
  would have folded at that global step (in-loop folds);
- the device done mask (EOS/stop-token id sets + per-lane length limits)
  early-exits the loop at the first finishing lane;
- the host trims acceptance at the earliest finish column (the K=1
  batch-recomposition point) and rewinds the unused key folds;
- the overlap pipeline's chained lookahead frames and the quarantine
  recovery path rewind a whole discarded horizon's folds (LIFO).

Any slip in any of these flips a temp-0.8 stream, so the K-sweep parity
tests are the gate.  The adaptive horizon controller and the one-trace-per
-batch-bucket compile guarantee ride along."""

import pytest

from smg_tpu.engine.config import SchedulerConfig
from smg_tpu.faults import FAULTS
from smg_tpu.protocols.sampling import SamplingParams

from tests.test_overlap import greedy, make_engine, run_streams, streams_of


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    FAULTS.clear()


def assert_stream_parity(got, base, what=""):
    """Byte-identical token streams, text, finish reasons, and matched
    stops; logprobs within 1e-3.  K=1 and K>1 run DIFFERENT compiled loop
    widths, and XLA's reduction order inside the sampler's logsumexp is not
    bit-stable across program shapes — tokens are exact (argmax), the
    reported logprob can move a few 1e-5."""
    assert set(got) == set(base)
    for rid in base:
        bt, btx, br, bm, bl = base[rid]
        gt, gtx, gr, gm, gl = got[rid]
        assert (gt, gtx, gr, gm) == (bt, btx, br, bm), (
            f"{what}: stream for {rid!r} diverged:\n{got[rid]}\nvs\n{base[rid]}"
        )
        assert len(gl) == len(bl) and all(
            abs(a - b) < 1e-3 for a, b in zip(gl, bl)
        ), f"{what}: logprobs for {rid!r} drifted past tolerance"


MIXED_JOBS = [
    # greedy, sampled, and penalty lanes; staggered lengths so finishes land
    # at many different columns inside a K>1 horizon (the penalty lane also
    # pins the on-device count updates across trims and discarded frames)
    ("g0", list(range(5, 25)), greedy(13)),
    ("s0", list(range(30, 55)),
     SamplingParams(temperature=0.8, top_k=40, top_p=0.95,
                    max_new_tokens=9, ignore_eos=True)),
    ("s1", list(range(60, 75)),
     SamplingParams(temperature=0.8, min_p=0.02, max_new_tokens=5,
                    ignore_eos=True)),
    ("p0", list(range(80, 100)),
     SamplingParams(temperature=0.8, frequency_penalty=0.4,
                    max_new_tokens=11, ignore_eos=True)),
]


@pytest.fixture(scope="module")
def k1_baseline():
    """The K=1 stream set every megastep configuration must reproduce."""
    return run_streams(make_engine(True), MIXED_JOBS)


def arrival_streams(engine, jobs, points=None, start=2):
    """``run_streams`` with arrivals mid-stream: the first ``start`` jobs are
    submitted together and each later one while they decode.  Without
    ``points`` a job is submitted after a step that reported a finish, as a
    closed loop's caller does, and the point of the streams at which it was
    admitted is recorded: every other request's tokens so far, read when
    its first token comes.  With ``points`` (another run's) it is submitted
    before the step that finds the streams at that point, which a schedule
    of one column a step passes whatever the frames of the other run were.
    Returns the streams and the points."""
    chunks: dict[str, list] = {rid: [] for rid, _, _ in jobs}
    seen: dict[str, dict] = {}
    count = lambda: {rid: sum(len(c.new_token_ids) for c in cs)
                     for rid, cs in chunks.items() if cs}

    def cb(out):
        if not chunks[out.rid]:
            seen[out.rid] = count()
        chunks[out.rid].append(out)

    due = list(jobs)

    def submit():
        rid, prompt, sampling = due.pop(0)
        engine.submit(prompt, sampling, rid=rid, on_output=cb)

    for _ in range(start):
        submit()
    for _ in range(5000):
        if not due and not engine.scheduler.has_work():
            break
        if points is not None and due and count() == points[due[0][0]]:
            submit()
        finishes = sum(1 for o in engine.step() if o.finished)
        if points is None:
            for _ in range(min(finishes, len(due))):
                submit()
    else:
        raise TimeoutError(f"jobs stuck: {engine.loads()}")
    return streams_of(chunks), seen


# tier-1 wall-clock: K=4 (both schedules) is the in-band gate; the K∈{2,8}
# variants ride the slow lane with the exhaustive sweep (ROADMAP practical
# note — the full suite must fit the 870s harness timeout)
@pytest.mark.parametrize("horizon", [
    pytest.param(2, marks=pytest.mark.slow), 4,
    pytest.param(8, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("overlap", [True, False])
@pytest.mark.parametrize("traffic", ["at_once", "arrivals"])
def test_k_sweep_byte_identical_to_k1(horizon, overlap, traffic, k1_baseline):
    if traffic == "at_once":
        got = run_streams(make_engine(overlap, decode_horizon=horizon), MIXED_JOBS)
        base = k1_baseline
    else:
        # two slots and a caller's next request after every finish: a later
        # job is admitted between two frames of the others, wherever their
        # ends fall at this K, and the one-column schedule given the same
        # arrivals has the same streams
        got, points = arrival_streams(
            make_engine(overlap, decode_horizon=horizon, max_batch=2), MIXED_JOBS)
        base, _ = arrival_streams(make_engine(False, max_batch=2), MIXED_JOBS, points)
    assert_stream_parity(got, base,
                         f"megastep K={horizon} overlap={overlap} {traffic}")


def test_eos_and_stop_token_finish_inside_horizon():
    """Natural EOS and stop_token_ids finishing mid-horizon: the device done
    mask must end the horizon at that column and the stream must equal K=1
    (including finish_reason/matched_stop)."""
    probe = run_streams(
        make_engine(False), [("p", list(range(5, 15)), greedy(6))]
    )["p"][0]
    stop_tok = probe[3]
    jobs = [
        ("e0", list(range(5, 15)),
         SamplingParams(temperature=0.0, max_new_tokens=32)),  # natural EOS
        ("e1", list(range(5, 15)),
         SamplingParams(temperature=0.0, max_new_tokens=32, ignore_eos=True,
                        stop_token_ids=[stop_tok])),
    ]
    base = run_streams(make_engine(True), jobs)
    e8 = make_engine(True, decode_horizon=8)
    got = run_streams(e8, jobs)
    assert_stream_parity(got, base, "eos/stop-token inside horizon")
    assert got["e1"][2] == "stop" and got["e1"][3] == stop_tok
    # the finishes landed mid-horizon, so the device loop must have exited
    # early rather than computing the full K columns
    assert e8.scheduler.num_megastep_early_exits > 0


def test_max_tokens_finish_inside_horizon_wastes_nothing():
    """A length finish at max_new % K != 0 ends the horizon mid-frame.  In
    the synchronous schedule (no lookahead frames to discard) the device
    early exit must make the megastep completely waste-free: every computed
    column is an accepted column."""
    jobs = [(f"m{i}", list(range(5 + 20 * i, 25 + 20 * i)), greedy(9 + i))
            for i in range(3)]
    base = run_streams(make_engine(False), jobs)
    e8 = make_engine(False, decode_horizon=8)
    got = run_streams(e8, jobs)
    assert_stream_parity(got, base, "max-tokens inside horizon")
    assert e8.scheduler.num_megastep_early_exits > 0
    assert e8.scheduler.num_wasted_decode_tokens == 0
    for rid, (toks, _t, reason, _m, _l) in got.items():
        assert reason == "length" and len(toks) == 9 + int(rid[1])


def test_stop_string_forces_horizon_one():
    """Stop strings match at the ENGINE layer after detokenization — the
    device done mask cannot see them — so any lane carrying one forces K=1
    (the same conservative rule as the overlap sync-forcing paths), and the
    stream still equals the K=1 engine's."""
    probe = run_streams(
        make_engine(False), [("p", list(range(60, 90)), greedy(8))]
    )["p"][0]
    stop_word = f"w{probe[2]}"
    jobs = [
        ("r0", list(range(60, 90)),
         SamplingParams(temperature=0.0, max_new_tokens=16, ignore_eos=True,
                        stop=[stop_word])),
        ("r1", list(range(7, 31)), greedy(14)),
    ]
    base = run_streams(make_engine(True), jobs)
    e8 = make_engine(True, decode_horizon=8)
    got = run_streams(e8, jobs)
    assert_stream_parity(got, base, "stop-string forced K=1")
    assert got["r0"][2] == "stop" and not got["r0"][1].endswith(stop_word)

    # white-box (reusing the drained K=8 engine): a lane set containing a
    # stop-string request picks (1, 1)
    e8.submit(list(range(60, 90)),
              SamplingParams(temperature=0.0, max_new_tokens=16,
                             ignore_eos=True, stop=[stop_word]), rid="w")
    for _ in range(2):
        e8.step()
    active = e8.scheduler._decode_active()
    assert active and e8.scheduler._pick_horizon(active) == (1, 1)
    while e8.scheduler.has_work():
        e8.step()


def test_chunked_prefill_admission_mid_horizon_parity():
    """A multi-chunk prompt admits under the per-step budget while K=4
    megasteps are in flight: resumable (fold-free) chunks must leave the
    in-loop fold sequence untouched and the final sampling chunk must order
    its fold before the next horizon's — any slip flips the temp-0.8
    streams."""
    jobs = [
        ("long", list(range(5, 185)),
         SamplingParams(temperature=0.8, top_k=40, max_new_tokens=10,
                        ignore_eos=True)),
        ("c0", list(range(200, 240)),
         SamplingParams(temperature=0.8, max_new_tokens=12, ignore_eos=True)),
        ("c1", list(range(250, 275)), greedy(9)),
    ]
    base = run_streams(make_engine(True), jobs)
    for overlap in (True, False):
        got = run_streams(make_engine(overlap, decode_horizon=4), jobs)
        assert_stream_parity(got, base,
                             f"chunked admission (overlap={overlap})")


def test_quarantine_rewind_across_megastep():
    """A poison decode step at K=4 quarantines the newest lane; the retry
    must refold the SAME keys the K=1 engine's recovery folds, which only
    holds if drop_inflight rewinds the whole discarded horizon's folds
    (frame.folds, not 1).  Survivor streams are compared between the
    faulted K=4 and faulted K=1 runs at temp 0.8 — key-sensitive."""

    def run(horizon: int) -> dict:
        eng = make_engine(True, decode_horizon=horizon)
        jobs = [
            (f"q{i}", list(range(5 + 30 * i, 25 + 30 * i)),
             SamplingParams(temperature=0.8, top_k=50, max_new_tokens=8,
                            ignore_eos=True))
            for i in range(3)
        ]
        chunks: dict = {rid: [] for rid, _, _ in jobs}
        for rid, prompt, sp in jobs:
            eng.submit(prompt, sp, rid=rid,
                       on_output=lambda o, rid=rid: chunks[rid].append(o))
        eng.step()  # admit + prefill all three
        FAULTS.arm("engine.decode_step", mode="once")
        for _ in range(200):
            if all(v and v[-1].finished for v in chunks.values()):
                break
            eng.step()
        while eng.scheduler.has_work():
            eng.step()
        FAULTS.clear()
        assert eng.scheduler.num_quarantined == 1
        return {
            rid: ([t for o in v for t in o.new_token_ids],
                  v[-1].finish_reason)
            for rid, v in chunks.items()
        }

    k4, k1 = run(4), run(1)
    # newest admission (q2) is blamed in both
    assert k4["q2"][1] == "error" and k1["q2"][1] == "error"
    for rid in ("q0", "q1"):
        assert k4[rid] == k1[rid], f"survivor {rid} diverged across megastep"


def test_static_horizon_page_pressure_parity():
    """The page-headroom clamp applies to the STATIC path too: a fixed K=8
    under a tight page pool must not make _ensure_seq_capacity preempt a
    peer the K=1 schedule would never touch (a preemption refolds the
    victim's keys — temp-0.8 streams would diverge).  The pool here drains
    to ~zero as three lanes grow, so unclamped K=8 launches would demand
    pages the pool cannot give without eviction."""
    jobs = [
        (f"pp{i}", list(range(5 + 40 * i, 40 + 40 * i)),
         SamplingParams(temperature=0.8, top_k=50, max_new_tokens=40,
                        ignore_eos=True))
        for i in range(3)
    ]
    kw = dict(num_pages=16, max_batch=4, max_seq_len=128)
    base = run_streams(make_engine(True, **kw), jobs)
    got = run_streams(make_engine(True, decode_horizon=8, **kw), jobs)
    assert_stream_parity(got, base, "static K=8 under page pressure")


def test_steady_state_guard_clean_at_k8():
    """Steady-state megastep decode at K=8: 0 recompiles and no implicit
    transfers across guarded steps (the per-launch K scalar, positions, and
    the in-loop fold's step counter all ride explicit uploads)."""
    from smg_tpu.analysis.runtime_guards import steady_state_guard

    eng = make_engine(True, decode_horizon=8, max_seq_len=512, num_pages=256)
    done: dict = {}
    prompts = [[(7 * i + j) % 90 + 5 for j in range(16)] for i in range(2)]
    for i, p in enumerate(prompts):
        eng.submit(p, greedy(200), rid=f"r{i}",
                   on_output=lambda o, i=i: done.setdefault(i, []).append(o))
    for _ in range(6):  # warmup: prefill + pipeline priming + compiles
        eng.step()
    with steady_state_guard() as cc:
        for _ in range(8):
            eng.step()
    assert cc.count == 0
    while eng.scheduler.has_work():
        eng.step()
    lens = {i: sum(len(o.new_token_ids) for o in v) for i, v in done.items()}
    assert lens == {0: 200, 1: 200}


def test_one_trace_serves_every_k():
    """One megastep trace per batch bucket: the compiled loop width is the
    horizon cap and the per-launch K rides a device scalar, so an adaptive
    controller sweeping K must never add a decode_multi variant."""
    eng = make_engine(True, decode_horizon=2, adaptive_horizon=True,
                      decode_horizon_max=8)
    run_streams(eng, [("a", list(range(5, 25)), greedy(30))])
    traces = [k for k in eng.runner._compiled if k[0] == "decode_multi"]
    assert len(traces) == 1
    # force K variation: a waiting queue collapses K to 1, its drain
    # re-opens the cap — same trace throughout
    run_streams(eng, [
        ("b", list(range(5, 25)), greedy(25)),
        ("c", list(range(30, 55)), greedy(12)),
        ("d", list(range(60, 85)), greedy(6)),
    ])
    traces = {k for k in eng.runner._compiled if k[0] == "decode_multi"}
    # at most one more variant (batch bucket 4 vs 1), never one per K
    assert len(traces) <= 2
    assert all(k[3] == 8 for k in traces)  # compiled width == cap everywhere


def test_adaptive_horizon_controller_behaviors():
    eng = make_engine(True, decode_horizon=1, adaptive_horizon=True,
                      decode_horizon_max=8)
    sched = eng.scheduler
    eng.submit(list(range(5, 25)), greedy(64), rid="a")
    for _ in range(3):
        eng.step()
    active = sched._decode_active()
    assert active
    # empty queue, no finish history: controller opens up to the cap
    assert sched._pick_horizon(active) == (8, 8)
    # pending admission work forces K=1 (a K=1 schedule can admit between
    # any two columns — byte-parity), within the same wide trace
    eng.submit(list(range(30, 60)), greedy(8), rid="b")
    assert sched._pick_horizon(active) == (1, 8)
    while sched.has_work():
        eng.step()
    # short observed finish gaps shrink K
    eng2 = make_engine(True, decode_horizon=1, adaptive_horizon=True,
                       decode_horizon_max=8)
    run_streams(eng2, [
        (f"s{i}", list(range(5 + 20 * i, 25 + 20 * i)),
         SamplingParams(temperature=0.0, max_new_tokens=2, ignore_eos=True))
        for i in range(3)
    ])
    assert 0 < eng2.scheduler._finish_gap_ema <= 4
    eng2.submit(list(range(5, 25)), greedy(64), rid="z")
    for _ in range(2):
        eng2.step()
    act2 = eng2.scheduler._decode_active()
    assert act2 and eng2.scheduler._pick_horizon(act2)[0] < 8


def test_a_submission_that_waited_out_the_fetch_is_prefilled_in_that_step():
    """Three lanes, one ends ("b"), and the step that reports it launches the
    next frame.  The caller's next request comes while the step after that
    holds the engine lock in the frame's fetch; no lookahead is out, because
    another lane's last token lies inside the frame ("d": lengths end most
    frames of the benchmark's cells so).  The step lets the submission in
    ahead of its prefill phase (``Scheduler.let_submitters_in``; here the hook
    submits, as the waiting thread does once the lock is handed over): the
    request is prefilled in that step, after the one frame, and not after
    the frame the step would have launched before the submission got the
    lock."""
    eng = make_engine(True, decode_horizon=8, max_batch=3)
    toks: dict = {rid: [] for rid in "abcd"}
    done = set()

    def cb(out):
        toks[out.rid].extend(out.new_token_ids)
        if out.finished:
            done.add(out.rid)

    for rid, first, n in (("a", 5, 60), ("b", 30, 11), ("d", 90, 15)):
        eng.submit(list(range(first, first + 20)), greedy(n), rid=rid, on_output=cb)
    while "b" not in done:
        eng.step()
    assert eng.scheduler.inflight is not None  # the frame the submission waits out
    at_finish, let_in = len(toks["a"]), []

    def waiting_submitter():
        let_in.append(eng.scheduler.flight.step_serial)
        if len(let_in) == 1:
            eng.submit(list(range(60, 85)), greedy(8), rid="c", on_output=cb)

    eng.scheduler.let_submitters_in = waiting_submitter
    eng.step()
    assert len(let_in) == 1 and len(toks["c"]) == 1  # let in and prefilled in one step
    assert "d" in done and len(toks["a"]) - at_finish == 4  # the one frame, cut at d's end
    frame = eng.scheduler.inflight  # and the launch behind the prefill holds both
    assert [r.rid for _s, r, _e in frame.lanes] == ["a", "c"]
    steps = eng.scheduler.flight.step_serial
    while eng.scheduler.has_work():
        eng.step()
    assert len(toks["a"]) == 60 and len(toks["c"]) == 8
    # a step with a lookahead out lets nobody in: a prefill behind it would
    # fold a key after the lookahead's, and the lookahead would go
    loads = eng.loads()
    assert loads["lookahead_kept"] > 0 and loads["lookahead_discarded"] == 0
    assert len(let_in) - 1 < eng.scheduler.flight.step_serial - steps


def test_adaptive_parity_under_churn(k1_baseline):
    """The adaptive controller changes K frame to frame; accepted streams
    must not notice (K-invariance is the whole point of the trim rule)."""
    got = run_streams(
        make_engine(True, decode_horizon=1, adaptive_horizon=True,
                    decode_horizon_max=8),
        MIXED_JOBS,
    )
    assert_stream_parity(got, k1_baseline, "adaptive horizon churn")


def test_flight_ring_and_metrics_record_megastep():
    from prometheus_client import generate_latest

    eng = make_engine(True, decode_horizon=4)
    run_streams(eng, [
        ("f0", list(range(5, 25)), greedy(10)),
        ("f1", list(range(30, 50)),
         SamplingParams(temperature=0.0, max_new_tokens=6, ignore_eos=True)),
    ])
    ring = eng.dump_flight()["ring"]
    assert any(r["horizon"] == 4 for r in ring)
    assert any(r["early_exits"] for r in ring)  # a finish ended a horizon
    assert all("wasted_decode_tokens" in r for r in ring)
    text = generate_latest(eng.metrics.registry).decode()
    assert "smg_engine_decode_horizon 4.0" in text
    assert "smg_engine_megastep_early_exits_total" in text
    assert "smg_engine_wasted_decode_tokens_total" in text


def test_cli_horizon_flags_reach_scheduler_config():
    from smg_tpu.cli import build_parser
    from smg_tpu.config.validation import validate_cli_args

    args = build_parser().parse_args([
        "worker", "--model-preset", "tiny",
        "--decode-horizon", "4", "--adaptive-horizon", "on",
        "--decode-horizon-max", "16",
    ])
    assert not [i for i in validate_cli_args(args) if i.severity == "error"]
    sc = SchedulerConfig(
        decode_horizon=args.decode_horizon,
        adaptive_horizon=args.adaptive_horizon == "on",
        decode_horizon_max=args.decode_horizon_max,
    )
    assert (sc.decode_horizon, sc.adaptive_horizon, sc.horizon_cap) \
        == (4, True, 16)

    bad = build_parser().parse_args(
        ["worker", "--model-preset", "tiny", "--decode-horizon", "0"])
    assert [i for i in validate_cli_args(bad) if i.severity == "error"]
    bad2 = build_parser().parse_args([
        "worker", "--model-preset", "tiny",
        "--decode-horizon", "8", "--decode-horizon-max", "4",
    ])
    assert [i for i in validate_cli_args(bad2) if i.severity == "error"]


def test_launch_wires_horizon_flags():
    from smg_tpu.cli import build_parser
    from smg_tpu.gateway.launch import build_engine_from_args

    args = build_parser().parse_args([
        "worker", "--model-preset", "tiny", "--dtype", "float32",
        "--max-batch-size", "4", "--max-seq-len", "256",
        "--decode-horizon", "4", "--adaptive-horizon", "on",
        "--decode-horizon-max", "8",
    ])
    eng = build_engine_from_args(args)
    try:
        sc = eng.config.scheduler
        assert sc.decode_horizon == 4
        assert sc.adaptive_horizon is True
        assert sc.horizon_cap == 8
    finally:
        eng.stop()


@pytest.mark.slow
@pytest.mark.parametrize("horizon", [2, 4, 8])
def test_exhaustive_k_parity_sweep(horizon):
    """Randomized stress: mixed greedy/sampled/stop/penalty workloads, many
    staggered finish points, K vs K=1 AND overlap vs sync at each K."""
    import random

    rng = random.Random(1000 + horizon)
    jobs = []
    for i in range(6):
        prompt = [rng.randrange(5, 500) for _ in range(rng.randrange(8, 60))]
        if i % 3 == 0:
            sp = greedy(rng.randrange(3, 20))
        elif i % 3 == 1:
            sp = SamplingParams(temperature=0.8, top_k=50,
                                max_new_tokens=rng.randrange(3, 20),
                                ignore_eos=True)
        else:
            sp = SamplingParams(temperature=0.0,
                                max_new_tokens=rng.randrange(6, 24),
                                frequency_penalty=0.3, ignore_eos=True)
        jobs.append((f"x{i}", prompt, sp))
    base = run_streams(make_engine(True), jobs)
    assert_stream_parity(
        run_streams(make_engine(True, decode_horizon=horizon), jobs), base,
        f"exhaustive K={horizon} overlap")
    assert_stream_parity(
        run_streams(make_engine(False, decode_horizon=horizon), jobs), base,
        f"exhaustive K={horizon} sync")

"""A model with recurrent layers through the engine on the CPU: state slots
beside pages in the scheduler, what a prefix hit and a preemption mean for a
sequence whose state is not in its pages, what a discarded decode frame does
to it, and what the engine refuses.  Every stream is held to the greedy tokens
of the plain reference (``benchmark/architectures/olmo_hybrid.py``)."""

import numpy as np
import pytest

from smg_tpu.engine.config import CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.models.config import tiny_olmo_hybrid_config
from smg_tpu.protocols.sampling import SamplingParams
from smg_tpu.tokenizer import MockTokenizer
from tests.test_olmo_hybrid import ARCH, hf_of


def make_engine(num_pages=128, max_batch=8, max_seq_len=256, horizon=4, overlap=True,
                **sched_kw) -> Engine:
    cfg = EngineConfig(
        model=tiny_olmo_hybrid_config(),
        cache=CacheConfig(page_size=16, num_pages=num_pages, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=max_batch, max_seq_len=max_seq_len, max_prefill_tokens=64,
            prefill_token_buckets=(16, 32, 64), decode_batch_buckets=(4, 8),
            decode_horizon=horizon, overlap_schedule=overlap, **sched_kw),
        dtype="float32",
    )
    return Engine(cfg, tokenizer=MockTokenizer())


def greedy(n, **kw) -> SamplingParams:
    return SamplingParams(temperature=0.0, max_new_tokens=n, ignore_eos=True, **kw)


def reference_tokens(engine, prompt, n) -> list:
    hf, toks = hf_of(engine.config.model), list(prompt)
    for _ in range(n):
        row = ARCH.logits(engine.runner.params, hf, np.asarray(toks, np.int32), [len(toks) - 1])
        toks.append(int(np.argmax(row[0])))
    return toks[len(prompt):]


def prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 500, size=n).tolist() for n in lengths]


def run_all(engine, jobs, steps=2000) -> dict:
    """Submit ``(prompt, sampling)`` jobs together, step until all finish;
    returns the token lists by job index."""
    out = {i: [] for i in range(len(jobs))}
    done = set()

    def sink(i):
        def on(o):
            out[i].extend(o.new_token_ids)
            if o.finished:
                done.add(i)
        return on

    for i, (p, sp) in enumerate(jobs):
        engine.submit(p, sp, on_output=sink(i))
    for _ in range(steps):
        engine.step()
        if len(done) == len(jobs):
            break
    assert len(done) == len(jobs), engine.loads()
    return out


@pytest.fixture(scope="module")
def engine():
    return make_engine()


def state_counters(engine) -> dict:
    return {k: v for k, v in engine.loads().items() if k.startswith("state_")}


def test_streams_are_the_references_through_chunks_groups_and_frames(engine):
    (short, long_, a, b, c) = prompts(1, 40, 150, 20, 70, 33)
    r = engine.generate(prompt_ids=short, sampling=greedy(10))
    assert r.token_ids == reference_tokens(engine, short, 10)
    # 150 tokens over a 64-token budget: two continuing chunks and a final one
    r = engine.generate(prompt_ids=long_, sampling=greedy(9))
    assert r.token_ids == reference_tokens(engine, long_, 9)
    out = run_all(engine, [(a, greedy(12)), (b, greedy(5)), (c, greedy(17))])
    for i, (p, n) in enumerate(((a, 12), (b, 5), (c, 17))):
        assert out[i] == reference_tokens(engine, p, n)
    loads = engine.loads()
    assert loads["lookahead_kept"] > 0 and loads["audit"]["clean"]
    assert loads["state_slots_total"] == 8 + 8 and loads["state_slots_in_use"] == 0
    # the tail's 6 rows of 128 take a whole float32 tile of 8
    assert loads["state_slot_bytes"] == 4 * (4 * 16 * 32 * 4 + 8 * 128 * 4)


def test_a_reused_slot_starts_from_zero(engine):
    """One lane at a time: every request gets the slot the last one freed."""
    for p in prompts(2, 30, 45, 30):
        assert engine.generate(prompt_ids=p, sampling=greedy(6)).token_ids \
            == reference_tokens(engine, p, 6)
    assert engine.loads()["state_slots_in_use"] == 0


def test_a_radix_match_without_a_snapshot_prefills_from_the_first_token(engine):
    (p,) = prompts(3, 80)
    before = state_counters(engine)["state_prefix_hits_declined"]
    first = engine.generate(prompt_ids=p, sampling=greedy(8))
    again = engine.generate(prompt_ids=p, sampling=greedy(8))  # its pages are cached now
    assert again.token_ids == first.token_ids == reference_tokens(engine, p, 8)
    assert again.cached_tokens == 0
    assert state_counters(engine)["state_prefix_hits_declined"] == before + 1


def test_a_preempted_request_comes_out_as_an_undisturbed_one():
    eng = make_engine(num_pages=12, max_batch=4, max_seq_len=128, watermark_pages=1)
    ps = prompts(4, 30, 33, 36)
    out = run_all(eng, [(p, greedy(40)) for p in ps])
    loads = eng.loads()
    assert loads["preemptions"] > 0 and loads["state_recomputed_tokens"] > 0
    for i, p in enumerate(ps):
        assert out[i] == reference_tokens(eng, p, 40)
    assert loads["audit"]["clean"] and loads["state_slots_in_use"] == 0


def test_the_leak_audit_sees_a_leaked_slot():
    eng = make_engine()
    assert eng.loads()["audit"]["clean"]
    leaked = eng.scheduler.state_pool.alloc()  # bound to no sequence
    audit = eng.loads()["audit"]
    assert audit["leaked_state_slots"] == 1 and not audit["clean"]
    eng.scheduler.state_pool.free(leaked)
    assert eng.loads()["audit"]["clean"]


@pytest.mark.parametrize("overlap", [True, False])
def test_a_finish_inside_a_frame_costs_the_other_lanes_nothing(overlap):
    """Lengths that end mid-frame and on a frame's last column: nobody's
    state moves beyond what is accepted and nothing is computed again."""
    eng = make_engine(overlap=overlap)
    ps = prompts(5, 25, 31, 28, 40)
    lengths = (6, 8, 13, 21)  # 8 ends a frame of four columns exactly
    out = run_all(eng, [(p, greedy(n)) for p, n in zip(ps, lengths)])
    for i, (p, n) in enumerate(zip(ps, lengths)):
        assert out[i] == reference_tokens(eng, p, n)
    loads = eng.loads()
    assert loads["state_recomputed_tokens"] == 0 and loads["preemptions"] == 0


@pytest.mark.parametrize("horizon", [1, 8])
def test_a_frame_launched_behind_a_grouped_prefill_leaves_the_streams_alone(horizon):
    """Requests that arrive while others decode, three of them at once (a
    group this runner prefills in two launches): the step's decode frame
    goes out before the first tokens are fetched, no lane loses its state,
    and the streams are the synchronous schedule's at temperature 0 and 0.8."""
    from tests.test_overlap import staged_streams

    ps = prompts(9, 30, 22, 41, 19, 20, 18, 27)
    jobs = [(f"j{i}", p, SamplingParams(temperature=t, top_k=20, max_new_tokens=n,
                                        ignore_eos=True))
            for i, (p, t, n) in enumerate(zip(
                ps, (0.8, 0.0, 0.8, 0.8, 0.0, 0.8, 0.0), (21, 17, 12, 15, 9, 11, 14)))]
    at = [0, 0, 3, 6, 6, 6, 11]
    engs = [make_engine(overlap=o, horizon=horizon) for o in (True, False)]
    streams = [staged_streams(e, jobs, at) for e in engs]
    assert streams[0] == streams[1]
    loads = engs[0].loads()
    assert loads["prefill_chained_launches"] >= 4
    assert not any(loads["prefill_sync_launches"].values())
    assert loads["wasted_decode_tokens"] == 0 and loads["state_recomputed_tokens"] == 0
    assert loads["audit"]["clean"] and engs[1].loads()["prefill_chained_launches"] == 0


def test_a_stop_token_ends_a_lane_and_the_others_go_on():
    """A finish the host cannot foresee, with a lookahead in flight: the frame
    chained on the one that met it runs no column on the device, so the
    surviving lane's state holds exactly its accepted tokens."""
    eng = make_engine()
    (p, q) = prompts(6, 30, 44)
    want = reference_tokens(eng, p, 12)
    stop = want[5]
    cut = want[: want.index(stop) + 1]
    out = run_all(eng, [(p, greedy(12, stop_token_ids=[stop])), (q, greedy(20))])
    assert out[0] == cut and out[1] == reference_tokens(eng, q, 20)
    loads = eng.loads()
    assert loads["lookahead_discarded"] > 0 and loads["state_recomputed_tokens"] == 0
    assert loads["preemptions"] == 0


def test_an_abort_mid_frame_costs_the_others_their_state_and_not_their_tokens():
    """An abort stales the frame in flight; it ran, so the lanes still alive
    give up slot and pages and prefill again, and their streams do not show it."""
    eng = make_engine()
    (p, q) = prompts(7, 30, 44)
    got = {"p": [], "q": []}
    done = set()
    rid_p = eng.submit(p, greedy(30), on_output=lambda o: got["p"].extend(o.new_token_ids))

    def on_q(o):
        got["q"].extend(o.new_token_ids)
        if o.finished:
            done.add("q")

    eng.submit(q, greedy(24), on_output=on_q)
    for _ in range(4):
        eng.step()
    assert eng.scheduler.inflight is not None
    assert eng.abort(rid_p)
    for _ in range(500):
        eng.step()
        if done:
            break
    assert got["q"] == reference_tokens(eng, q, 24)
    loads = eng.loads()
    assert loads["state_recomputed_tokens"] > 0
    assert loads["audit"]["clean"] and loads["state_slots_in_use"] == 0


def test_what_the_model_cannot_do_is_refused_with_a_sentence():
    from smg_tpu.config.validation import ConfigError

    model = tiny_olmo_hybrid_config()
    cache = CacheConfig(page_size=16, num_pages=64, auto_size=False, dtype="float32")
    with pytest.raises(ConfigError, match="verify block"):
        Engine(EngineConfig(model=model, cache=cache, dtype="float32",
                            scheduler=SchedulerConfig(speculative=True)))
    with pytest.raises(ConfigError, match="one device"):
        Engine(EngineConfig(model=model, cache=cache, dtype="float32",
                            parallel=ParallelConfig(tp=2)))
    from smg_tpu.models.weights import load_params

    with pytest.raises(ValueError, match="key map"):
        load_params(EngineConfig(model=model, model_path="/nonexistent", dtype="float32"))
    eng = make_engine()
    with pytest.raises(ValueError, match="LoRA"):
        eng.runner.load_lora("a", {})
    with pytest.raises(ValueError, match="embedding"):
        eng.embed([[1, 2, 3]])
    with pytest.raises(ValueError, match="recurrent state is not in the pages"):
        eng.runner.export_pages([1])
    assert not eng.runner.supports_kv_transfer
    with pytest.raises(ValueError, match="recurrent state"):
        eng.scheduler.prefill_only([1, 2, 3], greedy(1))


def test_a_llama_engine_reports_no_state_keys():
    from smg_tpu.models.config import tiny_test_config

    eng = Engine(EngineConfig(
        model=tiny_test_config(), dtype="float32",
        cache=CacheConfig(page_size=16, num_pages=64, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(max_batch_size=4, max_seq_len=128, max_prefill_tokens=64,
                                  prefill_token_buckets=(16, 32, 64),
                                  decode_batch_buckets=(4,))), tokenizer=MockTokenizer())
    loads = eng.loads()
    assert not [k for k in loads if k.startswith("state_")]
    assert "leaked_state_slots" not in loads["audit"] and eng.scheduler.state_pool is None

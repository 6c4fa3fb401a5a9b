"""The one decode frame (``ModelRunner._decode_frame_fn``) under each of the
five runners, through the engine on the CPU: a horizon-8 stream whose stop
token, length limits and penalties fall inside a frame is the horizon-1
stream byte for byte, what the frame donates is aliased in the compiled
program, and the programs are traced under the names the
benchmark's trace reduction finds them by (``benchmark/trace_reduce.py``:
decode launches are ``jit_multi*``, prefill launches ``jit_step*``)."""

import pytest

from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.models.config import (
    tiny_exaone_moe_config,
    tiny_mimo_config,
    tiny_olmo_hybrid_config,
    tiny_pangu_moe_config,
    tiny_test_config,
)
from smg_tpu.protocols.sampling import SamplingParams

RUNNERS = {
    "ModelRunner": (tiny_test_config, {}),
    "RecurrentModelRunner": (tiny_olmo_hybrid_config, {}),
    "LatentModelRunner": (lambda: tiny_pangu_moe_config(held=(4, 8)), {}),
    "WindowModelRunner": (lambda: tiny_mimo_config(held=(4, 8)), {}),
    "SelfDraftingRunner": (lambda: tiny_exaone_moe_config(held=(4, 8)), {"speculative": True}),
}


def make_engine(runner: str, horizon: int, **more) -> Engine:
    model, sched = RUNNERS[runner]
    engine = Engine(EngineConfig(
        model=model(),
        cache=CacheConfig(page_size=16, num_pages=96, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=4, max_seq_len=128, max_prefill_tokens=64,
            prefill_token_buckets=(32, 64), decode_batch_buckets=(4,),
            decode_horizon=horizon, **sched, **more),
        dtype="float32"))
    assert type(engine.runner).__name__ == runner
    engine.runner._programs.arm()
    return engine


def stream(engine: Engine, jobs) -> list:
    out = [[] for _ in jobs]
    reasons = [None] * len(jobs)

    def sink(i):
        def on(o):
            out[i].extend(o.new_token_ids)
            if o.finished:
                reasons[i] = o.finish_reason
        return on

    for i, (prompt, sampling) in enumerate(jobs):
        engine.submit(prompt, sampling, on_output=sink(i))
    for _ in range(400):
        engine.step()
        if all(r is not None for r in reasons):
            break
    assert all(r is not None for r in reasons), engine.loads()
    return [(toks, why) for toks, why in zip(out, reasons)]


@pytest.fixture(scope="module", params=list(RUNNERS))
def pair(request):
    """The same jobs through a horizon-1 engine on the synchronous schedule
    (on the CPU that one donates its caches) and a horizon-8 engine on the
    overlapped one."""
    one = make_engine(request.param, 1, overlap_schedule=False)
    eight = make_engine(request.param, 8)
    prompts = [list(range(5 + 7 * i, 27 + 9 * i)) for i in range(4)]
    # both engines, so that their key counters stand alike behind it
    plain, again = (e.generate(prompt_ids=prompts[0], sampling=SamplingParams(
        temperature=0.0, max_new_tokens=12, ignore_eos=True)).token_ids for e in (one, eight))
    assert plain == again and one.runner.rng_mark() == eight.runner.rng_mark()
    one.flush_cache(), eight.flush_cache()
    jobs = [
        # ends on a stop token in its frame's sixth column
        (prompts[0], SamplingParams(temperature=0.0, max_new_tokens=12,
                                    stop_token_ids=[int(plain[5])], ignore_eos=True)),
        # penalties, and a limit that is no multiple of the horizon
        (prompts[1], SamplingParams(temperature=0.0, max_new_tokens=11, repetition_penalty=1.3,
                                    frequency_penalty=0.5, ignore_eos=True)),
        # a limit inside the first frame
        (prompts[2], SamplingParams(temperature=0.0, max_new_tokens=5, ignore_eos=True)),
        # a lane that samples: column j's key is the one the j-th launch would fold
        (prompts[3], SamplingParams(temperature=0.8, top_p=0.9, max_new_tokens=13,
                                    presence_penalty=0.3, ignore_eos=True)),
    ]
    return one, eight, jobs, plain, stream(one, jobs), stream(eight, jobs)


def test_horizon_8_stream_is_the_horizon_1_stream(pair):
    _one, eight, _jobs, plain, at_one, at_eight = pair
    assert at_eight == at_one
    stopped, penalised, short, sampled = at_one
    assert stopped[1] == "stop" and len(stopped[0]) <= 6 and stopped[0] == plain[:len(stopped[0])]
    assert penalised[1] == "length" and len(penalised[0]) == 11
    assert short[1] == "length" and len(short[0]) == 5
    assert sampled[1] == "length" and len(sampled[0]) == 13
    # the horizon-8 engine ran frames wider than a column: fewer launches
    # for the same tokens, and some that ended early on a finish
    launches = lambda engine: sum(engine.loads()["decode_launches"].values())
    assert launches(eight) < launches(_one)
    assert eight.loads()["megastep_early_exits"] > 0


def test_programs_are_traced_as_multi_and_step(pair):
    _one, eight, *_ = pair
    names = {}
    for key, rec in eight.runner._programs._records.items():
        if rec.last_specs is None:
            continue
        text = rec.fn.lower(*rec.last_specs).as_text()
        names.setdefault(key[0], set()).add(text.split("module @", 1)[1].split(" ", 1)[0])
    assert names["decode_multi"] == {"jit_multi"}
    prefills = {k: v for k, v in names.items() if k.startswith("prefill")}
    assert prefills and all(v == {"jit_step"} for v in prefills.values()), names


def test_the_frames_donated_arguments_are_aliased(pair):
    """The donated positions are worked out from each frame's description
    (``n_held``, ``donate_held``, the penalty buffer behind them): every one
    is an alias in the compiled program, with penalties and without."""
    one, *_ = pair
    assert one.runner.donation.donate_kv
    report = one.program_audit()
    assert report["donation_unverified"] == 0, report
    frames = [p for p in report["programs"] if "decode_multi" in str(p["key"])]
    assert len(frames) >= 2, report  # the plain frame and the one with penalties
    for p in frames:
        assert p["donation"]["verified"], p
        assert p["donation"]["aliased"] == p["donation"]["intended"] >= 2, p

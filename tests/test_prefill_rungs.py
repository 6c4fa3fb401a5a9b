"""The rule by which a prefill launch's rows and lengths become a padded
``(G, T)``, on the CPU in float32 at a ladder scaled to the test models
(rungs 48 and 96 between the octaves 16 to 128, where serving has 1,536 and
3,072 between 64 and 4,096; a step's budget of 128 tokens for 4,096):

- one cold row pads to the finest rung and gives what the next octave gives:
  the first token, its log-probability, and everything the launch writes
  (pages, state slot, ring);
- a launch of several rows, a row behind a prefix, and both chunk paths pad to
  an octave;
- a group whose padded rows x tokens pass the budget goes up in parts that
  stay inside it, on the Llama path as on the others;
- ``loads()["prefill_padding"]`` counts it.
"""

import numpy as np
import pytest

from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.tokenizer import MockTokenizer
from tests.test_prefill_pack import MODELS, held

FINE = (16, 32, 48, 64, 96, 128)
OCTAVES = (16, 32, 64, 128)
BUDGET = 128


def make_engine(model, ladder=FINE) -> Engine:
    cfg = EngineConfig(
        model=MODELS[model](),
        cache=CacheConfig(page_size=16, num_pages=128, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=8, max_seq_len=256, max_prefill_tokens=BUDGET,
            prefill_token_buckets=ladder, decode_batch_buckets=(4, 8), decode_horizon=4),
        dtype="float32")
    return Engine(cfg, tokenizer=MockTokenizer())


@pytest.fixture(scope="module")
def engines():
    """One engine a model and ladder, built when first asked for."""
    made = {}

    def get(model, ladder=FINE):
        if (model, ladder) not in made:
            made[model, ladder] = make_engine(model, ladder)
        return made[model, ladder]

    return get


def group_of(runner, lengths, seed=0, prefix=0, temperature=0.0):
    """Rows of ``lengths`` tokens behind ``prefix`` cached ones, each on eight
    pages of its own; the sampling vectors; a state slot a row where the
    model keeps state or rings."""
    rng = np.random.default_rng(seed)
    mp, g = runner.max_pages_per_seq, len(lengths)
    chunks = []
    for i, n in enumerate(lengths):
        table = np.zeros(mp, np.int32)
        table[:9] = 1 + 9 * i + np.arange(9)
        chunks.append((rng.integers(2, 500, size=n).tolist(), prefix, table))
    samp = (np.full(g, temperature, np.float32), np.full(g, -1, np.int32),
            np.ones(g, np.float32), np.zeros(g, np.float32))
    kw = ({"state_slots": np.arange(1, g + 1, dtype=np.int32)}
          if hasattr(runner, "s_pool") else {})
    return chunks, samp, kw


def launched(runner, fn) -> dict:
    """The launches by padded shape that ``fn`` added to the runner's count."""
    before = dict(runner.prefill_padding["launches"])
    fn()
    after = runner.prefill_padding["launches"]
    return {k: n - before.get(k, 0) for k, n in after.items() if n != before.get(k, 0)}


def test_the_ladder_has_the_half_octave_rungs_and_the_octaves_are_spelt_out():
    sched = SchedulerConfig()
    assert sched.prefill_token_buckets == (64, 128, 256, 512, 1024, 1536, 2048, 3072, 4096)
    assert sched.coarse_prefill_buckets == (64, 128, 256, 512, 1024, 2048, 4096)
    assert [sched.prefill_bucket(n) for n in (1, 1024, 1025, 1536, 1537, 2049, 3072, 3073, 9999)] \
        == [64, 1024, 1536, 1536, 2048, 3072, 3072, 4096, 4096]
    assert [sched.coarse_prefill_bucket(n) for n in (1025, 1537, 2049, 3073, 9999)] \
        == [2048, 2048, 4096, 4096, 4096]
    # a ladder of octaves alone is its own coarse ladder, whatever its base
    for ladder in ((16, 32, 64), (100, 200, 400), (32,)):
        assert SchedulerConfig(max_prefill_tokens=ladder[-1], prefill_token_buckets=ladder
                               ).coarse_prefill_buckets == ladder
    assert SchedulerConfig(max_prefill_tokens=BUDGET, prefill_token_buckets=FINE
                           ).coarse_prefill_buckets == OCTAVES


@pytest.mark.parametrize("length", [40, 80])  # 1,300 and 2,500 of 4,096, at 128
@pytest.mark.parametrize("model", list(MODELS))
def test_one_cold_row_gives_at_its_fine_rung_what_the_next_octave_gives(model, length):
    fine, coarse = make_engine(model), make_engine(model, OCTAVES)
    rung = {40: (48, 64), 80: (96, 128)}[length]
    out = []
    for engine, T in zip((fine, coarse), rung):
        chunks, samp, kw = group_of(engine.runner, [length], seed=length)
        shapes = launched(engine.runner,
                          lambda: out.append(engine.runner.prefill_batched(chunks, *samp, **kw)))
        assert shapes == {f"1x{T}": 1}, (model, shapes)
    (tok_f, lp_f), (tok_c, lp_c) = out
    assert tok_f.tolist() == tok_c.tolist()
    np.testing.assert_allclose(lp_f, lp_c, rtol=1e-4, atol=1e-5)
    for got, want in zip(held(fine.runner), held(coarse.runner)):
        # page 0 and slot 0 take the padded positions' writes, which no one reads
        np.testing.assert_allclose(got[:, 1:], want[:, 1:], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("length", [40, 80])
@pytest.mark.parametrize("model", list(MODELS))
def test_every_other_prefill_pads_to_an_octave(engines, model, length):
    runner = engines(model).runner
    octave = {40: 64, 80: 128}[length]
    # two rows of one octave: a launch of 2 x 64; 2 x 128 is twice the step's
    # budget, so there the rows go up alone and no fine rung gets a second row
    chunks, samp, kw = group_of(runner, [length, length - 1])
    shapes = launched(runner, lambda: runner.prefill_batched(chunks, *samp, **kw))
    assert shapes == ({"2x64": 1} if length == 40 else {"1x96": 2}), shapes
    # one row behind a cached token: the program that gathers context
    chunks, samp, kw = group_of(runner, [length], prefix=1)
    shapes = launched(runner, lambda: runner.prefill_batched(chunks, *samp, **kw))
    assert shapes == {f"1x{octave}": 1}
    # the solo chunk that samples and the chunk that does not
    (ids, _pfx, table), = group_of(runner, [length])[0]
    slot = {"state_slot": 1} if kw else {}
    T = runner._chunk_bucket(length)
    assert T in OCTAVES and T >= length
    runner.prefill(ids, 0, table, 0.0, -1, 1.0, 0.0, **slot)
    runner.prefill_extend(ids, 0, table, **slot)
    solo = {k[1] for k in runner._compiled if k[0] in ("prefill", "prefill_extend")}
    assert T in solo and solo <= set(OCTAVES), solo


def test_the_recurrent_runners_chunks_take_every_second_octave_from_the_top():
    runner = make_engine("tiny-olmo-hybrid").runner
    assert [runner._chunk_bucket(n) for n in (1, 32, 33, 48, 128)] == [32, 32, 128, 128, 128]
    # at the served ladder: the rungs it had before the ladder grew
    served = SchedulerConfig().coarse_prefill_buckets[::-1][::2]
    assert served == (4096, 1024, 256, 64)


def test_on_the_llama_path_a_group_past_the_budget_goes_up_in_parts():
    """Eight rows with one long member pad to 8 x 64, four times the step's
    budget: they go up two rows a launch (what fits at the longest row's
    octave), in the callers' order, each part folding the next key."""
    engine = make_engine("llama")
    runner = engine.runner
    lengths = [9, 20, 12, 60, 5, 30, 16, 7]
    assert runner._split_group(lengths) == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert runner._split_group([30, 31, 32, 20]) == [[0, 1, 2, 3]]  # 4 x 32 is the budget
    assert runner._split_group([128, 5]) == [[0], [1]]
    chunks, samp, kw = group_of(runner, lengths, seed=3)
    mark = runner.rng_mark()
    parts = runner.prefill_batched_async(chunks, *samp, **kw)
    assert [p[0].tolist() for p in parts] == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert runner.rng_mark() == mark + 4  # a fold a part
    toks, lps = runner.fetch_first_tokens(parts, len(chunks))
    pad = engine.loads()["prefill_padding"]
    assert pad["groups_in_parts"] == 1
    assert pad["launches"] == {"2x32": 2, "2x64": 1, "2x16": 1}
    assert all(int(g) * int(t) <= BUDGET for g, t in (k.split("x") for k in pad["launches"]))
    solo = make_engine("llama", OCTAVES).runner
    for i, (ids, pfx, table) in enumerate(chunks):
        tok, lp = solo.prefill(ids, pfx, table, 0.0, -1, 1.0, 0.0)
        assert tok == toks[i] and abs(lp - lps[i]) < 1e-4, i
    for got, want in zip(held(runner), held(solo)):
        np.testing.assert_allclose(got[:, 1:], want[:, 1:], rtol=2e-4, atol=2e-5)
    # sampled: the group draws what its parts draw launched one by one from
    # the same counter, and a group inside the budget still folds one key
    hot = group_of(runner, lengths, seed=3, temperature=0.9)[1]
    runner.rng_restore(mark)
    whole, _ = runner.prefill_batched(chunks, *hot)
    runner.rng_restore(mark)
    by_hand = np.concatenate([
        runner.prefill_batched(chunks[lo:lo + 2], *(v[lo:lo + 2] for v in hot))[0]
        for lo in range(0, 8, 2)])
    assert runner.rng_mark() == mark + 4 and whole.tolist() == by_hand.tolist()
    runner.rng_restore(mark)
    runner.prefill_batched(chunks[:3], *(v[:3] for v in hot))  # 4 x 32
    assert runner.rng_mark() == mark + 1


SERVED = [[128], [100], [80], [40], [20], [128, 5], [70, 70], [40, 40, 40, 40], [20] * 8,
          [128] + [5] * 7, [30, 60, 90, 120, 10, 50, 70, 100], [96, 96, 3], [48, 47]]


@pytest.mark.parametrize("model", list(MODELS))
def test_no_runner_holds_a_group_program_past_the_budget_nor_a_fine_rung_of_rows(engines, model):
    """After lengths over the whole ladder in groups of 1 to 8: every grouped
    program but a lone row's is inside the step's budget, and a rung between
    two octaves has one row."""
    engine = engines(model)
    runner = engine.runner
    for seed, lengths in enumerate(SERVED):
        chunks, samp, kw = group_of(runner, lengths, seed=seed)
        toks, _lps = runner.prefill_batched(chunks, *samp, **kw)
        assert len(toks) == len(lengths)
    programs = {(k[1], k[2]) for k in runner._compiled if k[0] == "prefill_batched"}
    assert {(1, 48), (1, 96), (1, 128)} <= programs
    for G, T in programs:
        assert G == 1 or (G * T <= BUDGET and T in OCTAVES), (model, G, T)
    pad = engine.loads()["prefill_padding"]
    assert {f"{G}x{T}" for G, T in programs} == set(pad["launches"])


def test_loads_counts_real_and_padded_tokens_launches_by_shape_and_split_groups():
    engine = make_engine("llama")
    runner = engine.runner
    assert engine.loads()["prefill_padding"] == {
        "real_tokens": 0, "padded_tokens": 0, "launches": {}, "groups_in_parts": 0}
    for lengths in ([40], [40], [20, 9, 31], [100, 100, 5]):
        chunks, samp, kw = group_of(runner, lengths)
        runner.prefill_batched(chunks, *samp, **kw)
    pad = engine.loads()["prefill_padding"]
    # 1 x 48 twice, 4 x 32, and 100 + 100 + 5 a row a launch (4 x 128 is four
    # times the budget), each at its own finest rung
    assert pad["launches"] == {"1x48": 2, "4x32": 1, "1x128": 2, "1x16": 1}
    assert pad["real_tokens"] == 40 + 40 + 60 + 205
    assert pad["padded_tokens"] == 48 + 48 + 128 + 128 + 128 + 16
    assert pad["groups_in_parts"] == 1
    pad["launches"]["1x48"] = 0  # a copy: the runner's count stands
    assert engine.loads()["prefill_padding"]["launches"]["1x48"] == 2

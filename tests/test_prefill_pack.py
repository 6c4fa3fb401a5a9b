"""A grouped prefill's host inputs as one packed array (``engine/prefill_pack``):
the layout comes back bit for bit under ``jit``; through every runner the
grouped launch gives what ``prefill`` gives row by row, uploads one array a
launch under plain sampling and nothing the code did not ask for by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.analysis.runtime_guards import no_implicit_transfers
from smg_tpu.engine import prefill_pack
from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.models.config import (
    tiny_mimo_config,
    tiny_olmo_hybrid_config,
    tiny_pangu_moe_config,
    tiny_test_config,
)
from smg_tpu.tokenizer import MockTokenizer

T, MP = 32, 6


@pytest.mark.parametrize("slots", [False, True], ids=["pages", "pages_and_slots"])
@pytest.mark.parametrize("G", [1, 2, 8])
def test_layout_round_trips_bit_exactly_under_jit(G, slots):
    rng = np.random.default_rng(G)
    g = max(1, G - 1)  # padded rows too, where there is room
    chunks = [(rng.integers(1, 500, size=int(rng.integers(1, T + 1))).tolist(),
               int(rng.integers(0, 40)), rng.integers(1, 90, size=MP).astype(np.int32))
              for _ in range(g)]
    cycle = lambda vals, dt: np.asarray([vals[i % len(vals)] for i in range(g)], dt)
    temps = cycle([0.0, 0.7, 1.0], np.float32)
    topps = cycle([1.0, 0.7, 0.0], np.float32)
    minps = cycle([0.7, 0.0, 1.0], np.float32)
    topks = cycle([-1, 5, 64], np.int32)
    state = rng.integers(1, 9, size=g).astype(np.int32) if slots else None
    counter = 2**31 + 5  # past what 32 signed bits hold
    packed = prefill_pack.pack(chunks, temps, topks, topps, minps, counter, G, T,
                               state_slots=state)
    assert packed.dtype == np.int32 and packed.ndim == 1
    got = jax.jit(lambda p: prefill_pack.unpack(p, G, T, MP, slots))(jnp.asarray(packed))

    pad = lambda v, fill: np.concatenate([v, np.full(G - g, fill, v.dtype)])
    tokens = np.zeros((G, T), np.int32)
    for i, (ids, _p, _r) in enumerate(chunks):
        tokens[i, : len(ids)] = ids
    want = {
        "tokens": tokens,
        "page_tables": np.concatenate([np.stack([c[2] for c in chunks]),
                                       np.zeros((G - g, MP), np.int32)]),
        "prefix_lens": pad(np.asarray([c[1] for c in chunks], np.int32), 0),
        "t_reals": pad(np.asarray([len(c[0]) for c in chunks], np.int32), 0),
        "topks": pad(topks, -1), "temps": pad(temps, 0.0), "topps": pad(topps, 1.0),
        "minps": pad(minps, 0.0), "counter": np.uint32(counter),
    }
    if slots:
        want["slots"] = pad(state, 0)
    else:
        assert got.slots is None
    for name, w in want.items():
        x = np.asarray(getattr(got, name))
        assert x.dtype == w.dtype and x.shape == w.shape, name
        assert x.tobytes() == w.tobytes(), name  # bit for bit, the floats too


MODELS = {
    "llama": tiny_test_config,
    "tiny-olmo-hybrid": tiny_olmo_hybrid_config,
    "tiny-pangu-moe": lambda: tiny_pangu_moe_config(held=(4, 8)),
    "tiny-mimo": lambda: tiny_mimo_config(held=(4, 8)),
}


def make_engine(model) -> Engine:
    cfg = EngineConfig(
        model=MODELS[model](),
        cache=CacheConfig(page_size=16, num_pages=64, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=8, max_seq_len=256, max_prefill_tokens=128,
            prefill_token_buckets=(16, 32, 64, 128), decode_batch_buckets=(4, 8),
            decode_horizon=4),
        dtype="float32")
    return Engine(cfg, tokenizer=MockTokenizer())


def group_of(runner, lengths, seed=0):
    """Cold rows of ``lengths`` tokens on pages of their own, greedy."""
    rng = np.random.default_rng(seed)
    mp, g = runner.max_pages_per_seq, len(lengths)
    chunks = []
    for i, n in enumerate(lengths):
        table = np.zeros(mp, np.int32)
        table[:4] = 1 + 4 * i + np.arange(4)
        chunks.append((rng.integers(2, 500, size=n).tolist(), 0, table))
    samp = (np.zeros(g, np.float32), np.full(g, -1, np.int32), np.ones(g, np.float32),
            np.zeros(g, np.float32))
    # a model that keeps state or rings names each row's slot
    kw = ({"state_slots": np.arange(1, g + 1, dtype=np.int32)}
          if hasattr(runner, "s_pool") else {})
    return chunks, samp, kw


def held(runner) -> list:
    """Everything a prefill writes: the pages and, where there are any, the
    state or ring pools."""
    bufs = [runner.k_cache, runner.v_cache]
    if hasattr(runner, "s_pool"):
        bufs += [runner.s_pool, runner.c_pool]
    return [np.asarray(b) for b in bufs]


@pytest.mark.parametrize("model", list(MODELS))
def test_grouped_prefill_is_the_solo_prefills_with_one_upload_a_launch(model):
    grouped, solo = make_engine(model), make_engine(model)
    runner = grouped.runner
    chunks, samp, kw = group_of(runner, [20, 9, 31])
    mark = runner.rng_mark()
    runner.prefill_batched(chunks, *samp, **kw)  # compiles
    runner.rng_restore(mark)
    before = dict(grouped.loads()["prefill_uploads"])
    with no_implicit_transfers():
        toks, lps = runner.prefill_batched(chunks, *samp, **kw)
    after = grouped.loads()["prefill_uploads"]
    launches = after["launches"] - before["launches"]
    assert launches >= 1  # the latent runner launches a part for each token bucket
    assert after["arrays"] - before["arrays"] == launches

    for i, (ids, pfx, table) in enumerate(chunks):
        one = {"state_slot": int(kw["state_slots"][i])} if kw else {}
        tok, lp = solo.runner.prefill(ids, pfx, table, 0.0, -1, 1.0, 0.0, **one)
        assert tok == toks[i], (model, i)
        assert abs(lp - lps[i]) < 1e-4, (model, i)
    for got, want in zip(held(runner), held(solo.runner)):
        # page 0 and slot 0 take the padded row's writes, which no one reads
        np.testing.assert_allclose(got[:, 1:], want[:, 1:], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("model", list(MODELS))
def test_penalties_and_a_mask_ride_beside_the_packed_inputs(model):
    engine = make_engine(model)
    runner = engine.runner
    chunks, samp, kw = group_of(runner, [12, 25], seed=1)
    V, g = engine.config.model.vocab_size, len(chunks)
    counts = np.zeros((g, V), np.int32)
    counts[:, 7] = 3
    pen = (counts, counts > 0, np.full(g, 0.5, np.float32), np.full(g, 0.25, np.float32),
           np.full(g, 1.1, np.float32))
    mask = np.ones((g, V), bool)
    mask[:, ::2] = False  # only odd tokens may be sampled
    before = dict(engine.loads()["prefill_uploads"])
    toks, _lps = runner.prefill_batched(chunks, *samp, pen=pen, mask=mask, **kw)
    after = engine.loads()["prefill_uploads"]
    launches = after["launches"] - before["launches"]
    assert launches >= 1
    assert after["arrays"] - before["arrays"] == launches * (1 + 5 + 1)
    assert all(int(t) % 2 == 1 for t in toks)


def test_the_key_a_group_folds_is_the_key_next_key_would_have():
    """Equal seeds, equal counters: a sampled group draws what it drew when
    the host folded the key (``ModelRunner._next_key``)."""
    a, b = make_engine("llama").runner, make_engine("llama").runner
    chunks, _greedy, kw = group_of(a, [20, 9, 31])
    g = len(chunks)
    samp = (np.full(g, 0.9, np.float32), np.full(g, -1, np.int32), np.ones(g, np.float32),
            np.zeros(g, np.float32))
    toks_a, lps_a = a.prefill_batched(chunks, *samp, **kw)
    assert a.rng_mark() == 1
    # the same logits, sampled on the host's side with the key folded there
    key = b._next_key()
    logits, _kc, _vc = b.module.forward_prefill_batched(
        b.params, b.model_cfg, b.inv_freq,
        *(jnp.asarray(x) for x in _dense(chunks, 4, 32)), b.k_cache, b.v_cache,
        jnp.asarray(np.stack([c[2] for c in chunks] + [np.zeros_like(chunks[0][2])])),
        no_ctx=True)
    from smg_tpu.engine.sampling import sample_tokens

    pad = lambda v, fill: jnp.asarray(np.concatenate([v, np.full(1, fill, v.dtype)]))
    toks_b, lps_b = sample_tokens(logits, key, pad(samp[0], 0.0), pad(samp[1], -1),
                                  pad(samp[2], 1.0), pad(samp[3], 0.0))
    assert np.asarray(toks_b)[:g].tolist() == toks_a.tolist()
    np.testing.assert_allclose(np.asarray(lps_b)[:g], lps_a, rtol=1e-5, atol=1e-6)


def _dense(chunks, G, T):
    tokens = np.zeros((G, T), np.int32)
    pfx, real = np.zeros(G, np.int32), np.zeros(G, np.int32)
    for i, (ids, p, _row) in enumerate(chunks):
        tokens[i, : len(ids)], pfx[i], real[i] = ids, p, len(ids)
    return tokens, pfx, real

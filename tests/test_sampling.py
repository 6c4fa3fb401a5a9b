"""Sampling: the sort-free TPU path must match the exact full-sort reference
wherever it claims exactness (top_k <= 64, nucleus within 64 candidates), and
its top values must be ``lax.top_k``'s to the bit without a sort of the row."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.engine import sampling
from smg_tpu.engine.sampling import (
    K_CAP,
    NEG_INF,
    sample_tokens,
    sample_tokens_exact,
    top_values,
)


def _params(B, temp=1.0, top_k=-1, top_p=1.0, min_p=0.0):
    return (
        jnp.full((B,), temp, jnp.float32),
        jnp.full((B,), top_k, jnp.int32),
        jnp.full((B,), top_p, jnp.float32),
        jnp.full((B,), min_p, jnp.float32),
    )


def test_greedy_matches_argmax():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (4, 100))
    toks, lps = sample_tokens(logits, key, *_params(4, temp=0.0))
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(jnp.argmax(logits, -1)))
    # logprob is log_softmax of chosen token
    ref = jax.nn.log_softmax(logits, -1)
    np.testing.assert_allclose(
        np.asarray(lps), np.asarray(jnp.max(ref, -1)), rtol=1e-5
    )


@pytest.mark.parametrize("top_k,top_p,min_p", [
    (5, 1.0, 0.0), (1, 1.0, 0.0), (64, 1.0, 0.0),
    (-1, 0.5, 0.0), (-1, 0.9, 0.0), (10, 0.7, 0.0),
    (-1, 1.0, 0.25),
])
def test_fast_masks_match_exact_support(top_k, top_p, min_p):
    """Both implementations must sample from the same support set (exactness
    holds when the nucleus fits in K_CAP candidates, so use peaky logits):
    with a shared gumbel key the masked argmax must coincide."""
    key = jax.random.PRNGKey(42)
    # exponential-decay logits: nucleus of any top_p < 1 fits well inside 64
    base = -0.4 * jnp.arange(512, dtype=jnp.float32)
    perm = jax.random.permutation(key, 512)
    logits = jnp.tile(base[perm][None], (8, 1)) + jax.random.normal(key, (8, 512)) * 0.01
    params = _params(8, 1.0, top_k, top_p, min_p)
    for i in range(5):
        k = jax.random.fold_in(key, i)
        t_fast, _ = sample_tokens(logits, k, *params)
        t_exact, _ = sample_tokens_exact(logits, k, *params)
        np.testing.assert_array_equal(np.asarray(t_fast), np.asarray(t_exact))


def test_top_k_one_is_greedy():
    key = jax.random.PRNGKey(7)
    logits = jax.random.normal(key, (6, 333))
    toks, _ = sample_tokens(logits, key, *_params(6, temp=1.0, top_k=1))
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(jnp.argmax(logits, -1)))


def test_top_p_tiny_keeps_top_token():
    key = jax.random.PRNGKey(9)
    logits = jax.random.normal(key, (6, 200))
    toks, _ = sample_tokens(logits, key, *_params(6, temp=1.0, top_p=1e-6))
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(jnp.argmax(logits, -1)))


def test_sampling_distribution_sane():
    """With temp=1, sampled frequencies should roughly track softmax probs."""
    key = jax.random.PRNGKey(3)
    logits = jnp.tile(jnp.array([[2.0, 1.0, 0.0, -1.0]]), (1, 1))
    probs = np.asarray(jax.nn.softmax(logits[0]))
    counts = np.zeros(4)
    N = 2000
    batched = jnp.tile(logits, (N, 1))
    toks, _ = sample_tokens(batched, key, *_params(N, temp=1.0))
    for t in np.asarray(toks):
        counts[t] += 1
    freq = counts / N
    np.testing.assert_allclose(freq, probs, atol=0.05)


def test_mixed_greedy_and_sampled_rows():
    key = jax.random.PRNGKey(11)
    logits = jax.random.normal(key, (4, 50))
    temps = jnp.array([0.0, 1.0, 0.0, 0.5], jnp.float32)
    toks, _ = sample_tokens(
        logits, key, temps,
        jnp.full((4,), -1, jnp.int32), jnp.ones((4,), jnp.float32), jnp.zeros((4,), jnp.float32),
    )
    am = np.asarray(jnp.argmax(logits, -1))
    t = np.asarray(toks)
    assert t[0] == am[0] and t[2] == am[2]


def _parent_top_values(z, k):
    """How ``sample_tokens`` got its thresholds' candidates until PR 33."""
    top_vals, _ = jax.lax.top_k(z, k)
    return top_vals


ROWS = ("normal", "halves", "equal", "masked0", "masked1", "masked63", "masked64",
        "masked65", "infs")


def _rows(kind, B, V):
    key = jax.random.PRNGKey(V + B)
    z = jax.random.normal(key, (B, V), jnp.float32) * 3.0
    if kind == "halves":  # hundreds of columns tie at the 64th place
        return jnp.round(z * 2.0) / 2.0
    if kind == "equal":
        return jnp.full((B, V), 1.25, jnp.float32)
    if kind.startswith("masked"):  # a grammar's row: NEG_INF but for n entries
        n = min(int(kind[len("masked"):]), V)
        keep = jnp.zeros((V,), bool).at[jax.random.permutation(key, V)[:n]].set(True)
        return jnp.where(keep[None], z, NEG_INF)
    if kind == "infs":
        return z.at[:, 7].set(jnp.inf).at[:, V // 2].set(-jnp.inf).at[:, V - 1].set(-jnp.inf)
    return z


@functools.cache
def _jitted(fn, k):
    return jax.jit(functools.partial(fn, k=k))


@pytest.mark.parametrize("B", [1, 16])
@pytest.mark.parametrize("kind", ROWS)
@pytest.mark.parametrize("V", [151936, 100352, 131072, 5000, 256, 48])
def test_top_values_are_top_k_to_the_bit(V, kind, B):
    z = _rows(kind, B, V)
    k = min(K_CAP, V)
    got = np.asarray(_jitted(top_values, k)(z))
    want = np.asarray(_jitted(_parent_top_values, k)(z))
    assert got.shape == want.shape == (B, k)
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


@pytest.mark.parametrize("masked", [False, True], ids=["open", "grammar"])
@pytest.mark.parametrize("V", [151936, 100352])
def test_sample_tokens_returns_what_the_whole_sort_gave(V, masked, monkeypatch):
    """Same key, same parameters: the tokens and log-probabilities of the
    sampler whose candidates came from ``lax.top_k`` of the whole row."""
    B = 16
    key = jax.random.PRNGKey(33)
    logits = jax.random.normal(key, (B, V), jnp.float32) * 4.0
    params = _params(B, temp=0.8, top_k=20, top_p=0.95, min_p=0.05)
    mask = None
    if masked:  # each row may choose among a few hundred entries of its own
        mask = jax.random.uniform(jax.random.fold_in(key, 1), (B, V)) < 300.0 / V
    def run():  # a function of its own each time, so that each call traces
        return jax.jit(lambda *a: sample_tokens(*a))(logits, key, *params, mask)

    toks, lps = run()
    monkeypatch.setattr(sampling, "top_values", _parent_top_values)
    want_toks, want_lps = run()
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(want_toks))
    np.testing.assert_array_equal(np.asarray(lps).view(np.int32),
                                  np.asarray(want_lps).view(np.int32))
    assert len(set(np.asarray(toks).tolist())) > 1
    if masked:
        assert bool(mask[jnp.arange(B), toks].all())


_TENSOR = re.compile(r"tensor<([\dx]+)x[a-z]")


@pytest.mark.parametrize("B", [16, 1])
def test_no_sort_of_the_whole_vocabulary(B):
    """The module's docstring says "no full-vocab sort": no ``sort`` or
    ``top_k`` of the lowered sampler takes or gives a row the vocabulary
    wide."""
    V = 151936
    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype)  # noqa: E731
    text = jax.jit(sample_tokens).lower(
        s((B, V), jnp.float32), jax.random.PRNGKey(0), s((B,), jnp.float32),
        s((B,), jnp.int32), s((B,), jnp.float32), s((B,), jnp.float32), s((B, V), bool),
    ).as_text()
    assert "stablehlo.sort" not in text
    top_ks = [line for line in text.splitlines() if "chlo.top_k" in line]
    assert top_ks, "the sampler's top_k should show in the lowered text"
    widths = {int(dims.split("x")[-1]) for line in top_ks for dims in _TENSOR.findall(line)}
    assert widths and V not in widths, top_ks

"""On-device batched sampling: temperature / top-k / top-p / min-p, greedy mix.

One fused function over the whole decode batch with per-slot parameter arrays
(continuous batching mixes requests with different sampling configs in one
step).  Wire-parity with the reference's ``SamplingParams``
(``sglang_scheduler.proto:67-101``).

TPU-first implementation: **no full-vocab sort**.  Filtering works by
computing per-row probability thresholds from the ``K_CAP`` largest values of
a row, then sampling with gumbel-argmax over the masked logits.  Those values
come from ``top_values``: the maxima of the row's blocks of 128 columns,
``lax.top_k`` over the maxima, the ``K_CAP`` blocks it names gathered, the
same once more inside them with blocks of 16, and ``lax.top_k`` over the 1,024
candidates left.  That is exact in the values (bitwise ``lax.top_k`` of the
row; ties cost nothing because no index is used), where ``lax.top_k`` of a
152k-column row, compared against the row afterwards as the thresholds are,
compiles for the v5e as a sort of the whole row: 3.2 ms of a 10.7 ms decode
column (PERF.md, PR 33).  A row too short to hold four blocks for each value
wanted is not cut at that block size, so the toy vocabularies of the tests are
sorted as they are; the choice rests on the static shape of the logits alone.
top-k is exact for ``top_k <= K_CAP``; top-p is exact whenever the
nucleus fits in ``K_CAP`` candidates and conservatively includes the whole
distribution otherwise (wider, never narrower, than requested).  A full-sort
exact reference (``sample_tokens_exact``) backs the property tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
K_CAP = 64  # top-k candidates examined for thresholds
# ``top_values`` cuts a row into blocks of the first size, the blocks it keeps
# into blocks of the second (one lane tile of a float32 row, then an eighth)
BLOCKS = (128, 16)
MIN_BLOCKS_PER_K = 4  # fewer blocks than this for each value wanted: no cut


def _held_blocks(z: jnp.ndarray, k: int, block: int) -> jnp.ndarray:
    """Cut each row of ``z`` [B, V] into blocks of ``block`` columns (the
    tail padded with -inf) and keep the ``k`` blocks whose maxima are
    largest: [B, k * block], which holds the row's ``k`` largest values."""
    B, V = z.shape
    pad = -V % block
    if pad:
        z = jnp.concatenate([z, jnp.full((B, pad), -jnp.inf, z.dtype)], axis=1)
    blocks = z.reshape(B, -1, block)
    _, held = jax.lax.top_k(blocks.max(axis=-1), k)  # [B, k] block numbers
    held = jnp.take_along_axis(blocks, held[:, :, None], axis=1, mode="promise_in_bounds")
    return held.reshape(B, k * block)


def top_values(z: jnp.ndarray, k: int, blocks: tuple[int, ...] = BLOCKS) -> jnp.ndarray:
    """The ``k`` largest values of each row of ``z`` [B, V], descending:
    bitwise ``jax.lax.top_k(z, k)[0]``, without a sort of the row.

    Only values are wanted, so ties cost nothing.  Every element above the
    row's k-th largest value v sits in a block whose maximum is above v, there
    are fewer than k such blocks, and the k blocks with the largest maxima
    hold all of them; the places left go to blocks whose maximum equals v,
    one copy of v each.  The k largest of those blocks' elements are
    therefore the k largest of the row.  The same holds of the candidates,
    cut again into smaller blocks.  A row is cut at a block size only where
    it has ``MIN_BLOCKS_PER_K`` x k such blocks or more: a shorter row is
    sorted as it is."""
    for block in blocks:
        if z.shape[1] >= MIN_BLOCKS_PER_K * k * block:
            z = _held_blocks(z, k, block)
    return jax.lax.top_k(z, k)[0]


@jax.named_scope("smg.sample")
def sample_tokens(
    logits: jnp.ndarray,  # [B, V] float32
    key: jax.Array,
    temperature: jnp.ndarray,  # [B] (0 => greedy)
    top_k: jnp.ndarray,  # [B] int32 (-1 => disabled)
    top_p: jnp.ndarray,  # [B] (1.0 => disabled)
    min_p: jnp.ndarray,  # [B] (0.0 => disabled)
    mask: jnp.ndarray | None = None,  # [B, V] bool: sampleable vocabulary
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (tokens [B] int32, logprobs [B] float32 of the chosen token
    under the *unfiltered* distribution — OpenAI logprob semantics).

    ``mask`` (grammar-constrained decoding) hard-excludes tokens before any
    filtering; logprobs are then reported under the mask-renormalized
    distribution, since the excluded tokens were never sampleable."""
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    B, V = logits.shape
    greedy = temperature <= 0.0
    safe_temp = jnp.where(greedy, 1.0, temperature)
    z = (logits / safe_temp[:, None]).astype(jnp.float32)

    # top-K_CAP candidates give us every threshold we need
    k_cap = min(K_CAP, V)
    top_vals = top_values(z, k_cap)  # [B, k_cap] descending

    # top-k threshold: value of the k-th largest (clamped to k_cap)
    k_eff = jnp.where(top_k <= 0, k_cap, jnp.minimum(top_k, k_cap)).astype(jnp.int32)
    kth = jnp.take_along_axis(top_vals, (k_eff - 1)[:, None], axis=1)[:, 0]
    thresh_k = jnp.where(top_k <= 0, -jnp.inf, kth)  # disabled => no filter

    # top-p applies to the distribution *after* top-k renormalization
    # (sequential-filter semantics, matching the exact reference).  With
    # top-k on (k <= K_CAP) the candidates cover the entire filtered set, so
    # renormalization over them is exact; with top-k off, normalize over the
    # full row.
    cand_idx = jax.lax.broadcasted_iota(jnp.int32, (B, k_cap), 1)
    in_topk = cand_idx < k_eff[:, None]
    masked_vals = jnp.where(in_topk | (top_k[:, None] <= 0), top_vals, -jnp.inf)
    lse_full = jax.nn.logsumexp(z, axis=-1, keepdims=True)  # [B, 1]
    lse_topk = jax.nn.logsumexp(masked_vals, axis=-1, keepdims=True)
    denom = jnp.where((top_k > 0)[:, None], lse_topk, lse_full)
    cand_probs = jnp.exp(masked_vals - denom)  # [B, K_CAP] descending
    cum_excl = jnp.cumsum(cand_probs, axis=-1) - cand_probs
    in_nucleus = (cum_excl < top_p[:, None]) & (cand_probs > 0)  # keeps top-1
    # smallest kept candidate's logit = threshold; if the nucleus spills past
    # K_CAP (only possible with top-k off), conservatively keep everything
    spills = (cum_excl[:, -1] + cand_probs[:, -1] < top_p) & (top_k <= 0)
    kept_vals = jnp.where(in_nucleus, top_vals, jnp.inf)
    thresh_p = jnp.min(kept_vals, axis=-1)
    thresh_p = jnp.where(spills | (top_p >= 1.0), -jnp.inf, thresh_p)

    # min-p threshold: min_p * max_prob, in logit space
    max_logit = top_vals[:, 0]
    thresh_m = jnp.where(
        min_p > 0.0,
        max_logit + jnp.log(jnp.maximum(min_p, 1e-10)),
        -jnp.inf,
    )

    thresh = jnp.maximum(jnp.maximum(thresh_k, thresh_p), thresh_m)
    zf = jnp.where(z >= thresh[:, None], z, NEG_INF)

    g = jax.random.gumbel(key, z.shape, jnp.float32)
    sampled = jnp.argmax(zf + g, axis=-1)
    greedy_tok = jnp.argmax(logits, axis=-1)
    tokens = jnp.where(greedy, greedy_tok, sampled).astype(jnp.int32)

    # chosen-token logprob under the unfiltered distribution (no sort):
    # logprob = logit/T? No — OpenAI semantics: log softmax of raw logits.
    raw_lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    chosen_logit = jnp.take_along_axis(
        logits.astype(jnp.float32), tokens[:, None].astype(jnp.int32), axis=-1
    )[:, 0]
    return tokens, chosen_logit - raw_lse


@jax.named_scope("smg.sample")
def sample_tokens_exact(
    logits: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
    min_p: jnp.ndarray,
    mask: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sort reference implementation (exact for any top_k/top_p).
    Used by tests and available via SMG_EXACT_SAMPLING=1."""
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    B, V = logits.shape
    greedy = temperature <= 0.0
    safe_temp = jnp.where(greedy, 1.0, temperature)
    z = logits / safe_temp[:, None]

    order = jnp.argsort(-z, axis=-1)
    ranks = jnp.argsort(order, axis=-1)
    k_eff = jnp.where(top_k <= 0, V, top_k).astype(jnp.int32)
    z = jnp.where(ranks < k_eff[:, None], z, NEG_INF)

    probs = jax.nn.softmax(z, axis=-1)
    sorted_probs = jnp.take_along_axis(probs, order, axis=-1)
    cum_excl = jnp.cumsum(sorted_probs, axis=-1) - sorted_probs
    keep_sorted = cum_excl < top_p[:, None]
    keep = jnp.take_along_axis(keep_sorted, ranks, axis=-1)
    z = jnp.where(keep, z, NEG_INF)

    probs = jax.nn.softmax(z, axis=-1)
    max_prob = probs.max(axis=-1, keepdims=True)
    z = jnp.where(probs >= min_p[:, None] * max_prob, z, NEG_INF)

    g = jax.random.gumbel(key, z.shape, jnp.float32)
    sampled = jnp.argmax(z + g, axis=-1)
    greedy_tok = jnp.argmax(logits, axis=-1)
    tokens = jnp.where(greedy, greedy_tok, sampled).astype(jnp.int32)

    all_logprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    chosen = jnp.take_along_axis(all_logprobs, tokens[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return tokens, chosen


def _filtered_probs(
    logits: jnp.ndarray,  # [T, V] float32
    temperature: jnp.ndarray,  # scalar (> 0)
    top_k: jnp.ndarray,  # scalar int32 (-1 => disabled)
    top_p: jnp.ndarray,  # scalar (1.0 => disabled)
    min_p: jnp.ndarray,  # scalar (0.0 => disabled)
) -> jnp.ndarray:
    """Exact sequential temperature/top-k/top-p/min-p filtering shared by
    all T rows (one request's verify chunk) -> renormalized probs [T, V].
    Full-sort exact path (``sample_tokens_exact`` semantics): verify calls
    are per-request and rare, so exactness beats the sort cost."""
    T, V = logits.shape
    z = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    order = jnp.argsort(-z, axis=-1)
    ranks = jnp.argsort(order, axis=-1)
    k_eff = jnp.where(top_k <= 0, V, top_k).astype(jnp.int32)
    z = jnp.where(ranks < k_eff, z, NEG_INF)
    probs = jax.nn.softmax(z, axis=-1)
    sorted_probs = jnp.take_along_axis(probs, order, axis=-1)
    cum_excl = jnp.cumsum(sorted_probs, axis=-1) - sorted_probs
    keep = jnp.take_along_axis(cum_excl < top_p, ranks, axis=-1)
    z = jnp.where(keep, z, NEG_INF)
    probs = jax.nn.softmax(z, axis=-1)
    max_prob = probs.max(axis=-1, keepdims=True)
    z = jnp.where(probs >= min_p * max_prob, z, NEG_INF)
    return jax.nn.softmax(z, axis=-1)


def spec_accept_sample(
    logits: jnp.ndarray,  # [T, V] verify-forward logits (row i = dist after chunk[:i+1])
    proposals: jnp.ndarray,  # [K] int32 draft tokens (padded; k_real valid)
    k_real: jnp.ndarray,  # scalar int32
    key: jax.Array,
    temperature: jnp.ndarray,  # scalar > 0
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
    min_p: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Distribution-preserving speculative acceptance (rejection sampling,
    Leviathan/Chen speculative sampling specialized to a DETERMINISTIC
    draft).  The draft proposed token x_i deterministically, i.e. the
    proposal distribution q_i is the point mass on x_i, so:

    - accept x_i with probability min(1, p_i(x_i)/q_i(x_i)) = p_i(x_i);
    - on first rejection, sample from the residual (p_i - q_i)+ / Z =
      p_i with x_i zeroed, renormalized;
    - with every proposal accepted, sample the bonus token from p_K.

    The marginal distribution of the emitted tokens equals sampling from
    the target's filtered distribution exactly (tests pin this with a
    Monte-Carlo chi-square check).  Returns (final_token, n_accepted):
    the caller commits ``proposals[:n_accepted] + [final_token]``."""
    K = proposals.shape[0]
    V = logits.shape[-1]
    probs = _filtered_probs(logits, temperature, top_k, top_p, min_p)  # [T, V]
    key_u, key_s = jax.random.split(key)
    rows = jnp.arange(K)
    p_prop = probs[rows, jnp.clip(proposals, 0, V - 1)]  # [K]
    u = jax.random.uniform(key_u, (K,))
    accept = (u < p_prop) & (rows < k_real)
    n_acc = jnp.cumprod(accept.astype(jnp.int32)).sum()
    row = jnp.take(probs, jnp.minimum(n_acc, probs.shape[0] - 1), axis=0)  # [V]
    is_bonus = n_acc >= k_real
    rejected = jnp.clip(proposals[jnp.minimum(n_acc, K - 1)], 0, V - 1)
    resid = row * (1.0 - jax.nn.one_hot(rejected, V, dtype=row.dtype))
    resid_sum = resid.sum()
    dist = jnp.where(
        is_bonus | (resid_sum <= 0.0),
        row,
        resid / jnp.maximum(resid_sum, 1e-20),
    )
    final = jax.random.categorical(key_s, jnp.log(jnp.maximum(dist, 1e-38)))
    return final.astype(jnp.int32), n_acc.astype(jnp.int32)


def apply_penalties(
    logits: jnp.ndarray,  # [B, V]
    output_counts: jnp.ndarray,  # [B, V] int32: count of each token in the output so far
    prompt_mask: jnp.ndarray,  # [B, V] bool: token appeared in prompt
    frequency_penalty: jnp.ndarray,  # [B]
    presence_penalty: jnp.ndarray,  # [B]
    repetition_penalty: jnp.ndarray,  # [B]
) -> jnp.ndarray:
    """OpenAI frequency/presence penalties + HF-style repetition penalty."""
    logits = logits - frequency_penalty[:, None] * output_counts
    logits = logits - presence_penalty[:, None] * (output_counts > 0)
    seen = (output_counts > 0) | prompt_mask
    rp = repetition_penalty[:, None]
    penalized = jnp.where(logits > 0, logits / rp, logits * rp)
    return jnp.where(seen, penalized, logits)

"""Independent users at a fixed rate, one request each, nothing shared.
``rate_per_s * seconds`` requests; their gaps are the exponential
distribution's quantiles in a shuffled order, so the load and the multiset of
gaps are fixed (a Poisson process's mean and spread of gaps, without its
run-to-run change in the count)."""

from __future__ import annotations

from .common import request, rngs, stratified


def chains(params: dict, seed: int, seconds: float) -> list[dict]:
    rng, words_rng = rngs(seed)
    n = max(int(round(params["rate_per_s"] * seconds)), 1)
    gaps = stratified({"dist": "exponential", "mean": 1.0 / params["rate_per_s"]}, n, rng)
    prompts = stratified(params["prompt_tokens"], n, rng, integer=True)
    outputs = stratified(params["output_tokens"], n, rng, integer=True)
    out = []
    # the gaps sum to a little under ``seconds``: centre the arrivals in the window
    t = max(seconds - sum(gaps), 0.0) / 2.0 - gaps[0] / 2.0
    for gap, p, o in zip(gaps, prompts, outputs):
        t += gap
        out.append({"start": t, "requests": [request(words_rng, p, o)]})
    return out

"""A fixed number of callers, each sending its next request when the last
one ended, with no think time.  Sizes are dealt in rounds (``common.dealt``),
``pool_per_client`` of them; a caller who has sent them all starts over with
other words (``common.again``), so the pool is no ceiling.  Client ``c``
starts ``c * ramp_s / clients`` into the window, so that the callers are out
of step from the start, as they are once they have run for a while."""

from __future__ import annotations

from .common import dealt, request, rngs


def chains(params: dict, seed: int, seconds: float) -> list[dict]:
    rng, words_rng = rngs(seed)
    clients = int(params["clients"])
    per = int(params["pool_per_client"])
    prompts = dealt(params["prompt_tokens"], clients, per, rng, integer=True)
    outputs = dealt(params["output_tokens"], clients, per, rng, integer=True)
    ramp = float(params.get("ramp_s", 0.0))
    return [
        {"start": c * ramp / clients, "starts_over": True, "requests": [
            request(words_rng, prompts[k][c], outputs[k][c]) for k in range(per)]}
        for c in range(clients)
    ]

"""Readers of documents at a fixed concurrency: each of ``clients`` callers
opens a document, asks ``turns`` questions about it (the document followed by
the question), each a think time after the last answer ended, and then opens
the next document.  A closed loop: the offered load follows the system's
speed, so the system settles where that many readers hold it.  Sizes and
think times are dealt in rounds (``common.dealt``).  Client ``c`` starts
``c * ramp_s / clients`` into the window, out of step with the rest."""

from __future__ import annotations

from .common import WORD_SEEDS, dealt, request, rngs


def chains(params: dict, seed: int, seconds: float) -> list[dict]:
    rng, words_rng = rngs(seed)
    clients, turns = int(params["clients"]), int(params["turns"])
    # a reader who has been through them all starts over with other documents
    per = int(params["documents_per_client"])
    docs = dealt(params["document_tokens"], clients, per, rng, integer=True)
    questions = dealt(params["question_tokens"], clients, per * turns, rng, integer=True)
    outputs = dealt(params["output_tokens"], clients, per * turns, rng, integer=True)
    thinks = dealt(params["think_s"], clients, per * turns, rng)
    ramp = float(params.get("ramp_s", 0.0))
    out = []
    for c in range(clients):
        reqs = []
        for d in range(per):
            prefix = [words_rng.randrange(WORD_SEEDS), docs[d][c]]
            for k in range(turns):
                j = d * turns + k
                # the first question of the first document goes at once; every
                # other request waits its think time after the answer before it
                reqs.append(request(words_rng, questions[j][c], outputs[j][c], prefix=prefix,
                                    gap=thinks[j][c] if reqs else 0.0))
        out.append({"start": c * ramp / clients, "starts_over": True, "requests": reqs})
    return out

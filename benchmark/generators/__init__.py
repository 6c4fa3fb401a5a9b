"""Traffic generators, one module per kind.

A traffic mix is a data file ``benchmark/traffic/<mix>.json`` whose
``generator`` names a module here.  Every module exposes

    chains(params: dict, seed: int, seconds: float) -> list[dict]

and returns *chains*: ``{"start": s, "requests": [{"gap": g, "prefix": [seed,
n] | None, "body": [seed, n], "max_tokens": m}, ...]}``.  The first request of
a chain is due ``start`` seconds into the window, each later one ``gap``
seconds after the one before it ended.  A chain with ``"starts_over": true``
(a caller of a closed loop) goes through its requests again with other words
when it has sent the last (``common.again``).  One executor (``loadgen.py``) runs
every kind: an open loop is chains of one request, a closed loop is one long
chain per client with no gaps, a session is a chain with think times.

Every run gets the same multiset of sizes and gaps in the same arrangement
(``common.stratified`` or ``common.dealt``, shuffled once by
``common.ARRANGEMENT``); ``--seed`` draws the words.  Why the arrangement is
not the seed's: ``common.py``.
"""

"""Arithmetic shared by the generators: distributions as quantile functions,
stratified draws, and the prompt words.  Standard library only: the load
generator's process must never import JAX."""

from __future__ import annotations

import math
import random
from statistics import NormalDist

#: the mock tokenizer's chat template adds "[user]" and "[assistant]"
TEMPLATE_TOKENS = 2


def quantile(dist: dict, u: float) -> float:
    """The ``u`` quantile (0 < u < 1) of a distribution given as data."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "exponential":
        x = -dist["mean"] * math.log1p(-u)
    elif kind == "constant":
        x = dist["value"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist:
        x = max(x, dist["min"])
    if "max" in dist:
        x = min(x, dist["max"])
    return x


def stratified(dist: dict, n: int, rng: random.Random, integer: bool = False) -> list:
    """``n`` values at the quantiles (i + 1/2) / n, shuffled by ``rng``: the
    same multiset for every seed, in another order."""
    vals = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    if integer:
        vals = [int(round(v)) for v in vals]
    rng.shuffle(vals)
    return vals


def dealt(dist: dict, hands: int, rounds: int, rng: random.Random,
          integer: bool = False) -> list:
    """The ``hands * rounds`` stratified values of ``stratified`` dealt in
    rounds: ``out[k][h]`` is hand ``h``'s value in round ``k``.  A round takes
    one value from each of ``hands`` equal slices of the distribution, at
    offsets inside the slices that are themselves spread evenly, so every
    round by itself covers the distribution evenly.  Callers in a closed
    loop, who get through the first few rounds only, then meet the same work
    whichever rounds they reach.  ``rng`` shuffles the rounds and, inside
    each, who gets which value."""
    step = max(rounds // hands, 1)
    order = list(range(rounds))
    rng.shuffle(order)
    out = []
    for k in order:
        vals = [quantile(dist, (i + ((k + i * step) % rounds + 0.5) / rounds) / hands)
                for i in range(hands)]
        if integer:
            vals = [int(round(v)) for v in vals]
        rng.shuffle(vals)
        out.append(vals)
    return out


def words(seed: int, n: int, vocab: int) -> str:
    """``n`` mock-tokenizer tokens: one ``w<id>`` word is one token."""
    rng = random.Random(seed)
    return " ".join(f"w{rng.randrange(2, vocab)}" for _ in range(n))


#: The one arrangement every run replays: which caller sends which size, in
#: which round, and the order of an open loop's gaps.  Not a parameter.  On the
#: chip another arrangement of the same sizes moved tokens per second by 11 %
#: and the tails by 20 % and more, closed loop and open (PERF.md, Findings, PR
#: 24): lanes share the widest lane's page table and a megastep's length, so
#: the order is part of the work.  Parent and change are compared on the same
#: work; what differs from seed to seed is the words.
ARRANGEMENT = 0


def rngs(seed: int) -> tuple[random.Random, random.Random]:
    """``(arrangement, contents)``: the deal of the fixed multiset of sizes,
    gaps and think times, the same in every run, and the words, from
    ``--seed``."""
    return random.Random(ARRANGEMENT), random.Random((seed << 1) ^ 0x9E3779B9)


#: word seeds are drawn below this, so a lap's shifted seeds meet no dealt one
WORD_SEEDS = 1 << 30


def again(requests: list, i: int, lap: int) -> dict:
    """Request ``i`` of a chain on its lap ``lap`` >= 1.  A chain that has sent
    its last dealt request starts over (``loadgen.run_chain``): the same sizes
    and gaps in the same order, with other words, and other documents too, so
    that the repeat finds nothing cached.  A lap's first request waits as the
    deal's last one did (no time in a closed loop, a think time in a session).
    The deal itself is left alone: another pool size deals another arrangement
    (``dealt``), and that is other work (``ARRANGEMENT``)."""
    spec = requests[i]
    shift = lambda part: part and [part[0] + lap * WORD_SEEDS, part[1]]
    return {**spec, "gap": spec["gap"] if i else requests[-1]["gap"],
            "prefix": shift(spec["prefix"]), "body": shift(spec["body"])}


def request(rng: random.Random, body_tokens: int, max_tokens: int,
            prefix: list | None = None, gap: float = 0.0) -> dict:
    """One request.  ``body_tokens`` counts the template's two tokens, so the
    server's ``prompt_tokens`` is ``prefix tokens + body_tokens``."""
    return {
        "gap": gap, "prefix": prefix,
        "body": [rng.randrange(WORD_SEEDS), max(int(body_tokens) - TEMPLATE_TOKENS, 1)],
        "max_tokens": int(max_tokens),
    }

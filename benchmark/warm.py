"""Warm every program a cell's traffic can reach, so that nothing compiles
inside the window.

The runner compiles one program per (family, padded shape): grouped prefill
by (group size G, token bucket T, whether every member starts cold), solo
prefill and the KV-only chunk by T, decode by (batch bucket, page-table
width).  Which of them a run launches depends on how arrivals happen to share
a step, so replaying traffic until the program count stops growing leaves the
rare ones for the window to hit.  Instead the reachable set is worked out
from the lengths of the window's own requests (``lengths``) and the program's
own bucket functions (``SchedulerConfig.prefill_bucket`` / ``decode_bucket``,
``Scheduler._mp_bucket``) and per-step budget, and each program is run once
the way ``ModelRunner.warmup`` runs its four: zero page tables, so every KV
write lands on the garbage page.

What only the program can shorten: the count itself (one program per key)
and what each costs cold; both are printed.
"""

from __future__ import annotations

import time


def lengths(chains: list, page_size: int) -> dict:
    """What the requests of ``chains`` can ask of the runner: ``fresh``, the
    [min, max] new tokens of a prompt that finds nothing cached; ``tail``,
    those of a prompt whose prefix an earlier request of its chain left in
    the cache (the match ends on a page boundary); ``context``, the tokens a
    request holds while it decodes; ``concurrency``, the most requests in
    flight (a chain has one at a time)."""
    fresh, tail, ctx = [], [], []
    for chain in chains:
        seen = set()
        for r in chain["requests"]:
            own = r["body"][1] + 2  # the chat template's two tokens
            prompt = own + (r["prefix"][1] if r["prefix"] else 0)
            fresh.append(prompt)
            ctx += [prompt, prompt + r["max_tokens"]]
            if r["prefix"]:
                if tuple(r["prefix"]) in seen:
                    tail += [own, own + page_size]
                seen.add(tuple(r["prefix"]))
    span = lambda v: [min(v), max(v)] if v else None
    return {"fresh": span(fresh), "tail": span(tail), "context": span(ctx),
            "concurrency": len(chains)}


def _bucket_span(bucket_of, lo: int, hi: int) -> list:
    """``(bucket, smallest length landing in it)`` for the lengths lo..hi."""
    out: dict = {}
    for n in range(lo, hi + 1):
        out.setdefault(bucket_of(n), n)
    return sorted(out.items())


def reachable(sched, mp_bucket, want: dict, page_size: int) -> dict:
    """The program shapes that requests of the lengths ``want`` can reach
    under the scheduler configuration ``sched``; ``mp_bucket`` is the
    scheduler's page-table width for a number of pages."""
    budget = sched.max_prefill_tokens
    fresh, tail = want.get("fresh"), want.get("tail")
    groups = []
    g = 1
    while g <= sched.max_prefill_group:  # the runner pads a group to a power of two
        groups.append(g)
        g *= 2
    batched = []
    kinds = []
    if fresh:
        kinds.append((True, [fresh]))
    if tail:
        kinds.append((False, [tail, fresh]))
    for no_ctx, ranges in kinds:
        smallest = min(r[0] for r in ranges)
        seen = set()
        for lo, hi in ranges:
            for T, t_lo in _bucket_span(sched.prefill_bucket, lo, min(hi, budget)):
                for G in groups:
                    others = 0 if G == 1 else G // 2  # a group pads up from G/2 + 1 members
                    if t_lo + others * smallest <= budget and (G, T) not in seen:
                        seen.add((G, T))
                        batched.append((G, T, no_ctx))
    longest = max([r[1] for r in (fresh, tail) if r], default=0)
    chunk = ([T for T, _ in _bucket_span(sched.prefill_bucket, 1, min(longest, budget))]
             if longest else [])
    ctx = want.get("context")
    widths = []
    if ctx:
        pages = lambda tokens: -(-tokens // page_size)
        hi = min(ctx[1], sched.max_seq_len) + sched.horizon_cap
        widths = sorted({mp_bucket(pages(n)) for n in range(ctx[0] + 1, hi + 1)})
    top = sched.decode_bucket(min(sched.max_batch_size, want.get("concurrency") or 1))
    batches = sorted({sched.decode_bucket(b) for b in range(1, top + 1)})
    decode = [(B, w) for B in batches for w in widths]
    return {"batched": sorted(batched), "solo": chunk, "extend": chunk, "decode": decode}


def warm_shapes(engine, chains: list, log) -> list:
    """Run each program the requests of ``chains`` can reach once; returns
    ``(name, seconds)`` pairs."""
    import jax
    import numpy as np

    runner = engine.runner
    sched = engine.config.scheduler
    mp = runner.max_pages_per_seq
    ps = engine.config.cache.page_size
    want = lengths(chains, ps)
    shapes = reachable(sched, engine.scheduler._mp_bucket, want, ps)
    log(f"warm: lengths {want}")
    table = np.zeros(mp, np.int32)
    took = []

    def run(name, fn):
        t = time.perf_counter()
        try:
            fn()
            jax.block_until_ready((runner.k_cache, runner.v_cache))
        except Exception as e:  # noqa: BLE001 - report and go on: serving would meet it too
            log(f"warm: {name} FAILED: {type(e).__name__}: {str(e)[:300]}")
            took.append((name, None))
            return
        took.append((name, time.perf_counter() - t))

    mark = runner.rng_mark()
    limit = sched.max_seq_len - 1
    for G, T, no_ctx in shapes["batched"]:
        t = min(T, limit)
        group = [([0] * t, 0 if no_ctx else 1, table)] * G
        zeros, ones = np.zeros(G, np.float32), np.ones(G, np.float32)
        run(f"prefill_batched G={G} T={T} no_ctx={no_ctx}",
            lambda: runner.prefill_batched(group, zeros, np.full(G, -1, np.int32), ones, zeros))
    for T in shapes["extend"]:
        run(f"prefill_extend T={T}", lambda: runner.prefill_extend([0] * min(T, limit), 0, table))
    for T in shapes["solo"]:
        run(f"prefill T={T}",
            lambda: runner.prefill([0] * min(T, limit), 0, table, 0.0, -1, 1.0, 0.0))
    N = sched.horizon_cap
    sliced = set()

    def decode(B, w):
        zeros, ones = np.zeros(B, np.float32), np.ones(B, np.float32)
        never = (np.full((B, 1), -1, np.int32), np.full(B, np.int32(2**30)), np.ones(B, bool))
        toks, _lps, _steps = runner.decode_multi_async(
            np.zeros(B, np.int32), np.full(B, w * ps, np.int32), np.zeros((B, w), np.int32),
            zeros, np.full(B, -1, np.int32), ones, zeros, N, max_steps=N, stop_state=never)
        if B not in sliced:
            # the overlapped schedule chains the next launch from column K-1
            # of the frame in flight with a static slice: one tiny program
            # for every (batch bucket, K)
            sliced.add(B)
            for k in range(N):
                jax.lax.index_in_dim(toks, k, axis=1, keepdims=False)
        jax.block_until_ready(toks)

    for B, w in shapes["decode"]:
        run(f"decode_multi B={B} mp={w}", lambda: decode(B, w))
    runner.rng_restore(mark)
    done = [s for _, s in took if s is not None]
    log(f"warm: {len(took)} programs in {sum(done):.1f}s "
        f"(slowest {max(done, default=0):.1f}s): "
        + ", ".join(f"{n} {s:.1f}s" if s is not None else f"{n} FAILED" for n, s in took))
    return took

#!/usr/bin/env python3
"""The load generator: a process of its own that never imports JAX (a chip
belongs to one process, and a client shares no interpreter lock with the
server in any deployment).

    python3 benchmark/loadgen.py <plan.json> <result.json>

The plan (written by ``run.py``) holds the server's port, the window length
and the chains of ``generators/``.  One asyncio loop sends every request as a
streaming ``/v1/chat/completions`` call with ``ignore_eos`` and an exact
``max_tokens``, and stamps ``time.monotonic()`` (CLOCK_MONOTONIC, which the
server process shares) when each request was due, was sent, brought its first
and its last content delta, and ended.  A chain's first request is due at
``t0 + start``; each later one ``gap`` seconds after the one before it ended.
A chain marked ``starts_over`` (a caller of a closed loop) that has sent its
last request goes through them again with other words
(``generators.common.again``), so no caller falls silent inside a window.
Requests due after the window's end are not sent.  After the window the
requests in flight are given ``drain_s`` seconds to end.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import os
import sys
import time

import aiohttp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from generators.common import again, words  # noqa: E402


async def one_request(session, plan: dict, rec: dict, spec: dict) -> None:
    vocab = plan["vocab"]
    parts = []
    if spec["prefix"]:
        parts.append(words(spec["prefix"][0], spec["prefix"][1], vocab))
    parts.append(words(spec["body"][0], spec["body"][1], vocab))
    body = json.dumps({
        "messages": [{"role": "user", "content": " ".join(parts)}],
        "max_tokens": spec["max_tokens"], "temperature": 0, "ignore_eos": True,
        "stream": True, "stream_options": {"include_usage": True},
    })
    rec["sent"] = time.monotonic()
    try:
        async with session.post(
            plan["url"] + "/v1/chat/completions", data=body,
            headers={"Content-Type": "application/json", "X-Request-Id": rec["id"]},
        ) as resp:
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue
                data = raw[5:].strip()
                if data == b"[DONE]":
                    break
                now = time.monotonic()
                chunk = json.loads(data)
                if chunk.get("error"):
                    rec["error"] = json.dumps(chunk["error"])[:200]
                usage = chunk.get("usage")
                if usage:
                    rec["output_tokens"] = usage.get("completion_tokens", 0)
                    rec["prompt_tokens"] = usage.get("prompt_tokens", 0)
                    rec["cached_tokens"] = (usage.get("prompt_tokens_details") or {}).get(
                        "cached_tokens", 0)
                for choice in chunk.get("choices", ()):
                    content = (choice.get("delta") or {}).get("content")
                    if content:
                        if rec["first"] is None:
                            rec["first"] = now
                        rec["last"] = now
                        rec["chunks"] += 1
                        # the mock tokenizer writes one word per token (and none for
                        # a sampled special id, two in 151,936: the count errs low)
                        words_in = len(content.split())
                        rec["streamed_tokens"] += words_in
                        if now <= plan["t_end"]:
                            rec["tokens_in_window"] += words_in
                    if choice.get("finish_reason"):
                        rec["finish"] = choice["finish_reason"]
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError, OSError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        rec["done"] = time.monotonic()


async def run_chain(session, plan: dict, t0: float, ci: int, chain: dict,
                    records: list) -> None:
    end = t0 + plan["seconds"]
    due = t0 + chain["start"]
    prev_done = due
    dealt = chain["requests"]
    for k in itertools.count():
        lap, i = divmod(k, len(dealt))
        if lap and not chain.get("starts_over"):
            return
        spec = again(dealt, i, lap) if lap else dealt[i]
        if k:
            due = prev_done + spec["gap"]
        if due >= end:
            return
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = {
            "id": f"{plan['tag']}-{ci}-{k}", "chain": ci, "turn": k, "lap": lap, "due": due,
            "sent": None, "first": None, "last": None, "done": None, "chunks": 0,
            "want_prompt_tokens": (spec["prefix"][1] if spec["prefix"] else 0)
            + spec["body"][1] + 2,
            "want_output_tokens": spec["max_tokens"],
            "output_tokens": 0, "prompt_tokens": 0, "cached_tokens": 0,
            "streamed_tokens": 0, "tokens_in_window": 0,
            "finish": None, "error": None,
        }
        records.append(rec)
        await one_request(session, plan, rec, spec)
        prev_done = rec["done"]
        if lap and rec["error"]:
            return  # a server that refuses everything is not asked without end


async def amain(plan: dict) -> dict:
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None, sock_read=plan["seconds"] + plan["drain_s"])
    records: list = []
    async with aiohttp.ClientSession(connector=conn, timeout=timeout) as session:
        t0 = max(plan.get("t0") or 0.0, time.monotonic() + 0.2)
        plan["t_end"] = t0 + plan["seconds"]
        tasks = [asyncio.create_task(run_chain(session, plan, t0, ci, chain, records))
                 for ci, chain in enumerate(plan["chains"])]
        _done, pending = await asyncio.wait(
            tasks, timeout=t0 + plan["seconds"] + plan["drain_s"] - time.monotonic())
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        for t in tasks:
            if not t.cancelled() and t.exception() is not None:
                raise t.exception()
    return {"t0": t0, "seconds": plan["seconds"], "unfinished_chains": len(pending),
            "requests": records}


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        plan = json.load(f)
    result = asyncio.run(amain(plan))
    tmp = argv[2] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, argv[2])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""What the readers of a cell whose attention reads a learned selection of the
cache share (``glm-5.2.longdoc``).  (A file whose name starts with ``_`` is
not a metric.)"""

from __future__ import annotations

from _common import columns_run, peak
from _scope_time import FAMILY, split_of, under

MODEL_TYPE = "glm_moe_dsa"

#: the indexer's scopes: its queries, its keys (projection, norm, rotary, the
#: cache write and the gather of a context's keys), the scores, the selection
INDEX = ("smg.mla.index",)
#: of those, the part that grows with the context: every cached token's key
#: meets every index head, and the row's largest are found
INDEX_OVER_CONTEXT = ("smg.mla.index.score", "smg.mla.index.select")
#: attention over the selection: the gather of the selected entries and what
#: attends over the gathered block
SPARSE_ATTENTION = ("smg.attn.decode", "smg.mla.sparse")


def is_cell(ctx) -> bool:
    return ctx["hf"].get("model_type") == MODEL_TYPE


def scope_seconds(ctx, family: str, scopes) -> "tuple[float, float] | None":
    """Device seconds of the ``family``'s launches in the trace under
    ``scopes``, and of the launches whole; None without a trace, the program's
    scope map or a second under such a scope."""
    assert family in FAMILY
    sp = split_of(ctx, family)
    if sp is None:
        return None
    seconds = sum(s for sc, s in sp["scopes"].items() if under(sc, scopes))
    return (seconds, sp["family_s"]) if seconds and sp["family_s"] else None


def live_tokens(ctx, window, cap: "int | None" = None) -> float:
    """Time-average over ``window`` of the context tokens the decoding
    requests hold, each caller's own context cut at ``cap`` (the tokens a
    query attends behind a selection of ``cap``): a request decodes from its
    first token to its end and holds ``prompt + output so far`` meanwhile."""
    lo, hi = window
    total = 0.0
    for r in ctx["requests"]:
        if r["first"] is None or r["done"] is None:
            continue
        a, b = max(r["first"], lo), min(r["done"], hi)
        if b <= a:
            continue
        span = max(r["done"] - r["first"], 1e-9)
        mid = ((a + b) / 2 - r["first"]) / span  # progress through the output
        held = r["prompt_tokens"] + mid * r["output_tokens"]
        total += (b - a) * (held if cap is None else min(held, cap))
    return total / (hi - lo)


def time_share(ctx, family: str, scopes):
    """Percent of the ``family``'s device seconds under ``scopes``, for this
    cell; None elsewhere, without a trace or without the scope map."""
    if ctx.get("trace") is None or not is_cell(ctx):
        return None
    seconds = scope_seconds(ctx, family, scopes)
    return 100.0 * seconds[0] / seconds[1] if seconds else None


def decode_roofline_share(ctx, scopes, units_a_column: float, bytes_each: float,
                          flops_each: float):
    """Least time for ``units_a_column`` reads a decode column (each the
    larger of ``bytes_each`` over the chip's bandwidth and ``flops_each`` over
    its peak) over the columns run in the traced window, over the device time
    under ``scopes`` inside decode launches, in percent."""
    columns, seconds = columns_run(ctx), scope_seconds(ctx, "decode", scopes)
    if not columns or seconds is None:
        return None
    p = peak(ctx)
    least = columns * units_a_column * max(bytes_each / p["bytes_per_s"],
                                           flops_each / p["flops_per_s"])
    return 100.0 * least / (ctx["chips"] * seconds[0])

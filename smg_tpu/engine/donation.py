"""KV-buffer donation policy: which (backend, schedule mode) pairs donate
the cache buffers into the jitted step functions.

Donating the KV buffers lets XLA alias the in-place cache update — on TPU
this is non-negotiable (the cache is most of HBM; an undonated update would
double it).  The CPU PJRT client, however, BLOCKS the dispatching thread for
the whole execution when any input is donated (measured in PR 2: a donated
jit call returns after compute, an undonated one in ~0.1ms), which would
serialize the overlapped decode pipeline's async launches on the host
thread.  CPU memory is not the scarce resource, so the overlapped schedule
skips donation there and keeps async dispatch.

PR 2 carried this as a runner-internal heuristic
(``_kv_donation_blocks_dispatch``); the sharded tensor-parallel runner mode
made the implicit rules worth stating, so they live here as an explicit
per-backend / per-mode policy the runner resolves ONCE at construction:

==========  ==============  ==========  ======================================
backend     overlap active  donate KV   why
==========  ==============  ==========  ======================================
tpu / gpu   any             yes         async dispatch survives donation; the
                                        cache must alias in place (HBM)
cpu         no              yes         a synchronous schedule gains nothing
                                        from async dispatch; keep the in-place
                                        update rather than a full cache copy
cpu         yes             no          donated CPU dispatch is synchronous
                                        and would defeat the lookahead
==========  ==============  ==========  ======================================

Sharded meshes follow the same backend predicate — GSPMD donation aliases
each device's local shard in place, so a TP mesh changes the *unit* of
aliasing, not the dispatch blocking behavior (the PJRT client per platform
does).  "Overlap active" covers speculative decoding too: its verify frames
stay in flight across steps since the fused spec path landed.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class DonationPolicy:
    """Resolved donation verdict for one engine configuration."""

    donate_kv: bool
    platform: str  # "cpu" | "tpu" | "gpu"
    overlap_active: bool
    sharded: bool
    reason: str

    def describe(self) -> str:
        return (
            f"kv donation {'on' if self.donate_kv else 'off'} "
            f"(platform={self.platform}, "
            f"overlap={'on' if self.overlap_active else 'off'}, "
            f"{'sharded' if self.sharded else 'single-device'}): {self.reason}"
        )


def kv_donation_policy(
    platform: str, *, overlap_active: bool, sharded: bool = False
) -> DonationPolicy:
    """Resolve the KV donation policy for (backend platform, schedule mode).

    ``platform`` is the PJRT platform of the devices the cache lives on
    ("cpu", "tpu", "gpu"; anything that is not "cpu" gets the accelerator
    rule and donates).  ``overlap_active`` means the overlapped schedule
    (including its speculative variant) will keep frames in flight across
    steps.  ``sharded`` only annotates the reason: GSPMD aliases per-shard,
    the verdict rides the platform.
    """
    if platform == "cpu" and overlap_active:
        return DonationPolicy(
            donate_kv=False, platform=platform, overlap_active=True,
            sharded=sharded,
            reason="CPU PJRT blocks dispatch on donated inputs; async "
                   "lookahead launches need the undonated (copying) path",
        )
    if platform == "cpu":
        return DonationPolicy(
            donate_kv=True, platform=platform, overlap_active=False,
            sharded=sharded,
            reason="synchronous schedule: nothing to overlap, keep the "
                   "in-place cache update",
        )
    return DonationPolicy(
        donate_kv=True, platform=platform, overlap_active=overlap_active,
        sharded=sharded,
        reason=(
            "accelerator client dispatches donated calls asynchronously; "
            + ("each device aliases its local cache shard in place"
               if sharded else "the cache aliases in place")
        ),
    )

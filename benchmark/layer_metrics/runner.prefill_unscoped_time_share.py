"""Device time inside the prefill launches that no reader can put a name to, over
the device time of those launches, in percent: as
``runner.decode_unscoped_time_share`` (the launches' own parts stand under
``smg.prefill.*`` and are not in it; what came to a scope by the map's ``"~"``
keys is not in it either).  A prefill program that holds no Pallas kernel is
loaded from the compile cache whatever its metadata says: on a cache that a
commit with other scopes filled, the map marks such a program stale and all
its time is in this share (PERF.md, Layers).
Summed by ``_scope_time`` from the trace's leaf operations and the program's
scope map; None without the map (the parent of PR 53) or the launches."""

from _scope_time import share

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations inside jit_step* by the scope the program's "
                  "scope map gives them (no scope, or a launch no map resolves), over jit_step* device time"}


def read(ctx):
    return share(ctx, "prefill", "unscoped")

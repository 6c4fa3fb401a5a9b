"""Device time inside the decode launches that no reader can put a name to, over
the device time of those launches, in percent: the leaf operations that the
program's scope map lists under no scope, those it does not list, and every
leaf operation of a launch that no program's map covers or whose program runs
an executable of another commit (the map marks it stale and names nothing).
What the other ``runner.decode_*_time_share`` cannot see.  **Not in it**: an
operation the compiler left without metadata whose readers all stand under one
scope counts under that scope (the map's ``"~" + scope`` keys: a relayout
before a product, a cache-shaped scatter); ``scripts/scope_split.py`` prints
that part of every scope, and the strict reading is this share plus it.
Summed by ``_scope_time`` from the trace's leaf operations and the program's
scope map; None without the map (the parent of PR 53) or the launches."""

from _scope_time import share

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations inside jit_multi* by the scope the program's "
                  "scope map gives them (no scope, or a launch no map resolves), over jit_multi* device time"}


def read(ctx):
    return share(ctx, "decode", "unscoped")

"""Device time of the indexers inside the prefill launches over the device time
of those launches, in percent, for ``glm-5.2.longdoc``: what choosing the
tokens costs a chunk.  Summed are the leaf operations the program's scope map
puts under ``smg.mla.index.*``: the index queries and head weights (``.q``),
the chunk's index keys, their write and the gather of the context's keys from
the pages (``.k``), the scores of every (query, cached token) pair by head
(``.score``) and the 32 compare-and-count passes that find each row's 2,048th
largest (``.select``), in the two layers that have an indexer.  A part of
``runner.prefill_mixer_time_share`` (the scopes are under ``smg.mla``).
Another architecture, no trace or a program without the scope map gives None."""

from _dsa import INDEX, time_share

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations inside jit_step* under the scopes "
                  "smg.mla.index.* (the program's scope map), over jit_step* device time"}


def read(ctx):
    return time_share(ctx, "prefill", INDEX)

"""Device time of the indexers inside the decode launches over the device time
of those launches, in percent, for ``glm-5.2.longdoc``: what choosing the
tokens costs a column, beside ``kernels.dsa_attn_decode_roofline_share`` for
what reading them costs.  Summed are the leaf operations the program's scope
map puts under ``smg.mla.index.*``: the index queries and head weights
(``.q``), the column's index key, its write and the gather of the lanes' keys
from the pages (``.k``), the scores (``.score``) and the selection
(``.select``), in the two layers that have an indexer.  A part of
``runner.decode_mixer_time_share`` (the scopes are under ``smg.mla``).  Another
architecture, no trace or a program without the scope map gives None."""

from _dsa import INDEX, time_share

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations inside jit_multi* under the scopes "
                  "smg.mla.index.* (the program's scope map), over jit_multi* device time"}


def read(ctx):
    return time_share(ctx, "decode", INDEX)

"""``layer_metrics/_scope_time.py`` and the nine ``runner.*_time_share``
readers over it (PR 53), on a recorded trace made of plain lists and a
hand-written scope map: a launch is resolved to the program whose heads cover
its leaves before seconds are summed; a launch no map covers is unscoped
whole; the parts add up to the leaf seconds inside the launches.  CPU, under a
second:
python3 -m pytest benchmark/tests -q
"""

import json
import os

import pytest

import catalog

SHARES = {
    "runner.decode_mixer_time_share": ("decode", "mixer"),
    "runner.decode_ffn_time_share": ("decode", "ffn"),
    "runner.decode_routing_time_share": ("decode", "routing"),
    "runner.decode_head_time_share": ("decode", "head"),
    "runner.decode_frame_time_share": ("decode", "frame"),
    "runner.decode_unscoped_time_share": ("decode", "unscoped"),
    "runner.prefill_mixer_time_share": ("prefill", "mixer"),
    "runner.prefill_ffn_time_share": ("prefill", "ffn"),
    "runner.prefill_unscoped_time_share": ("prefill", "unscoped"),
}

BF = "bf16[64,128]{1,0:T(8,128)(2,1)}"
F32 = "f32[64]{0:T(128)}"


def ev(name, shape, op, start, dur):
    """A device event as ``trace_reduce.load_xplane`` keeps one."""
    return [f"%{name} = {shape} {op}({shape} %p.1), kind=kLoop", start, dur]


#: two decode programs that reuse ``fusion.7`` (under different scopes and
#: shapes) and ``fusion.8`` (same head, different scopes), and one prefill
SCOPES = {
    "('decode_multi', 8)": {"family": "multi", "scopes": {
        "smg.attn.qkv": [f"fusion.7 = {BF}"], "smg.mlp": [f"fusion.8 = {BF}"],
        "smg.moe.route": [f"fusion.9 = {F32}"], "smg.sample": [f"fusion.10 = {F32}"],
        "smg.frame.emit": [f"fusion.11 = {F32}"], "smg.mtp": [f"fusion.12 = {F32}"],
        "~smg.mlp": [f"copy.3 = {BF}"], "": [f"copy.4 = {F32}", f"while.1 = ({F32})"]}},
    "('decode_multi', 64)": {"family": "multi", "scopes": {
        "smg.mlp": [f"fusion.7 = {F32}"], "smg.attn.out": [f"fusion.8 = {BF}"],
        "smg.kda.decode": [f"smg.kda.decode.5 = {BF}"], "smg.lm_head": [f"fusion.20 = {BF}"],
        "smg.frame.land": [f"fusion.21 = {BF}"], "": []}},
    "('prefill_batched', 2)": {"family": "step", "scopes": {
        "smg.attn.prefill": [f"fusion.7 = {BF}"], "smg.moe.experts": [f"fusion.8 = {BF}"],
        "smg.prefill.unpack": [f"fusion.30 = {F32}"], "": [f"copy.9 = {BF}"]}},
}


def trace():
    ops = [
        # jit_multi(111): the first decode program, 0.0 .. 1.0
        ev("while.1", f"({F32})", "while", 0.0, 1.0),  # encloses: not a leaf
        ev("fusion.7", BF, "fusion", 0.00, 0.10), ev("fusion.8", BF, "fusion", 0.10, 0.20),
        ev("fusion.9", F32, "fusion", 0.30, 0.05), ev("fusion.10", F32, "fusion", 0.35, 0.05),
        ev("fusion.11", F32, "fusion", 0.40, 0.10), ev("fusion.12", F32, "fusion", 0.50, 0.10),
        ev("copy.3", BF, "copy", 0.60, 0.10), ev("copy.4", F32, "copy", 0.70, 0.05),
        ev("fusion.99", F32, "fusion", 0.75, 0.05),  # in no map: unscoped
        # jit_multi(222): the second, 2.0 .. 3.0; it reuses fusion.7 and fusion.8
        ev("fusion.7", F32, "fusion", 2.00, 0.30), ev("fusion.8", BF, "fusion", 2.30, 0.10),
        ev("smg.kda.decode.5", BF, "custom-call", 2.40, 0.20),
        ev("fusion.20", BF, "fusion", 2.60, 0.10), ev("fusion.21", BF, "fusion", 2.70, 0.10),
        # jit_multi(333): a program the profile's map does not hold, 4.0 .. 4.5
        ev("fusion.41", BF, "fusion", 4.00, 0.20), ev("fusion.8", F32, "fusion", 4.20, 0.20),
        # jit_step(444): 5.0 .. 6.0
        ev("fusion.7", BF, "fusion", 5.00, 0.50), ev("fusion.8", BF, "fusion", 5.50, 0.30),
        ev("fusion.30", F32, "fusion", 5.80, 0.10), ev("copy.9", BF, "copy", 5.90, 0.05),
        # outside every launch
        ev("fusion.7", BF, "fusion", 7.00, 0.50),
    ]
    modules = [["jit_multi(111)", 0.0, 1.0], ["jit_multi(222)", 2.0, 1.0],
               ["jit_multi(333)", 4.0, 0.5], ["jit_step(444)", 5.0, 1.0],
               ["jit_merge(5)", 7.0, 0.5]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": []}


def ctx(scopes=SCOPES, tr=None):
    return {"trace": tr or trace(), "trace_window": (0.0, 8.0),
            "loads_after": {"programs": {"scopes": scopes} if scopes is not None else {}}}


def scope_time():
    catalog.layer_metric_reader("runner.decode_mixer_time_share")  # puts the readers on the path
    import _scope_time

    return _scope_time


def test_two_programs_that_reuse_a_name_under_different_scopes_resolve_by_launch():
    st = scope_time()
    sp = st.split(trace(), SCOPES, "decode")
    s = sp["scopes"]
    # fusion.7 is smg.attn.qkv in the first program and smg.mlp in the second;
    # fusion.8 smg.mlp in the first and smg.attn.out in the second
    assert s["smg.attn.qkv"] == pytest.approx(0.10) and s["smg.attn.out"] == pytest.approx(0.10)
    assert s["smg.mlp"] == pytest.approx(0.20 + 0.10 + 0.30)  # with the copy only it reads
    assert sp["adopted"] == {"smg.mlp": pytest.approx(0.10)}
    assert s["smg.kda.decode"] == pytest.approx(0.20)  # a kernel named by its scope
    assert sp["family_s"] == pytest.approx(2.5)


def test_a_launch_no_map_covers_goes_to_unscoped_whole():
    st = scope_time()
    sp = st.split(trace(), SCOPES, "decode")
    # jit_multi(333): fusion.8 is a name the maps know, under another shape
    assert sp["unresolved_s"] == pytest.approx(0.40)
    assert sp["scopes"][""] == pytest.approx(0.40 + 0.05 + 0.05)  # and copy.4, fusion.99
    heads = sp["heads"]
    assert heads[f"fusion.41 = {BF}"] == pytest.approx(0.20)
    assert heads[f"fusion.99 = {F32}"] == pytest.approx(0.05)
    assert not any(h.startswith("while.1") for h in heads)  # what encloses is no leaf
    # and the heads of any other scope, where one is asked for
    assert st.split(trace(), SCOPES, "decode", "smg.mlp")["heads"] == {
        f"fusion.8 = {BF}": pytest.approx(0.20), f"copy.3 = {BF}": pytest.approx(0.10),
        f"fusion.7 = {F32}": pytest.approx(0.30)}


@pytest.mark.parametrize("family", ["decode", "prefill"])
def test_the_parts_add_up_to_the_leaf_seconds_inside_the_launches(family):
    st = scope_time()
    sp = st.split(trace(), SCOPES, family)
    parts = {p: st.seconds_in(sp, p) for p in ("mixer", "ffn", "head", "frame", "unscoped",
                                               "other")}
    assert sum(parts.values()) == pytest.approx(sp["leaf_s"]) == pytest.approx(
        sum(sp["scopes"].values()))
    assert sp["leaf_s"] <= sp["family_s"]
    assert st.seconds_in(sp, "routing") <= parts["ffn"]
    if family == "decode":
        assert parts == {"mixer": pytest.approx(0.40), "ffn": pytest.approx(0.65),
                         "head": pytest.approx(0.15), "frame": pytest.approx(0.20),
                         "unscoped": pytest.approx(0.50), "other": pytest.approx(0.10)}
        assert sp["leaf_s"] == pytest.approx(2.0)
    else:  # smg.prefill.* is under a scope, and in none of the three shares
        assert parts["other"] == pytest.approx(0.10) and parts["unscoped"] == pytest.approx(0.05)


@pytest.mark.parametrize("name", sorted(SHARES))
def test_a_reader_gives_its_parts_share_of_the_familys_device_seconds(name):
    family, part = SHARES[name]
    st = scope_time()
    sp = st.split(trace(), SCOPES, family)
    want = 100.0 * st.seconds_in(sp, part) / sp["family_s"]
    got = catalog.layer_metric_reader(name).read(ctx())
    assert got == pytest.approx(want) and 0.0 < got < 100.0


@pytest.mark.parametrize("name", sorted(SHARES))
def test_a_reader_gives_nothing_without_the_map_the_trace_or_the_launches(name):
    read = catalog.layer_metric_reader(name).read
    assert read(ctx(scopes=None)) is None  # the parent of PR 53: no ``scopes``
    assert read({**ctx(), "trace": None}) is None  # a --trace 0 context
    assert read({"trace": trace(), "loads_after": {}}) is None
    other = {k: v for k, v in SCOPES.items() if v["family"] != {
        "decode": "multi", "prefill": "step"}[SHARES[name][0]]}
    assert read(ctx(scopes=other)) is None  # no program of the family in the map
    quiet = trace()
    quiet["devices"]["/device:TPU:0"]["modules"] = [["jit_merge(5)", 7.0, 0.5]]
    assert read(ctx(tr=quiet)) is None  # the family did not run


def test_a_launch_that_goes_on_behind_the_traces_end_is_left_out():
    """``trace_cut.json`` keeps what starts in a quarter second: the launch
    that the cut ends in has lost its later operations, and would read as a
    gap inside a launch."""
    st = scope_time()
    whole = st.split(trace(), SCOPES, "decode")
    cut = trace()
    dev = cut["devices"]["/device:TPU:0"]
    dev["modules"].append(["jit_multi(111)", 9.0, 2.0])
    dev["ops"].append(ev("fusion.7", BF, "fusion", 9.1, 0.1))  # the last thing the cut kept
    assert st.split(cut, SCOPES, "decode") == whole


def test_routing_is_nothing_for_a_model_without_routed_experts():
    dense = {k: {**v, "scopes": {s: h for s, h in v["scopes"].items()
                                 if not s.startswith("smg.moe")}} for k, v in SCOPES.items()}
    assert catalog.layer_metric_reader("runner.decode_routing_time_share").read(
        ctx(scopes=dense)) is None
    assert catalog.layer_metric_reader("runner.decode_unscoped_time_share").read(
        ctx(scopes=dense)) > 0  # what it was under is unscoped now


def test_a_head_is_an_events_name_and_result_shape():
    st = scope_time()
    assert st.head(f"%fusion.7 = {BF} fusion({BF} %p), kind=kLoop") == f"fusion.7 = {BF}"
    tup = f"({F32}, /*index=1*/{BF})"
    assert st.head(f"%while.3 = {tup} while({tup} %t), body=%b") == f"while.3 = {tup}"
    assert st.head(f"ROOT %copy.1 = {F32} copy({F32} %x)") == f"copy.1 = {F32}"
    assert len(st.head("%while.9 = (" + ", ".join([BF] * 40) + ") while(%t)")) == st.HEAD_CHARS
    assert st.head("SlinkyThreadPool::Await") == "SlinkyThreadPool::Await"  # the CPU's lines


@pytest.mark.parametrize("name", sorted(SHARES))
def test_each_share_has_its_entry_and_its_file(name):
    """Looked up by name: where the entries stand in the list is nobody's
    business here (PERF.md §7.18, §7.30a)."""
    bench = catalog.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["unit"] == "%" and entry["source"] == "device_trace"
    assert entry["layer"] == "runner" and entry["moves"] == "output_tok_per_s"
    assert entry["better"] == "lower"
    routed = name == "runner.decode_routing_time_share"
    assert ("workloads" in entry) == routed  # every cell, but the two without routed experts
    reader = catalog.layer_metric_reader(name)
    assert reader is not None and reader.META["unit"] == "%" and reader.__doc__
    assert os.path.getsize(os.path.join(catalog.HERE, "layer_metrics", name + ".py")) < 2500


def test_the_split_is_plain_numbers():
    st = scope_time()
    json.dumps(st.split(trace(), SCOPES, "decode"))


def test_a_program_of_a_stale_executable_reads_as_unscoped_and_says_so():
    """What the program publishes for an executable another commit compiled:
    every head under no scope and ``"stale": true``.  Its launches resolve to
    it, their seconds are unscoped, and ``stale_s`` tells them from the rest."""
    st = scope_time()
    key = "('decode_multi', 8)"
    stale = dict(SCOPES)
    stale[key] = {"family": "multi", "stale": True,
                  "scopes": {"": [h for hs in SCOPES[key]["scopes"].values() for h in hs]}}
    own, sp = st.split(trace(), SCOPES, "decode"), st.split(trace(), stale, "decode")
    assert own["stale_s"] == 0.0 and sp["unresolved_s"] == own["unresolved_s"]
    assert sp["stale_s"] > 0 and sp["leaf_s"] == pytest.approx(own["leaf_s"])
    assert sp["scopes"][""] > own["scopes"][""] and sp["scopes"][""] >= sp["stale_s"]
    assert sum(sp["scopes"].values()) == pytest.approx(sp["leaf_s"])  # nothing is lost
    assert "smg.attn.qkv" not in sp["scopes"]  # the first program's alone
    assert sp["scopes"]["smg.kda.decode"] == pytest.approx(own["scopes"]["smg.kda.decode"])

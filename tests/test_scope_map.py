"""The scope map (ISSUE 53): each compiled program says which ``smg.*`` named
scope every one of its instructions belongs to, by the head a trace prints for
it (``analysis/runtime_guards.ProgramAuditor.scope_map``), so that a trace's
readers can put a name to ``fusion.516``.  The map is made when a profile
ends, off the step thread and off the engine lock, appears in
``loads()["programs"]["scopes"]``, and costs no program a recompile.  One
parametrised test a property, a case a runner.  CPU, seconds each."""

import json
import threading
import time

import pytest

from smg_tpu.analysis import runtime_guards as rg
from smg_tpu.models.config import (
    tiny_exaone_moe_config,
    tiny_mimo_config,
    tiny_olmo_hybrid_config,
    tiny_pangu_moe_config,
    tiny_test_config,
)
from tests.test_engine_tracing import drive, fake_profiler, greedy, make_engine  # noqa: F401

#: the five runners: what builds each, and the scopes its programs must hold
#: beside the frame's (every ``multi``) and the prefill's own (every ``step``)
RUNNERS = {
    "llama": (tiny_test_config, {}, "ModelRunner",
              {"smg.attn.qkv", "smg.attn.kv", "smg.attn.out", "smg.mlp"}),
    "recurrent": (tiny_olmo_hybrid_config, {}, "RecurrentModelRunner",
                  {"smg.linattn.layer", "smg.linattn.conv", "smg.attn.layer", "smg.mlp"}),
    "latent": (lambda: tiny_pangu_moe_config(held=(4, 8)), {}, "LatentModelRunner",
               {"smg.mla.block", "smg.mla.q", "smg.mla.kv", "smg.moe.residual",
                "smg.moe.route", "smg.moe.dispatch", "smg.moe.experts"}),
    "window": (lambda: tiny_mimo_config(held=(4, 8)), {}, "WindowModelRunner",
               {"smg.attn.qkv", "smg.attn.kv", "smg.attn.out", "smg.moe.residual",
                "smg.moe.route"}),
    "drafting": (lambda: tiny_exaone_moe_config(held=(4, 8)), {"speculative": True},
                 "SelfDraftingRunner",
                 {"smg.attn.qkv", "smg.attn.kv", "smg.attn.out", "smg.moe.residual",
                  "smg.mtp.project"}),
}
FRAME = {"smg.frame.begin", "smg.frame.emit", "smg.frame.land"}
EVERYWHERE = {"smg.embed", "smg.lm_head", "smg.sample"}
#: of a program's instructions, the most that may stand under no scope.  On
#: the CPU those are the loops' counters and the copies of a sampling key
#: from one iteration to the next, which the compiler writes without metadata
UNSCOPED_MOST = 0.25


@pytest.fixture(scope="module", autouse=True)
def metadata_in_the_cache_key():
    """JAX leaves metadata out of the compile cache's key, and the suite keeps
    its cache between runs (``conftest.py``): a program whose scopes alone
    changed would come back from the cache with the scopes it had when the
    entry was written (the stale-executable trap of PERF.md, Layers), and the
    map would mark it stale and name nothing.  What this file compiles is
    keyed with its metadata, so its scopes are its own."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    yield
    jax.config.update(flag, before)


@pytest.fixture(scope="module")
def profiled():
    """Each runner driven once under a (stand-in) profile: its engine and
    ``loads()["programs"]`` before the profile, during it and after its end."""
    import jax

    kept: dict = {}
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    jax.profiler.start_trace, jax.profiler.stop_trace = (lambda *a, **kw: None), (lambda: None)
    try:
        for name, (model, kw, _runner, _scopes) in RUNNERS.items():
            eng = make_engine(model=model(), **kw)
            drive(eng, [[5, 6, 7, 8]], n=4)  # warm-up: every program's first launch
            before = eng.loads()["programs"]
            eng.start_profile("")
            drive(eng, [[5, 6, 7, 8 + i] for i in range(3)], n=8)
            during = eng.loads()["programs"]
            eng.stop_profile()
            kept[name] = (eng, before, during, eng.loads()["programs"])
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
    return kept


def maps_of(programs: dict, family: str) -> list:
    return [p["scopes"] for p in programs["scopes"].values() if p["family"] == family]


@pytest.mark.parametrize("runner", RUNNERS)
def test_a_decode_and_a_grouped_prefill_program_hold_the_scopes_of_their_family(profiled, runner):
    eng, _before, _during, after = profiled[runner]
    assert type(eng.runner).__name__ == RUNNERS[runner][2]
    own = RUNNERS[runner][3]
    decode, prefill = maps_of(after, "multi"), maps_of(after, "step")
    assert decode and prefill
    assert any(k.startswith("('prefill_batched'") for k in after["scopes"])
    for scopes in decode:
        assert FRAME | EVERYWHERE | own <= set(scopes), sorted(scopes)
    for scopes in prefill:
        assert {"smg.prefill.unpack"} | EVERYWHERE | own - {"smg.mtp.project"} <= set(scopes)
        assert not FRAME & set(scopes)


@pytest.mark.parametrize("runner", RUNNERS)
def test_the_unscoped_heads_are_under_a_fixed_share_of_a_programs_instructions(profiled, runner):
    *_, after = profiled[runner]
    for key, p in after["scopes"].items():
        heads = [h for hs in p["scopes"].values() for h in hs]
        assert len(heads) == len(set(heads)) > 50, key  # a head names one instruction
        assert len(p["scopes"][""]) <= UNSCOPED_MOST * len(heads), (key, p["scopes"][""][:20])
        # what was adopted from a reader says so, and names a scope that is there
        adopted = [s for s in p["scopes"] if s.startswith("~")]
        assert all(s[1:].startswith("smg.") for s in adopted), adopted


@pytest.mark.parametrize("runner", RUNNERS)
def test_loads_has_no_scopes_before_a_profile_and_the_launched_programs_after(profiled, runner):
    eng, before, during, after = profiled[runner]
    assert "scopes" not in before and "scopes" not in during
    assert before["scope_lowerings"] == during["scope_lowerings"] == 0
    was = {p["key"]: p["launches"] for p in before["programs"]}
    launched = {p["key"] for p in after["programs"] if p["launches"] > was.get(p["key"], 0)}
    assert set(after["scopes"]) == launched and launched
    assert {p["family"] for p in after["scopes"].values()} == {"multi", "step"}
    json.dumps(after)  # what ``/scheduler`` and a run's ``run.json`` write
    # handed out by reference, and nobody changes it
    again = eng.loads()["programs"]["scopes"]
    assert again is after["scopes"]
    some = next(iter(again.values()))
    with pytest.raises(TypeError):
        again["x"] = some
    with pytest.raises(TypeError):
        some["scopes"][""] = ()
    assert isinstance(some["scopes"][""], tuple)


@pytest.mark.parametrize("runner", RUNNERS)
def test_the_maps_lowerings_are_counted_apart_and_recompile_nothing(profiled, runner):
    eng, _before, during, after = profiled[runner]
    assert after["scope_lowerings"] == len(after["scopes"]) > 0
    assert after["recompiles"] == 0 and all(p["recompiles"] == 0 for p in after["programs"])
    assert after["compiles"] == during["compiles"]  # nothing compiled for a map counts
    # a second profile over the same programs lowers nothing again
    auditor = eng.runner._programs
    key = next(k for k in auditor.launch_counts() if repr(k) in after["scopes"])
    assert auditor.scope_map(key) is after["scopes"][repr(key)]["scopes"]
    assert auditor.scope_lowerings == after["scope_lowerings"]


def test_a_compile_on_a_marked_thread_is_no_programs_recompile():
    """The listener's rule itself: what the map's own thread compiles is not
    counted, what any other thread compiles is."""
    import jax

    rg._ensure_listener()
    n0 = rg.compile_count()
    rg._thread.scope_map = True
    try:
        jax.jit(lambda x: x * 3 + 1)(jax.numpy.zeros(7))
    finally:
        rg._thread.scope_map = False
    assert rg.compile_count() == n0
    jax.jit(lambda x: x * 5 + 1)(jax.numpy.zeros(7))
    assert rg.compile_count() == n0 + 1


@pytest.mark.parametrize("runner", ["llama", "latent"])
def test_steps_and_submits_go_on_while_the_maps_are_made(fake_profiler, runner, monkeypatch):
    """As ``test_submit_and_steps_go_on_while_stop_profile_writes`` holds for
    the write: the maps are made behind it on the same thread, under no lock
    a step or a submit takes."""
    model, kw, *_ = RUNNERS[runner]
    eng = make_engine(model=model(), **kw)
    eng.generate(prompt_ids=[5, 6, 7], sampling=greedy(4))
    making, release = threading.Event(), threading.Event()
    publish = eng.runner._programs.publish_scopes

    def slow_publish(keys):
        making.set()
        assert release.wait(60)
        return publish(keys)

    monkeypatch.setattr(eng.runner._programs, "publish_scopes", slow_publish)
    eng.start()
    try:
        eng.start_profile("")
        eng.generate(prompt_ids=[5, 6, 8], sampling=greedy(4))
        stopper = threading.Thread(target=eng.stop_profile, name="stopper")
        stopper.start()
        assert making.wait(10)  # the trace is written; the maps are being made
        serial0 = eng.scheduler.flight.step_serial
        t = time.monotonic()
        done = threading.Event()
        eng.submit([9, 8, 7], greedy(8), on_output=lambda o: o.finished and done.set())
        assert time.monotonic() - t < 1.0  # the submit did not wait for the maps
        assert done.wait(60) and eng.scheduler.flight.step_serial > serial0
        assert "scopes" not in eng.loads()["programs"]  # loads() is not held up either
        assert stopper.is_alive()
        release.set()
        stopper.join(30)
        assert not stopper.is_alive() and not eng._profiling
        assert set(p["family"] for p in eng.loads()["programs"]["scopes"].values()) == {
            "multi", "step"}
    finally:
        release.set()
        eng.stop()


def test_the_map_is_written_beside_the_traces_directory(fake_profiler, tmp_path):
    eng = make_engine()
    eng.start_profile(str(tmp_path / "trace"))
    eng.generate(prompt_ids=[5, 6, 7], sampling=greedy(4))
    eng.stop_profile()
    with open(tmp_path / "trace.scopes.json") as f:
        beside = json.load(f)
    assert beside == json.loads(json.dumps(eng.loads()["programs"]["scopes"]))
    eng.start_profile(str(tmp_path / "idle"))  # nothing launched: nothing written
    eng.stop_profile()
    assert not (tmp_path / "idle.scopes.json").exists()


# ---- an executable of another commit ----


def test_an_executable_another_commit_compiled_names_nothing(tmp_path):
    """The stale-executable trap: the compile cache's key has no metadata, so
    a program whose scopes alone changed is loaded from the entry an earlier
    commit wrote, with that commit's scopes.  The map sees it (the lowering's
    scope names are not the executable's), counts it, marks it, and lists
    every head under no scope; an empty cache gives the program its own."""
    import jax
    import jax.numpy as jnp

    def program(scope):
        def step(x):
            with jax.named_scope(scope):
                return jnp.tanh(x @ x.T + 1.5) * 0.731  # no program of the suite's
        return jax.jit(step)

    def mapped(scope):
        auditor = rg.ProgramAuditor()
        launch = auditor.wrap(("step", scope), program(scope))
        launch(jnp.ones((8, 8)))
        return auditor, auditor.publish_scopes([("step", scope)])[repr(("step", scope))]

    flag, cache = "jax_compilation_cache_include_metadata_in_key", "jax_compilation_cache_dir"
    before = {f: getattr(jax.config, f) for f in (flag, cache)}
    jax.config.update(flag, False)
    jax.config.update(cache, str(tmp_path))
    try:
        auditor, own = mapped("smg.before")  # an empty cache: the entry is written here
        assert "stale" not in own and own["scopes"]["smg.before"] and auditor.scope_stale == 0
        jax.clear_caches()
        auditor, stale = mapped("smg.after")  # the same program but for its metadata
        assert stale["stale"] is True and set(stale["scopes"]) == {""}
        assert sorted(stale["scopes"][""]) == sorted(h for hs in own["scopes"].values() for h in hs)
        snap = auditor.snapshot()
        assert snap["scope_stale"] == snap["scope_lowerings"] == 1
        assert snap["scopes"][repr(("step", "smg.after"))] is stale
        jax.clear_caches()
        jax.config.update(cache, str(tmp_path / "empty"))
        auditor, fresh = mapped("smg.after")
        assert "stale" not in fresh and fresh["scopes"]["smg.after"] and auditor.scope_stale == 0
    finally:
        for f, v in before.items():
            jax.config.update(f, v)


# ---- the text of a compiled module ----

HLO = """HloModule jit_multi, is_scheduled=true

%fused_computation.1 (p.1: f32[8,128]) -> f32[8,128] {
  %p.1 = f32[8,128]{1,0} parameter(0)
  ROOT %inside.1 = f32[8,128]{1,0} negate(%p.1), metadata={op_name="jit(multi)/smg.mlp/neg"}
}

%add.clone (x.1: f32[], y.1: f32[]) -> f32[] {
  %x.1 = f32[] parameter(0)
  %y.1 = f32[] parameter(1)
  ROOT %sum.1 = f32[] add(%x.1, %y.1)
}

%body.2 (c.1: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %c.1 = (s32[], f32[8,128]{1,0:T(8,128)}) parameter(0)
  %gte.0 = s32[]{:T(128)} get-tuple-element(%c.1), index=0
  %gte.1 = f32[8,128]{1,0:T(8,128)} get-tuple-element(%c.1), index=1
  %copy.3 = f32[8,128]{0,1:T(8,128)} copy(f32[8,128]{1,0:T(8,128)} %gte.1)
  %fusion.7 = f32[8,128]{1,0:T(8,128)} fusion(f32[8,128]{0,1:T(8,128)} %copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(multi)/while/body/smg.mtp/smg.mlp/dot_general" source_file="x.py"}
  %smg.attn.decode.4 = f32[8,128]{1,0:T(8,128)} custom-call(%fusion.7), custom_call_target="tpu_custom_call"
  %reduce.9 = f32[8]{0:T(128)} reduce(%smg.attn.decode.4, %k.1), dimensions={1}, to_apply=%add.clone, metadata={op_name="jit(multi)/while/body/vmap(smg.sample)/reduce_sum"}
  %copy.5 = s32[]{:T(128)} copy(%gte.0)
  ROOT %tuple.1 = (s32[], f32[8,128]{1,0:T(8,128)}) tuple(%copy.5, %smg.attn.decode.4)
}

ENTRY %main.3 (a.1: f32[8,128]) -> f32[8,128] {
  %a.1 = f32[8,128]{1,0} parameter(0)
  %zero.1 = s32[]{:T(128)} constant(0)
  %copy.8 = f32[8,128]{1,0:T(8,128)} copy(f32[8,128]{1,0} %a.1)
  %t.0 = (s32[]{:T(128)}, f32[8,128]{1,0:T(8,128)}) tuple(%zero.1, %copy.8)
  %while.1 = (s32[], /*index=1*/f32[8,128]{1,0:T(8,128)}) while(%t.0), condition=%cond.2, body=%body.2, metadata={op_name="jit(multi)/while"}
  %gte.9 = f32[8,128]{1,0:T(8,128)} get-tuple-element(%while.1), index=1
  %out.1 = f32[8,128]{1,0} copy(%gte.9), metadata={op_name="jit(multi)/smg.frame.land/copy"}
  %copy.9 = f32[8,128]{0,1} copy(f32[8,128]{1,0} %out.1)
  ROOT %res.1 = (f32[8,128]{0,1}) tuple(%copy.9)
}
"""


def test_the_text_of_a_module_gives_each_instruction_its_innermost_scope():
    scopes = rg.scopes_of_hlo(HLO)
    assert scopes == {
        "smg.mlp": ("fusion.7 = f32[8,128]{1,0:T(8,128)}",),  # the innermost of two
        "~smg.mlp": ("copy.3 = f32[8,128]{0,1:T(8,128)}",),  # a relayout only it reads
        "smg.attn.decode": ("smg.attn.decode.4 = f32[8,128]{1,0:T(8,128)}",),  # by its name
        "smg.sample": ("reduce.9 = f32[8]{0:T(128)}",),  # through a transform's brackets
        "smg.frame.land": ("out.1 = f32[8,128]{1,0}",),
        # nothing is guessed beyond a reader's scope: a counter's copy that goes
        # back into the loop, what a loop under no scope is handed (the loop takes
        # no scope from what reads its results, and hands none on), the loop, and
        # what nothing reads but the program's result stay under none
        "": ("copy.5 = s32[]{:T(128)}", "copy.8 = f32[8,128]{1,0:T(8,128)}",
             "while.1 = (s32[], /*index=1*/f32[8,128]{1,0:T(8,128)})",
             "copy.9 = f32[8,128]{0,1}"),
    }
    heads = [h for hs in scopes.values() for h in hs]
    assert not any(h.startswith(("inside", "sum", "p.1", "gte", "tuple", "a.1", "zero", "t.0", "res"))
                   for h in heads)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(multi)/while/body/smg.mlp/dot_general", "smg.mlp"),
    ("jit(multi)/smg.mtp/smg.attn.qkv/mul", "smg.attn.qkv"),
    ("jit(step)/jit(smg.moe.route)/top_k", "smg.moe.route"),
    ("jit(multi)/while/body/closed_call/mul", ""),
    ("", ""),
])
def test_an_op_name_gives_its_innermost_scope(op_name, scope):
    assert rg.scope_of(op_name) == scope


def test_a_head_is_cut_where_a_loop_names_every_buffer_it_carries():
    shape = "(" + ", ".join(["bf16[36864]{0:T(1024)(128)(2,1)S(1)}"] * 40) + ")"
    head, name, rest = rg.instruction_head(f"  %while.344 = {shape} while(%tuple.9), body=%b")
    assert name == "while.344" and rest.startswith("while(")
    assert len(head) == rg.HEAD_CHARS and head.startswith("while.344 = (bf16[36864]")
    assert rg.instruction_head("ENTRY %main (a: f32[]) -> f32[] {") is None


# ---- the two parsers of a head: the program's and the trace reader's ----

LINES = [
    "  %fusion.516 = bf16[64,7680]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[64,7680]{1,0:T(8,128)(2,1)} %p.1), kind=kLoop, calls=%f",
    "  ROOT %copy.1 = f32[64]{0:T(128)} copy(f32[64]{0} %x)",
    "  %copy-start.85 = (s32[4]{0:T(128)}, s32[4]{0:T(128)S(1)}, u32[]{:S(2)}) copy-start(%get-tuple-element.16652)",
    "  %while.1 = (s32[], /*index=1*/f32[8,128]{1,0:T(8,128)}) while(%t.0), condition=%c, body=%b",
    "  %smg.kda.decode.5 = (bf16[64,32,128]{2,1,0:T(8,128)(2,1)}, f32[8]{0}) custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"",
    "  %while.344 = (" + ", ".join(["bf16[36864]{0:T(1024)(128)(2,1)S(1)}"] * 40) + ") while(%tuple.9), body=%b",
    "  %constant.3 = s32[]{:T(128)} constant(0)",
]


@pytest.mark.parametrize("line", LINES, ids=[ln.split(" = ")[0].strip(" %ROT") for ln in LINES])
def test_the_program_and_the_trace_reader_cut_one_head_from_one_instruction(line):
    """``runtime_guards.instruction_head`` writes the map's heads and
    ``layer_metrics/_scope_time.head`` cuts a trace event's name to look it up
    (the benchmark imports nothing of the program's): a trace prints the line
    without its indent, and with or without the ``%``."""
    import os
    import sys

    readers = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark", "layer_metrics")
    sys.path.insert(0, readers)
    try:
        import _scope_time
    finally:
        sys.path.remove(readers)
    assert _scope_time.HEAD_CHARS == rg.HEAD_CHARS
    head = rg.instruction_head(line)[0]
    assert len(head) <= rg.HEAD_CHARS and not head.startswith("%")
    event = line.strip().removeprefix("ROOT ")
    assert _scope_time.head(event) == _scope_time.head(event.lstrip("%")) == head

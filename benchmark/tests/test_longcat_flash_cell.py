"""The ``longcat_flash`` architecture, the ``longcat-flash-chat`` configuration and
the cell ``reason`` over it hold what ``test_architectures.py`` asks of one: the
harness resolves them by name and the configuration is the catalog row's but
for the cut; the costs are the cut's sizes by hand; ``reference.check_engine``
holds the drive (8 cache layers for 4 layers) to the file's own ``logits`` with
the shared control and the drive's nine all missing the tolerance, and a dead
identity term or a dead routed path is not correct; the five new readers read a
hand-made trace, ring and counters and give nothing for the five older cells;
and the additions are new files and entries of their own.  CPU."""

import json
import os
import subprocess

import catalog
import pytest
import reference
from conftest import ROOT

CELL = "longcat-flash-chat.reason"
OLDER = ("qwen3-1.7b.eval", "olmo-hybrid-7b.gen", "openpangu-ultra-moe-718b.reason",
         "mimo-v2-flash.mixed", "k-exaone-236b-a23b.reason")
NEW = ("runner.moe_zero_pick_share", "runner.scmoe_experts_time_share",
       "kernels.scmoe_mla_decode_roofline_share", "kernels.scmoe_experts_decode_roofline_share",
       "runner.scmoe_held_pick_share")
SOURCE = "https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json"
REDUCED = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
ROW_KEYS = (
    "attention_bias", "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
    "num_layers", "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
    "v_head_dim", "qk_nope_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
    "routed_scaling_factor", "n_routed_experts", "max_position_embeddings", "rms_norm_eps",
    "rope_theta", "attention_method", "zero_expert_num", "zero_expert_type", "moe_topk")


def catalog_row() -> dict:
    """The row of the model-configs guide where this machine has it, else the
    file's own ``published`` laid over the file."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(path):
        for line in open(path):
            row = json.loads(line)
            if row["name"] == "LongCat-Flash-Chat":
                assert row["source_url"] == SOURCE
                return row["config"]
    conf = catalog.Cell(catalog.load_benchmark(), CELL).config
    return {**{k: conf[k] for k in ROW_KEYS}, **conf["published"]}


def test_the_cell_resolves_and_the_configuration_is_the_rows_but_for_the_cut():
    bench = catalog.load_benchmark()
    cell = catalog.Cell(bench, CELL)
    assert cell.architecture.__name__.endswith("longcat_flash") and cell.chips == 1
    hf, conf, row = cell.hf_config, cell.config, catalog_row()
    assert not set(hf) & {"architecture", "reduced", "published", "assumed", "rehearsal"}
    assert set(row) == set(ROW_KEYS)
    for key, want in row.items():
        assert hf[key] == REDUCED.get(key, want), key
    assert (row["num_layers"], row["n_routed_experts"], row["vocab_size"]) == (28, 512, 131072)
    # every width of the row, the head count, the router's 768 outputs and top 12 as published
    assert (hf["hidden_size"], hf["num_attention_heads"], hf["ffn_hidden_size"],
            hf["expert_ffn_hidden_size"]) == (6144, 64, 12288, 2048)
    assert (hf["q_lora_rank"], hf["kv_lora_rank"], hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
            hf["v_head_dim"]) == (1536, 512, 128, 64, 128)
    assert (hf["moe_topk"], hf["zero_expert_num"], hf["routed_scaling_factor"]) == (12, 256, 6)
    assert hf["router_num_experts"] + hf["zero_expert_num"] == 768
    assert (hf["router_num_experts"], hf["routed_expert_offset"]) == (512, 0)
    entry = cell.config_entry
    assert entry["reduced"] == conf["reduced"] == list(REDUCED) and entry["source"] == SOURCE
    assert conf["published"] == {k: row[k] for k in REDUCED}
    assert "32 chips share each layer" in conf["deployment"] and len(conf["assumed"]) >= 8
    assert "pays it in line" in conf["deployment"]
    assert cell.serve_args == ["--decode-horizon", "8"]
    t = cell.traffic
    assert (t["generator"], t["clients"], t["ramp_s"], t["pool_per_client"], t["drain_s"]) == \
        ("closed_loop", 64, 6, 24, 60)
    assert t["prompt_tokens"]["max"] + t["output_tokens"]["max"] == 3072
    names = {m["name"] for m in catalog.metrics_for(bench, CELL, "per_layer")}
    assert set(NEW) <= names and "runner.moe_held_pick_share" not in names  # T11 folds it in
    assert {m["name"] for m in bench["per_layer"] if "workloads" not in m} <= names
    for older in OLDER:
        assert not set(NEW) & {m["name"] for m in catalog.metrics_for(bench, older, "per_layer")}


def test_the_program_loads_the_configuration_and_the_costs_are_the_hand_counts():
    import math

    import jax

    from smg_tpu.models import longcat_flash
    from smg_tpu.models.config import ModelConfig

    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    hf, arch = cell.hf_config, cell.architecture
    cfg = ModelConfig.from_hf_config(hf)
    assert (cfg.arch, cfg.num_layers, cfg.num_cache_layers) == ("longcat_flash", 4, 8)
    assert cfg.held_experts == (0, 16) and (cfg.num_experts, cfg.zero_experts) == (768, 256)
    # how loud the random weights are is the module's to say: the file has no key for it
    assert not [k for k in cell.config if k.startswith("random_")]
    assert not [k for k in cell.config["rehearsal"] if k.startswith("random_")]
    attention = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 64 * 128 * 6144
    dense, router, expert = 3 * 6144 * 12288, 6144 * 768, 3 * 6144 * 2048
    assert abs(attention - 90.57e6) < 0.01e6 and abs(dense - 226.49e6) < 0.01e6
    assert abs(router - 4.72e6) < 0.01e6 and abs(expert - 37.75e6) < 0.01e6
    outside = 2 * attention + 2 * dense + router
    assert abs(outside - 638.8e6) < 0.1e6
    p = arch.param_count(hf)
    assert p["always"] == 4 * outside and p["routed"] == 4 * 16 * expert
    assert p["embed"] == p["lm_head"] == 16384 * 6144
    assert abs(p["total"] - 5.17e9) < 0.01e9  # the issue's arithmetic: 10.35 GB
    whole = arch.param_count({**hf, "num_layers": 28, "n_routed_experts": 512,
                              "vocab_size": 131072})
    assert abs(whole["total"] - 560.7e9) < 0.1e9
    shapes = jax.eval_shape(lambda: longcat_flash.init_params(cfg, jax.random.PRNGKey(0)))
    count = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    assert abs(count - p["total"]) < 0.001e9  # the norms and the selection bias are the rest
    assert arch.attention_layers(hf) == 8 and arch.latent_entry_bytes(hf) == 1152
    assert arch.kv_bytes_per_token(hf) == 8 * 1152
    assert arch.mla_decode_flops_per_token(hf) == 2 * 64 * (576 + 512)
    assert arch.expert_bytes(hf) == 2 * expert and arch.expert_flops_per_row(hf) == 2 * expert
    peak = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
    # a 64-lane column at 1,300 tokens a lane: 5.31 GB whatever the routing, 0.77 GB of entries
    fixed = 2 * (4 * outside + 16384 * 6144)
    assert abs(fixed - 5.31e9) < 0.01e9
    least = arch.decode_min_seconds(hf, 1, 64 * 1300, 1, peak, 2)
    assert abs(least - (fixed + 8 * 1152 * 64 * 1300) / 819e9) < 1e-12
    flops = 2 * 4 * outside * 1000 + 2 * 64 * (128 + 64 + 128) * 8 * 500500
    assert abs(arch.prefill_min_seconds(hf, 1000, 500500, 1, peak) - flops / 197e12) < 1e-15


CONTROLS = ("", ".rotary_lanes_zeroed", ".latent_of_other_sequence", ".sublayer_caches_swapped",
            ".held_experts_give_nothing", ".identity_term_dropped", ".identity_picks_unweighted",
            ".q_scale_left_out", ".kv_scale_left_out", ".router_cut_to_held")


@pytest.fixture(scope="module")
def rehearsed():
    """The rehearsal's engine (tiny widths, float32), as ``run.py --rehearsal``
    builds it, and its cell."""
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.engine.latent_runner import LatentModelRunner
    from smg_tpu.models.config import ModelConfig
    from smg_tpu.tokenizer import MockTokenizer

    cell = catalog.Cell(catalog.load_benchmark(), CELL, rehearsal=True)
    model = ModelConfig.from_hf_config(cell.hf_config, dtype="float32")
    assert model.held_experts == (8, 8) and (model.num_experts, model.zero_experts) == (144, 48)
    assert (model.num_layers, model.num_cache_layers) == (3, 6)
    engine = Engine(EngineConfig(
        model=model, dtype="float32",
        cache=CacheConfig(page_size=16, num_pages=256, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(max_seq_len=1024, max_prefill_tokens=256, decode_horizon=8)),
        tokenizer=MockTokenizer())
    assert isinstance(engine.runner, LatentModelRunner) and engine.runner.spec.num_layers == 6
    return engine, cell


def test_the_shared_verdict_holds_the_drive_and_every_control_misses(rehearsed):
    engine, cell = rehearsed
    check = reference.check_engine(engine, cell, 34, True)
    assert check["ok"] and check["worst"] < 1e-3
    assert set(check["errors"]) == {"xla", "pallas_interpret"}
    assert set(check["control_errors"]) == {
        f"{impl}{name}" for impl in ("xla", "pallas_interpret") for name in CONTROLS}
    assert all(e > check["tolerance"] for e in check["control_errors"].values())


def _served_with(engine, change):
    """The engine's runner with ``change(params)`` served in place of its
    parameters, which the reference goes on reading."""
    import copy

    served = copy.copy(engine.runner)
    served.params = change(engine.runner.params)
    return served


@pytest.mark.parametrize("dead", ["identity_term", "routed_path"])
def test_a_dead_identity_term_or_routed_path_is_not_correct(rehearsed, dead, monkeypatch):
    """The identity picks adding nothing, then the held routed experts' output
    projections zeroed, in what is served and not in what the reference reads:
    a sound row misses the tolerance, so ``correct`` sees the one and the other
    (the rehearsal's share: 8 held of 96 real outputs beside 48 identity ones,
    top 8, so that a token meets a held expert in two layers of five as it does
    in one of four at the published share; the drive's ``controls`` hold the
    same two faults on the chip, ``held_experts_give_nothing`` and
    ``identity_term_dropped``)."""
    import jax.numpy as jnp

    from smg_tpu.ops import moe

    engine, cell = rehearsed
    arch = cell.architecture
    served = engine.runner
    if dead == "identity_term":
        monkeypatch.setattr(moe, "identity_picks", lambda x, routing, first: (
            jnp.zeros(x.shape, jnp.float32), jnp.sum(routing.experts >= first).astype(jnp.int32)))
    else:
        served = _served_with(engine, lambda p: {**p, "experts": {
            **p["experts"], "w_down": jnp.zeros_like(p["experts"]["w_down"])}})
    try:
        arch.drive = lambda _runner, *a: arch.Drive(served, *a)
        silent = reference.check_engine(engine, cell, 34, True)
    finally:
        arch.drive = arch.Drive
    sound = [e for per in silent["errors"].values() for e in per.values()]
    assert not silent["ok"] and max(sound) > silent["tolerance"]


def ctx(cell=CELL, **kw):
    c = catalog.Cell(catalog.load_benchmark(), cell)
    return {"hf": c.hf_config, "costs": c.architecture, "chips": 1, "kv_dtype_bytes": 2,
            "device": {"kind": "TPU v5 lite"}, "trace": None, "trace_window": None,
            "requests": [], "steps": [], "loads_before": {}, "loads_after": {}, **kw}


ATTN = "%smg.attn.decode.9 = bf16[64,64,640] custom-call(...)"
EXPERTS = "%smg.moe.experts.2 = bf16[768,2048] custom-call(...)"


def layer(t0: float) -> list:
    """One double block on a device's timeline as XLA orders it on a v5e: the
    first attention, the router, the first MLP, the second attention, the
    second MLP's first products, the branch's three grouped products and the
    combine, the last product with the addition."""
    return [[ATTN, t0, 0.0004], ["%fusion.1", t0 + 0.0005, 0.0003],
            ["%sort.2", t0 + 0.001, 0.0001], ["%fusion.4", t0 + 0.0012, 0.0006],
            [ATTN, t0 + 0.002, 0.0004], ["%fusion.5", t0 + 0.0025, 0.0004],
            [EXPERTS, t0 + 0.003, 0.0003], [EXPERTS, t0 + 0.0034, 0.0003],
            [EXPERTS, t0 + 0.0038, 0.0003], ["%fusion.3", t0 + 0.0042, 0.0001],
            ["%add_convert_fusion.4", t0 + 0.0044, 0.0002]]


TRACE = {"devices": {"/device:TPU:0": {
    "modules": [["jit_multi(7)", 0.0, 1.0], ["jit_step(3)", 2.0, 1.0]],
    "ops": [["%while.1", 0.0, 1.0],  # encloses the rest: not a leaf
            *[op for i in range(8) for op in layer(0.01 * i)],  # two columns of four layers
            [EXPERTS, 2.3, 0.2]]}},  # a prefill's: no
    "host": []}
STEPS = [{"kind": "decode", "t": 5.0, "horizon": 8, "decode_tokens": 128, "columns_run": 2,
          "moe_picks_held": 40, "moe_experts_hit": 22, "moe_picks_zero": 512},
         {"kind": "decode", "t": 99.0, "horizon": 8, "decode_tokens": 512, "columns_run": 8,
          "moe_picks_held": 160, "moe_experts_hit": 90, "moe_picks_zero": 2048},
         {"kind": "prefill", "t": 5.5, "horizon": 0, "decode_tokens": 0, "columns_run": 0}]
REQUESTS = [{"due": 4.5, "sent": 4.5, "first": 4.6, "last": 6.6, "done": 6.6,
             "prompt_tokens": 500, "output_tokens": 400}] * 64


def test_zero_pick_share_reads_the_counters():
    read = catalog.layer_metric_reader("runner.moe_zero_pick_share").read
    before = {"moe": {"picks": 1200, "picks_held": 30, "picks_zero": 400}}
    after = {"moe": {"picks": 13200, "picks_held": 280, "picks_zero": 4400}}
    assert read({"loads_before": before, "loads_after": after}) == 100 * 4000 / 12000
    assert read({"loads_before": before, "loads_after": before}) is None
    assert read({"loads_before": {}, "loads_after": {}}) is None
    older = {"moe": {"picks": 8, "picks_held": 1}}  # a model without identity experts
    assert read({"loads_before": older, "loads_after": older}) is None


def test_experts_time_share_sums_the_experts_kernels_inside_decode_launches():
    read = catalog.layer_metric_reader("runner.scmoe_experts_time_share").read
    got = read(ctx(trace=TRACE, trace_window=(4.0, 7.0), steps=STEPS))
    # eight layers' three grouped products; the prefill's kernel is not counted
    assert abs(got - 100 * 24 * 0.0003 / 1.0) < 1e-9
    unnamed = {"devices": {"d": {"modules": [["jit_multi(7)", 0.0, 1.0]],
                                 "ops": [[ATTN, 0.3, 0.5]]}}, "host": []}
    assert read(ctx(trace=unnamed, trace_window=(4.0, 7.0), steps=STEPS)) is None
    assert read(ctx(steps=STEPS)) is None


def test_the_thin_readers_hand_the_context_to_the_accepted_readers():
    import sys
    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layer_metrics"))
    from _common import columns_run

    c = ctx(trace=TRACE, trace_window=(4.0, 7.0), steps=STEPS, requests=REQUESTS,
            window=(4.0, 7.0))
    # 16 executions of the attention kernel over 8 cache layers: two columns
    assert columns_run(c) == 2
    mla = catalog.layer_metric_reader("kernels.scmoe_mla_decode_roofline_share").read(c)
    live = catalog.layer_metric_reader("kernels.decode_roofline_share").live_tokens(c, (4.0, 7.0))
    least = 2 * live * 8 * 1152 / 819e9  # the entry's bytes bound the kernel at 64 heads
    assert least > 2 * live * 8 * 2 * 64 * (576 + 512) / 197e12
    assert abs(mla - 100 * least / (16 * 0.0004)) < 1e-9
    experts = catalog.layer_metric_reader("kernels.scmoe_experts_decode_roofline_share").read(c)
    # one record in the traced window: 22 experts hit bound it (40 rows are nothing beside)
    least = max(22 * 2 * 3 * 6144 * 2048 / 819e9, 40 * 2 * 3 * 6144 * 2048 / 197e12)
    assert abs(experts - 100 * least / (24 * 0.0003)) < 1e-9
    same = catalog.layer_metric_reader("kernels.moe_decode_roofline_share").read(c)
    assert experts == same  # no second copy of the arithmetic
    before = {"moe": {"picks": 1200, "picks_held": 30, "picks_zero": 400}}
    after = {"moe": {"picks": 13200, "picks_held": 280, "picks_zero": 4400}}
    c = ctx(loads_before=before, loads_after=after)
    held = catalog.layer_metric_reader("runner.scmoe_held_pick_share").read(c)
    assert held == catalog.layer_metric_reader("runner.moe_held_pick_share").read(c)
    assert held == 100 * 250 / 12000


def test_the_new_readers_give_nothing_for_the_five_older_cells():
    for older in OLDER:
        c = ctx(older, trace=TRACE, trace_window=(4.0, 7.0), steps=STEPS, requests=REQUESTS,
                window=(4.0, 7.0),
                loads_before={"computed_prompt_tokens": 0, "moe": {"picks": 0, "picks_held": 0}},
                loads_after={"computed_prompt_tokens": 9, "moe": {"picks": 8, "picks_held": 1}})
        for name in NEW:
            assert catalog.layer_metric_reader(name).read(c) is None, (older, name)


def test_the_new_files_are_new_and_no_existing_entry_changed():
    """By name and not by place, and against the parent commit where git has
    it: every entry the parent's ``BENCHMARK.json`` has is there unchanged and
    in its place, and under ``benchmark/`` the parent's files are as they
    were."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = {c["name"]: c for c in bench["configs"]}["longcat-flash-chat"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("longcat-flash-chat", "reason", 1)
    assert config["file"] == "benchmark/configs/longcat-flash-chat.json"
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = metrics[name]
        assert m["workloads"] == [CELL]
        meta = catalog.layer_metric_reader(name).META
        assert (m["layer"], m["moves"], m["unit"]) == (meta["layer"], "output_tok_per_s", meta["unit"])
        assert meta["source"].startswith(m["source"])
    for m in bench["per_layer"]:  # no older metric's list took the new cell
        assert m["name"] in NEW or CELL not in m.get("workloads", [])
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    listing = catalog.listing()
    assert "longcat_flash" in listing["architectures"] and "reason" in listing["traffic"]
    assert set(NEW) <= set(listing["layer_metrics"])
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "reason.json")))
    assert traffic["clients"] == 64 and traffic["output_tokens"]["median"] == 512
    parent = "4a8fd70df0a2bb951f5e0b5ff457b621ee3ec6c6"
    git = lambda *a: subprocess.run(["git", "-C", ROOT, *a], capture_output=True, text=True)
    if git("cat-file", "-e", parent + "^{commit}").returncode != 0:
        pytest.skip("the parent commit is not in this checkout")
    was = json.loads(git("show", parent + ":BENCHMARK.json").stdout)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[key][: len(was[key])] == was[key], key
    assert {k: v for k, v in bench.items() if not isinstance(v, list) or k in ("command", "paths")} \
        == {k: v for k, v in was.items() if not isinstance(v, list) or k in ("command", "paths")}
    changed = git("diff", "--name-status", parent, "--", "benchmark").stdout.split("\n")
    assert all(line.startswith("A\t") for line in changed if line), changed

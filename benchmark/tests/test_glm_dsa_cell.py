"""The ``glm_moe_dsa`` architecture, the ``glm-5.2`` configuration and the cell
``longdoc`` over it hold what ``test_architectures.py`` asks of one: the harness
resolves them by name and the configuration is the catalog row's but for the
cut; the costs are the cut's sizes by hand, and the generic decode cost never
passes the exact count; ``reference.check_engine`` holds the drive (two caches,
two side buffers) to the file's own ``logits`` in the rehearsal, where the
sequences pass ``index_topk`` and the selector's two controls are offered and
miss the tolerance with the shared one; the seven new readers read a hand-made
trace, scope map, ring and counters and give nothing for the older cells; the
traffic's sizes are the issue's; and the additions are new files and entries of
their own.  CPU."""

import json
import os
import subprocess

import catalog
import pytest
import reference
from conftest import ROOT

CELL = "glm-5.2.longdoc"
OLDER = ("qwen3-1.7b.eval", "olmo-hybrid-7b.gen", "openpangu-ultra-moe-718b.reason",
         "mimo-v2-flash.mixed", "k-exaone-236b-a23b.reason", "longcat-flash-chat.reason",
         "nemotron-3-super-120b-a12b.reason", "kimi-linear-48b-a3b.reason")
NEW = ("kernels.dsa_index_decode_roofline_share", "kernels.dsa_attn_decode_roofline_share",
       "kernels.dsa_moe_decode_roofline_share", "runner.dsa_moe_held_pick_share",
       "runner.dsa_index_time_share", "runner.dsa_prefill_index_time_share",
       "runner.dsa_selecting_row_share")
SOURCE = "https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json"
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "mlp_layer_types": ["dense"] + ["sparse"] * 4,
           "indexer_types": ["full", "full", "shared", "shared", "shared"],
           "n_routed_experts": 16, "vocab_size": 19360}
PEAK = {"bytes_per_s": 819e9, "flops_per_s": 197e12}


def catalog_row() -> dict:
    """The row of the model-configs guide where this machine has it, else the
    file's own ``published`` laid over the file."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(path):
        for line in open(path):
            row = json.loads(line)
            if row["name"] == "GLM-5.2":
                assert row["source_url"] == SOURCE
                return row["config"]
    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    own = {"router_num_experts", "routed_expert_offset"}
    return {**{k: v for k, v in cell.hf_config.items() if k not in own}, **cell.config["published"]}


def test_the_cell_resolves_and_the_configuration_is_the_rows_but_for_the_cut():
    bench = catalog.load_benchmark()
    cell = catalog.Cell(bench, CELL)
    assert cell.architecture.__name__.endswith("glm_moe_dsa") and cell.chips == 1
    hf, conf, row = cell.hf_config, cell.config, catalog_row()
    assert not set(hf) & {"architecture", "reduced", "published", "assumed", "rehearsal"}
    assert set(hf) == set(row) | {"router_num_experts", "routed_expert_offset"}
    for key, want in row.items():
        assert hf[key] == REDUCED.get(key, want), key
    assert (row["num_hidden_layers"], row["n_routed_experts"], row["vocab_size"]) == (78, 256, 154880)
    # the kept layers are the published layer 2 and the period 6-9, kinds and all
    kept = [2, 6, 7, 8, 9]
    assert [row["indexer_types"][l] for l in kept] == hf["indexer_types"]
    assert [row["mlp_layer_types"][l] for l in kept] == hf["mlp_layer_types"]
    # every width of the row as published
    assert (hf["hidden_size"], hf["num_attention_heads"], hf["intermediate_size"],
            hf["moe_intermediate_size"]) == (6144, 64, 12288, 2048)
    assert (hf["q_lora_rank"], hf["kv_lora_rank"], hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
            hf["v_head_dim"]) == (2048, 512, 192, 64, 256)
    assert (hf["index_topk"], hf["index_n_heads"], hf["index_head_dim"]) == (2048, 32, 128)
    assert (hf["num_experts_per_tok"], hf["routed_scaling_factor"]) == (8, 2.5)
    assert (hf["router_num_experts"], hf["routed_expert_offset"]) == (256, 0)
    entry = cell.config_entry
    assert entry["reduced"] == conf["reduced"] == list(REDUCED) and entry["source"] == SOURCE
    assert conf["published"] == {k: row[k] for k in REDUCED}
    assert "sixteen chips share each layer" in conf["deployment"] and len(conf["assumed"]) >= 10
    assert "layers 2 and 6-9" in conf["deployment"] and "idle_share" in conf["deployment"]
    assert cell.serve_args == ["--decode-horizon", "8", "--max-seq-len", "17536"]
    t = cell.traffic
    assert (t["generator"], t["clients"], t["ramp_s"], t["pool_per_client"], t["drain_s"]) == \
        ("closed_loop", 32, 6, 24, 60)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 6144, "sigma": 0.5,
                                  "min": 3072, "max": 16384}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.6,
                                  "min": 128, "max": 1024}
    # the longest request and a frame fit the table
    assert t["prompt_tokens"]["max"] + 2 + t["output_tokens"]["max"] + 8 <= 17536
    names = {m["name"] for m in catalog.metrics_for(bench, CELL, "per_layer")}
    assert set(NEW) <= names and "runner.moe_held_pick_share" not in names  # T11 folds it in
    assert "runner.decode_routing_time_share" not in names  # the benchmark's list to extend
    assert {m["name"] for m in bench["per_layer"] if "workloads" not in m} <= names
    for older in OLDER:
        assert not set(NEW) & {m["name"] for m in catalog.metrics_for(bench, older, "per_layer")}


def test_the_program_loads_the_configuration_and_the_costs_are_the_hand_counts():
    import math

    import jax

    from smg_tpu.models import glm_moe_dsa
    from smg_tpu.models.config import ModelConfig

    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    hf, arch = cell.hf_config, cell.architecture
    cfg = ModelConfig.from_hf_config(hf)
    assert (cfg.arch, cfg.num_layers, cfg.num_cache_layers, cfg.num_index_layers) == \
        ("glm_moe_dsa", 5, 5, 2)
    assert cfg.held_experts == (0, 16) and cfg.num_experts == 256
    assert not [k for k in cell.config if k.startswith("random_")]
    attention = 6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448 + 64 * 256 * 6144
    indexer = 2048 * 4096 + 6144 * 128 + 6144 * 32
    dense, router, expert = 3 * 6144 * 12288, 6144 * 256, 3 * 6144 * 2048
    assert abs(attention - 165.0e6) < 0.1e6 and abs(indexer - 9.4e6) < 0.1e6
    assert abs(dense - 226.5e6) < 0.1e6 and abs(router - 1.6e6) < 0.1e6
    assert abs(attention + indexer + dense - 400.9e6) < 0.1e6
    assert abs(attention + router + 17 * expert - 808.3e6) < 0.1e6
    p = arch.param_count(hf)
    always = 5 * attention + 2 * indexer + dense + 4 * (expert + router)
    assert p["always"] == always and p["routed"] == 4 * 16 * expert
    assert p["embed"] == p["lm_head"] == 19360 * 6144
    assert abs(p["total"] - 3.88e9) < 0.01e9  # the issue's arithmetic: 7.76 GB
    whole = arch.param_count({**hf, **catalog_row(), "router_num_experts": 256})
    assert abs(whole["total"] - 743e9) < 1e9
    shapes = jax.eval_shape(lambda: glm_moe_dsa.init_params(cfg, jax.random.PRNGKey(0)))
    count = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    assert abs(count - p["total"]) < 0.001e9  # the norms and the selection bias are the rest
    assert arch.attention_layers(hf) == 5 and arch.index_layers(hf) == 2
    assert arch.latent_entry_bytes(hf) == 1152 and arch.index_key_bytes(hf) == 256
    assert arch.kv_bytes_per_token(hf) == 5 * 1152 + 2 * 256 == 6272
    assert arch.mla_decode_flops_per_token(hf) == 2 * 64 * (576 + 512)
    assert arch.index_decode_flops_per_token(hf) == 2 * 32 * 128 + 2 * 32
    assert arch.expert_bytes(hf) == 2 * expert and arch.expert_flops_per_row(hf) == 2 * expert
    assert arch.selected(700, hf) == 700 and arch.selected(9000, hf) == 2048
    # a prefill of 4,096 tokens behind 8,192: the indexers meet every pair, the
    # attention's pairs are counted at their least
    pairs = sum(range(8193, 8193 + 4096))
    flops = (2 * always * 4096 + (2 * 32 * 128 + 64) * 2 * pairs
             + 2 * 64 * (192 + 64 + 256) * 5 * pairs * 2048 / 1048576)
    assert abs(arch.prefill_min_seconds(hf, 4096, pairs, 1, PEAK) - flops / 197e12) < 1e-12


@pytest.mark.parametrize("lanes", [[3100] * 32, [17000] * 4 + [3072] * 28, [500, 2048, 9000]],
                         ids=["all-short", "four-long", "below-at-above"])
def test_the_generic_decode_cost_never_passes_the_exact_count(lanes):
    """``decode_min_seconds`` is given the lanes' contexts as one sum; what a
    column must read is ``min(context, 2048)`` entries of each lane.  It errs
    low on every mix, and the experts are on neither side."""
    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    hf, arch = cell.hf_config, cell.architecture
    p = arch.param_count(hf)
    fixed = (p["always"] + p["lm_head"]) * 2
    exact = (fixed + sum(2 * 256 * n + 5 * 1152 * min(n, 2048) for n in lanes)) / 819e9
    got = arch.decode_min_seconds(hf, 1, sum(lanes), 1, PEAK, 2)
    assert fixed / 819e9 < got <= exact
    # the column of the issue: 32 lanes at 7.5 k read 0.12 GB of index keys and 0.38 GB of entries
    keys, entries = 32 * 7500 * 2 * 256, 32 * 2048 * 5 * 1152
    assert abs(keys - 0.12e9) < 0.01e9 and abs(entries - 0.38e9) < 0.01e9
    assert abs(fixed - 2.6e9) < 0.1e9


CONTROLS = ("", ".rotary_lanes_zeroed", ".router_cut_to_held",
            ".index_keys_of_other_sequence", ".nearest_selected")


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
    assert model.held_experts == (4, 4) and model.num_experts == 16
    assert (model.num_layers, model.num_index_layers, model.index_topk) == (5, 2, 32)
    engine = Engine(EngineConfig(
        model=model, dtype="float32",
        cache=CacheConfig(page_size=16, num_pages=256, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(max_seq_len=1024, max_prefill_tokens=256, decode_horizon=8)),
        tokenizer=MockTokenizer())
    assert isinstance(engine.runner, LatentModelRunner)
    assert engine.runner.v_cache.shape == (2, 256, 16, 32)
    return engine, cell


def test_the_shared_verdict_holds_the_drive_with_the_selection_live(rehearsed):
    """The check's 88 and 48 tokens pass the rehearsal's ``index_topk`` 32, so
    every compared row reads a selection and the selector's controls are
    offered; at the published 2,048 the check's 700 and 380 tokens would not
    reach it (PERF.md, Open questions)."""
    engine, cell = rehearsed
    check = reference.check_engine(engine, cell, 34, True)
    assert check["ok"] and check["worst"] < 1e-3
    assert set(check["errors"]) == {"xla", "pallas_interpret"}
    assert set(check["control_errors"]) == {
        f"{impl}{name}" for impl in ("xla", "pallas_interpret") for name in CONTROLS}
    assert all(e > check["tolerance"] for e in check["control_errors"].values())
    wide = catalog.Cell(catalog.load_benchmark(), CELL, rehearsal=True)
    wide.config = {**wide.config, "index_topk": 2048}
    assert 88 < wide.hf_config["index_topk"]  # the published regime of the check: dense


def ctx(cell=CELL, **kw):
    c = catalog.Cell(catalog.load_benchmark(), cell)
    return {"hf": c.hf_config, "costs": c.architecture, "chips": 1, "kv_dtype_bytes": 2,
            "device": {"kind": "TPU v5 lite"}, "trace": None, "trace_window": None,
            "requests": [], "steps": [], "loads_before": {}, "loads_after": {}, **kw}


BF, F32 = "bf16[32,2048,640]{2,1,0:T(8,128)(2,1)}", "f32[32,17544]{1,0:T(8,128)}"
SCOPES = {
    "('decode_multi', 32)": {"family": "multi", "scopes": {
        "smg.mla.index.q": [f"fusion.1 = {F32}"], "smg.mla.index.k": [f"gather.2 = {BF}"],
        "smg.mla.index.score": [f"fusion.3 = {F32}"], "smg.mla.index.select": [f"sort.4 = {F32}"],
        "smg.mla.sparse": [f"gather.5 = {BF}"], "smg.attn.decode": [f"fusion.6 = {BF}"],
        "smg.mla.q": [f"fusion.7 = {BF}"], "smg.moe.route": [f"fusion.8 = {F32}"],
        "": [f"while.1 = ({F32})"]}},
    "('prefill', 4096)": {"family": "step", "scopes": {
        "smg.mla.index.score": [f"fusion.3 = {F32}"], "smg.mla.index.select": [f"fusion.4 = {F32}"],
        "smg.attn.prefill": [f"fusion.6 = {BF}"], "": []}},
}


def ev(name, shape, op, start, dur):
    return [f"%{name} = {shape} {op}({shape} %p.1), kind=kLoop", start, dur]


def column(t0: float) -> list:
    """One decode column: two layers with an indexer (queries, keys, scores,
    selection) and five that gather and attend."""
    ops = []
    for l in range(5):
        t = t0 + 0.01 * l
        if l < 2:
            ops += [ev("fusion.1", F32, "fusion", t, 0.0002), ev("gather.2", BF, "gather", t + 0.0002, 0.0004),
                    ev("fusion.3", F32, "fusion", t + 0.0006, 0.0003),
                    ev("sort.4", F32, "sort", t + 0.0009, 0.0005)]
        ops += [ev("gather.5", BF, "gather", t + 0.002, 0.001),
                ev("fusion.6", BF, "fusion", t + 0.003, 0.0005),
                ev("fusion.7", BF, "fusion", t + 0.004, 0.001)]
    return ops


TRACE = {"devices": {"/device:TPU:0": {
    "modules": [["jit_multi(7)", 0.0, 1.0], ["jit_step(3)", 2.0, 0.7]],
    "ops": [ev("while.1", f"({F32})", "while", 0.0, 1.0),  # encloses the rest: not a leaf
            *column(0.0), *column(0.1),
            ev("fusion.3", F32, "fusion", 2.1, 0.02), ev("fusion.4", F32, "fusion", 2.2, 0.03),
            ev("fusion.6", BF, "fusion", 2.3, 0.4)]}},
    "host": []}
STEPS = [{"kind": "decode", "t": 5.0, "horizon": 8, "decode_tokens": 64, "columns_run": 2,
          "moe_picks_held": 40, "moe_experts_hit": 22},
         {"kind": "decode", "t": 99.0, "horizon": 8, "decode_tokens": 256, "columns_run": 8,
          "moe_picks_held": 160, "moe_experts_hit": 90},
         {"kind": "prefill", "t": 5.5, "horizon": 0, "decode_tokens": 0, "columns_run": 0}]
#: thirty callers behind 7,000-7,400 tokens and two behind 1,000-1,400
REQUESTS = ([{"due": 4.5, "sent": 4.5, "first": 4.6, "last": 6.6, "done": 6.6,
              "prompt_tokens": 7000, "output_tokens": 400}] * 30
            + [{"due": 4.5, "sent": 4.5, "first": 4.6, "last": 6.6, "done": 6.6,
                "prompt_tokens": 1000, "output_tokens": 400}] * 2)


def traced(**kw):
    return ctx(trace=TRACE, trace_window=(4.0, 7.0), steps=STEPS, requests=REQUESTS,
               window=(4.0, 7.0), loads_after={"programs": {"scopes": SCOPES}}, **kw)


def test_the_two_roofline_readers_take_each_callers_own_context():
    import sys
    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layer_metrics"))
    from _common import columns_run
    from _dsa import live_tokens

    c = traced()
    assert columns_run(c) == 2  # no kernel named smg.attn.decode: the step ring's columns
    # the callers decode through two of the window's three seconds
    live = live_tokens(c, (4.0, 7.0))
    assert abs(live - (30 * 7200 + 2 * 1200) * 2 / 3) < 1e-6
    capped = live_tokens(c, (4.0, 7.0), cap=2048)
    assert abs(capped - (30 * 2048 + 2 * 1200) * 2 / 3) < 1e-6
    index = catalog.layer_metric_reader("kernels.dsa_index_decode_roofline_share").read(c)
    least = 2 * live * 2 * 256 / 819e9  # the keys' bytes bound it
    assert least > 2 * live * 2 * (2 * 32 * 128 + 64) / 197e12
    assert abs(index - 100 * least / (4 * (0.0003 + 0.0005))) < 1e-9
    attn = catalog.layer_metric_reader("kernels.dsa_attn_decode_roofline_share").read(c)
    least = 2 * capped * 5 * 1152 / 819e9
    assert abs(attn - 100 * least / (10 * (0.001 + 0.0005))) < 1e-9
    for name in NEW[:2]:  # nothing without a trace, a scope map or the columns
        read = catalog.layer_metric_reader(name).read
        assert read(ctx(steps=STEPS, requests=REQUESTS)) is None
        assert read({**c, "loads_after": {}}) is None
        assert read({**c, "steps": []}) is None


def test_the_time_shares_sum_the_indexers_scopes_by_family():
    c = traced()
    decode = catalog.layer_metric_reader("runner.dsa_index_time_share").read(c)
    assert abs(decode - 100 * 4 * (0.0002 + 0.0004 + 0.0003 + 0.0005) / 1.0) < 1e-9
    prefill = catalog.layer_metric_reader("runner.dsa_prefill_index_time_share").read(c)
    assert abs(prefill - 100 * (0.02 + 0.03) / 0.7) < 1e-9
    mixer = catalog.layer_metric_reader("runner.decode_mixer_time_share").read(c)
    assert mixer > decode  # the indexer's scopes are the mixer's: under smg.mla
    for name in ("runner.dsa_index_time_share", "runner.dsa_prefill_index_time_share"):
        assert catalog.layer_metric_reader(name).read({**c, "loads_after": {}}) is None


def test_the_thin_readers_and_the_counter():
    c = traced()
    before = {"moe": {"picks": 1200, "picks_held": 30, "dsa_rows": 100, "dsa_rows_selecting": 90}}
    after = {"moe": {"picks": 13200, "picks_held": 780, "dsa_rows": 1100,
                     "dsa_rows_selecting": 1070}}
    k = ctx(loads_before=before, loads_after=after)
    held = catalog.layer_metric_reader("runner.dsa_moe_held_pick_share").read(k)
    assert held == catalog.layer_metric_reader("runner.moe_held_pick_share").read(k) == 100 * 750 / 12000
    rows = catalog.layer_metric_reader("runner.dsa_selecting_row_share").read
    assert rows(k) == 100 * 980 / 1000
    assert rows(ctx(loads_before=before, loads_after=before)) is None
    assert rows(ctx(loads_before={"moe": {"picks": 1}}, loads_after={"moe": {"picks": 9}})) is None
    experts = {"devices": {"d": {"modules": [["jit_multi(7)", 0.0, 1.0]], "ops": [
        ["%smg.moe.experts.2 = bf16[768,2048] custom-call(...)", 0.1 * i, 0.0003]
        for i in range(6)]}}, "host": []}
    e = {**c, "trace": experts}
    got = catalog.layer_metric_reader("kernels.dsa_moe_decode_roofline_share").read(e)
    least = max(22 * 2 * 3 * 6144 * 2048 / 819e9, 40 * 2 * 3 * 6144 * 2048 / 197e12)
    assert abs(got - 100 * least / (6 * 0.0003)) < 1e-9
    assert got == catalog.layer_metric_reader("kernels.moe_decode_roofline_share").read(e)


def test_the_new_readers_give_nothing_for_the_older_cells():
    loads = {"programs": {"scopes": SCOPES},
             "moe": {"picks": 8, "picks_held": 1, "dsa_rows": 5, "dsa_rows_selecting": 5}}
    for older in OLDER:
        c = ctx(older, trace=TRACE, trace_window=(4.0, 7.0), steps=STEPS, requests=REQUESTS,
                window=(4.0, 7.0), loads_before={"moe": {"picks": 0, "picks_held": 0}},
                loads_after=loads)
        for name in NEW[:-1]:
            assert catalog.layer_metric_reader(name).read(c) is None, (older, name)
    # the counter's reader reads whatever program has the counters: no other has
    none = ctx(OLDER[2], loads_before={"moe": {"picks": 0}}, loads_after={"moe": {"picks": 8}})
    assert catalog.layer_metric_reader(NEW[-1]).read(none) is None


@pytest.mark.parametrize("seed", [0, 2_900_000_011])
def test_the_traffics_sizes_are_the_issues(seed):
    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    chains = cell.chains(seed, 51.0)
    assert len(chains) == 32 and all(len(c["requests"]) == 24 for c in chains)
    prompts = [r["body"][1] + 2 for c in chains for r in c["requests"]]  # the template's two
    outputs = [r["max_tokens"] for c in chains for r in c["requests"]]
    assert min(prompts) >= 3072 and max(prompts) <= 16384 and not any(
        r["prefix"] for c in chains for r in c["requests"])
    assert min(outputs) >= 128 and max(outputs) <= 1024
    prompts.sort()
    assert 5400 < prompts[len(prompts) // 2] < 6900
    assert 0.70 < sum(p > 4096 for p in prompts) / len(prompts) < 0.90  # four in five are cut
    rows = sum(prompts)
    assert 0.60 < sum(max(p - 2048, 0) for p in prompts) / rows < 0.78  # ~70 % select
    assert [c["start"] for c in chains] == [c * 6 / 32 for c in range(32)]


def test_the_new_files_are_new_and_no_existing_entry_changed():
    """By name and not by place, and against the parent commit where git has
    it: every entry the parent's ``BENCHMARK.json`` has is there unchanged and
    in its place, and under ``benchmark/`` the parent's files are as they
    were."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = {c["name"]: c for c in bench["configs"]}["glm-5.2"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("glm-5.2", "longdoc", 1)
    assert config["file"] == "benchmark/configs/glm-5.2.json" and config["source"] == SOURCE
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
    assert "glm_moe_dsa" in listing["architectures"] and "longdoc" in listing["traffic"]
    assert set(NEW) <= set(listing["layer_metrics"])
    parent = "f21796077fee759ed82d958a96a2d4ede39a836d"
    git = lambda *a: subprocess.run(["git", "-C", ROOT, *a], capture_output=True, text=True)
    if git("cat-file", "-e", parent + "^{commit}").returncode != 0:
        pytest.skip("the parent commit is not in this checkout")
    was = json.loads(git("show", parent + ":BENCHMARK.json").stdout)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[key][: len(was[key])] == was[key], key
    assert (len(bench["configs"]), len(bench["workloads"]), len(bench["per_layer"])) == \
        (len(was["configs"]) + 1, len(was["workloads"]) + 1, len(was["per_layer"]) + 7)
    assert {k: v for k, v in bench.items() if not isinstance(v, list) or k in ("command", "paths")} \
        == {k: v for k, v in was.items() if not isinstance(v, list) or k in ("command", "paths")}
    changed = git("diff", "--name-status", parent, "--", "benchmark").stdout.split("\n")
    assert all(line.startswith("A\t") for line in changed if line), changed

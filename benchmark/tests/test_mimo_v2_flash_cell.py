"""The ``mimo_v2_flash`` architecture, the ``mimo-v2-flash`` configuration and
the cell ``mixed`` hold what ``test_architectures.py`` asks of one: the harness
resolves them by name, ``reference.check_engine`` holds the drive to the file's
own ``logits`` with the shared control and the drive's four (the window
ignored, the sink left out, the other sequence's ring, the router cut to the
experts held) all missing the tolerance, and a routed path that gives nothing
is not correct; the costs give the cut's sizes by hand, the two new readers read
hand-made contexts and give nothing for the three older cells, and the
additions are new files and entries of their own.  CPU."""

import json
import os

import catalog
import reference
from conftest import ROOT

CELL = "mimo-v2-flash.mixed"
OLDER = ("qwen3-1.7b.eval", "olmo-hybrid-7b.gen", "openpangu-ultra-moe-718b.reason")
NEW = ("kernels.swa_decode_roofline_share", "scheduler.window_recompute_share")
PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
CATALOG_ROW = {
    "attention_value_scale": 0.707, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 16384, "max_position_embeddings": 262144,
    "model_type": "mimo_v2_flash", "num_attention_heads": 64, "head_dim": 192,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
    "rope_theta": 5000000, "tie_word_embeddings": False, "vocab_size": 152576,
    "partial_rotary_factor": 0.334, "sliding_window": 128, "swa_rope_theta": 10000,
    "attention_bias": False, "v_head_dim": 128, "hybrid_layer_pattern": PATTERN,
    "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
    "sliding_window_size": 128, "attention_chunk_size": 128,
    "moe_layer_freq": [0] + [1] * 47, "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": None, "num_experts_per_tok": 8, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
    "routed_scaling_factor": None, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 8, "swa_head_dim": 192, "swa_v_head_dim": 128}
REDUCED = {"num_hidden_layers": 7, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 1, 0],
           "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "n_routed_experts": 16, "vocab_size": 19072}


def test_the_cell_resolves_and_the_configuration_is_the_rows_but_for_the_cut():
    bench = catalog.load_benchmark()
    cell = catalog.Cell(bench, CELL)
    assert cell.architecture.__name__.endswith("mimo_v2_flash") and cell.chips == 1
    hf, conf = cell.hf_config, cell.config
    assert not set(hf) & {"architecture", "reduced", "published", "assumed", "rehearsal"}
    for key, want in CATALOG_ROW.items():
        assert hf[key] == REDUCED.get(key, want), key
    entry = cell.config_entry
    assert entry["reduced"] == conf["reduced"] == list(REDUCED)
    assert conf["published"] == {k: CATALOG_ROW[k] for k in REDUCED}
    assert entry["source"] == \
        "https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json"
    # the router is never cut: its width stands beside the experts held
    assert (hf["router_num_experts"], hf["routed_expert_offset"]) == (256, 0)
    assert "sixteen chips share each layer" in conf["deployment"] and len(conf["assumed"]) >= 8
    assert cell.serve_args == ["--decode-horizon", "8"]
    t = cell.traffic
    assert (t["generator"], t["clients"], t["ramp_s"], t["pool_per_client"], t["drain_s"]) == \
        ("closed_loop", 64, 6, 24, 60)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 1.0,
                                  "min": 128, "max": 6000}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 768, "sigma": 0.6,
                                  "min": 128, "max": 2048}
    # a decode frame short of serve's default --max-seq-len
    assert t["prompt_tokens"]["max"] + t["output_tokens"]["max"] <= 8192 - 8
    names = {m["name"] for m in catalog.metrics_for(bench, CELL, "per_layer")}
    assert set(NEW) <= names and "kernels.linattn_decode_roofline_share" not in names
    globals_ = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert globals_ <= names
    for older in OLDER:
        assert not set(NEW) & {m["name"] for m in catalog.metrics_for(bench, older, "per_layer")}


def test_the_traffic_is_the_mix_the_issue_describes():
    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    chains = cell.chains(7, 51.0)
    assert len(chains) == 64
    prompts = sorted(r["body"][1] + 2 for c in chains for r in c["requests"])
    outputs = [r["max_tokens"] for c in chains for r in c["requests"]]
    n = len(prompts)
    assert n == 64 * 24 and prompts[0] == 128 and prompts[-1] == 6000
    assert 0.07 < sum(p < 290 for p in prompts) / n < 0.13
    assert 0.07 < sum(p > 3700 for p in prompts) / n < 0.13
    assert 1 / 16 < sum(p > 4096 for p in prompts) / n < 1 / 9  # over a step's budget
    assert 1450 < sum(prompts) / n < 1650 and 840 < sum(outputs) / n < 940
    assert max(p + o for c in chains for r in c["requests"]
               for p, o in [(r["body"][1] + 2, r["max_tokens"])]) <= 8048


def test_the_program_loads_the_configuration_and_the_costs_are_the_hand_counts():
    from smg_tpu.models.config import ModelConfig

    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    hf, arch = cell.hf_config, cell.architecture
    cfg = ModelConfig.from_hf_config(hf)
    assert (cfg.arch, cfg.num_layers, cfg.num_cache_layers) == ("mimo_v2_flash", 7, 2)
    assert cfg.held_experts == (0, 16) and cfg.num_experts == 256
    assert cfg.kv_lanes(False) == (768, 512) and cfg.kv_lanes(True) == (1536, 1024)
    full = 4096 * 64 * 192 + 4096 * 4 * 192 + 4096 * 4 * 128 + 64 * 128 * 4096
    window = 4096 * 64 * 192 + 4096 * 8 * 192 + 4096 * 8 * 128 + 64 * 128 * 4096
    expert, dense, router = 3 * 4096 * 2048, 3 * 4096 * 16384, 4096 * 256
    assert abs(full - 89.13e6) < 0.01e6 and abs(window - 94.37e6) < 0.01e6
    assert abs(expert - 25.17e6) < 0.01e6 and abs(dense - 201.33e6) < 0.01e6
    p = arch.param_count(hf)
    always = 2 * full + 5 * window + dense + 6 * router
    assert p["always"] == always and p["routed"] == 6 * 16 * expert
    assert p["embed"] == p["lm_head"] == 19072 * 4096
    assert abs(p["total"] - 3.43e9) < 0.005e9
    # what every token passes whatever the routing, and what its expected three rows add
    assert abs((always + p["lm_head"]) - 0.936e9) < 0.005e9
    assert abs(6 * 0.5 * expert - 75.5e6) < 0.1e6
    assert arch.kv_bytes_per_token(hf) == 2 * 2560 == 5120
    assert arch.window_entry_bytes(hf) == 5120 and arch.window_layers(hf) == 5
    assert arch.window(hf) == 128 and arch.attention_layers(hf) == 2
    assert arch.expert_bytes(hf) == 2 * expert and abs(arch.expert_bytes(hf) - 50.33e6) < 0.01e6
    assert arch.expert_flops_per_row(hf) == 6 * 4096 * 2048
    peak = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
    # a 64-lane column at 2,100 tokens a lane: 1.87 GB whatever the routing, 0.69 GB of pages
    least = arch.decode_min_seconds(hf, 1, 64 * 2100, 1, peak, 2)
    fixed = 2 * (always + 19072 * 4096)
    assert abs(fixed - 1.87e9) < 0.01e9
    assert abs(least - (fixed + 5120 * 64 * 2100) / 819e9) < 1e-12
    flops = 2 * always * 1000 + 2 * 64 * (192 + 128) * 2 * 500500
    assert abs(arch.prefill_min_seconds(hf, 1000, 500500, 1, peak) - flops / 197e12) < 1e-15


CONTROLS = ("", ".window_ignored", ".sink_left_out", ".other_sequences_ring",
            ".router_cut_to_held")


def test_the_shared_verdict_holds_the_drive_and_every_control_misses():
    """The rehearsal's engine (tiny widths, float32), as ``run.py --rehearsal``
    builds it, through ``reference.check_engine``: two chunks (the second
    behind a prefix of 40, five windows long), then decode past the ring's
    wrap.  Then the same with the held experts' output projections zeroed in
    what is served and not in what the reference reads: not correct."""
    import copy

    import jax.numpy as jnp

    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.models.config import ModelConfig
    from smg_tpu.tokenizer import MockTokenizer

    cell = catalog.Cell(catalog.load_benchmark(), CELL, rehearsal=True)
    model = ModelConfig.from_hf_config(cell.hf_config, dtype="float32")
    assert model.held_experts == (4, 8) and model.num_experts == 16
    assert model.sliding_window == 8 and model.rope_dim == 16
    engine = Engine(EngineConfig(
        model=model, dtype="float32",
        cache=CacheConfig(page_size=16, num_pages=256, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(max_seq_len=1024, max_prefill_tokens=256, decode_horizon=8)),
        tokenizer=MockTokenizer())
    assert engine.runner.state_spec.ring_tokens == 32
    check = reference.check_engine(engine, cell, 34, True)
    assert check["ok"] and check["worst"] < 1e-3
    assert set(check["errors"]) == {"xla", "pallas_interpret"}
    assert set(check["control_errors"]) == {
        f"{impl}{name}" for impl in ("xla", "pallas_interpret") for name in CONTROLS}
    assert all(e > check["tolerance"] for e in check["control_errors"].values())

    dead = copy.copy(engine.runner)
    dead.params = {kind: ({**stack, "w_down": jnp.zeros_like(stack["w_down"])}
                          if isinstance(stack, dict) and "router" in stack else stack)
                   for kind, stack in engine.runner.params.items()}
    arch = cell.architecture
    try:
        arch.drive = lambda _runner, *a: arch.Drive(dead, *a)
        silent = reference.check_engine(engine, cell, 34, True)
    finally:
        arch.drive = arch.Drive
    assert not silent["ok"] and silent["worst"] > silent["tolerance"]


def ctx(cell=CELL, **kw):
    c = catalog.Cell(catalog.load_benchmark(), cell)
    return {"hf": c.hf_config, "costs": c.architecture, "chips": 1, "kv_dtype_bytes": 2,
            "device": {"kind": "TPU v5 lite"}, "trace": None, "trace_window": None,
            "requests": [], "steps": [], **kw}


PAGED = "%smg.attn.decode.9 = bf16[64,64,512] custom-call(...)"
RING = "%smg.attn.window_decode.4 = bf16[64,64,1024] custom-call(...)"
TRACE = {"devices": {"/device:TPU:0": {
    "modules": [["jit_multi(7)", 0.0, 1.0], ["jit_step(3)", 2.0, 1.0]],
    "ops": [["%while.1", 0.0, 1.0],  # encloses the rest: not a leaf
            *[[PAGED, 0.01 * i, 0.002] for i in range(4)],  # two columns of two full layers
            *[[RING, 0.1 + 0.01 * i, 0.0005] for i in range(10)],  # and of five window layers
            [RING, 2.10, 0.30],  # a kernel of that name in a prefill launch: not counted
            ["%fusion.3", 0.50, 0.4]]}}, "host": []}
STEPS = [{"kind": "decode", "t": 5.0, "horizon": 8, "decode_tokens": 128, "columns_run": 2,
          "state_lanes": 64},
         {"kind": "decode", "t": 99.0, "horizon": 8, "decode_tokens": 512, "columns_run": 8,
          "state_lanes": 64},
         {"kind": "prefill", "t": 5.5, "horizon": 0, "decode_tokens": 0, "columns_run": 0}]


def test_swa_roofline_share_reads_the_ring_kernel_inside_decode_launches():
    read = catalog.layer_metric_reader("kernels.swa_decode_roofline_share").read
    got = read(ctx(trace=TRACE, trace_window=(4.0, 7.0), steps=STEPS))
    # 128 lane-columns, five window layers, a window of 128 entries of 5,120 B
    least = 128 * 5 * 128 * 5120 / 819e9
    assert abs(got - 100 * least / 0.005) < 1e-9 and 0 < got < 100
    assert read(ctx(steps=STEPS)) is None  # no trace
    no_kernel = {"devices": {"d": {"modules": [["jit_multi(7)", 0.0, 1.0]],
                                   "ops": [[PAGED, 0.3, 0.5]]}}, "host": []}
    assert read(ctx(trace=no_kernel, trace_window=(4.0, 7.0), steps=STEPS)) is None
    # the paged kernel's columns are counted from its own name and the full layers alone
    import sys
    sys.path.insert(0, os.path.join(ROOT, "benchmark", "layer_metrics"))
    from _common import columns_run
    assert columns_run(ctx(trace=TRACE, trace_window=(4.0, 7.0), steps=STEPS)) == 2


def test_window_recompute_share_reads_the_counters():
    read = catalog.layer_metric_reader("scheduler.window_recompute_share").read
    before = {"window_recomputed_tokens": 100, "computed_prompt_tokens": 1000}
    after = {"window_recomputed_tokens": 350, "computed_prompt_tokens": 6000}
    assert read({"loads_before": before, "loads_after": after}) == 5.0
    assert read({"loads_before": before, "loads_after": {**before,
                                                         "computed_prompt_tokens": 2000}}) == 0.0
    assert read({"loads_before": before, "loads_after": before}) is None
    # a program without window slots (the parent's, or another architecture's)
    assert read({"loads_before": {"computed_prompt_tokens": 0},
                 "loads_after": {"computed_prompt_tokens": 9}}) is None


def test_the_new_readers_give_nothing_for_the_three_older_cells():
    for older in OLDER:
        c = ctx(older, trace=TRACE, trace_window=(4.0, 7.0), steps=STEPS,
                loads_before={"computed_prompt_tokens": 0, "state_recomputed_tokens": 0},
                loads_after={"computed_prompt_tokens": 9, "state_recomputed_tokens": 3})
        for name in NEW:
            assert catalog.layer_metric_reader(name).read(c) is None, (older, name)


def test_the_new_files_are_new_and_the_entries_are_there():
    """By name and not by place: the next configuration is appended behind
    this one."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = {c["name"]: c for c in bench["configs"]}["mimo-v2-flash"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("mimo-v2-flash", "mixed", 1)
    assert config["file"] == "benchmark/configs/mimo-v2-flash.json"
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = metrics[name]
        assert m["workloads"] == [CELL]
        meta = catalog.layer_metric_reader(name).META
        assert (m["layer"], m["moves"], m["unit"]) == (meta["layer"], "output_tok_per_s", meta["unit"])
        assert meta["source"].startswith(m["source"])
    # the two readers generic over the counters list the latent model's cell alone
    for name in ("kernels.moe_decode_roofline_share", "runner.moe_held_pick_share"):
        assert CELL not in metrics[name]["workloads"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    listing = catalog.listing()
    assert "mimo_v2_flash" in listing["architectures"] and "mixed" in listing["traffic"]
    assert set(NEW) <= set(listing["layer_metrics"])

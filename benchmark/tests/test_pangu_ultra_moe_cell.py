"""The ``pangu_ultra_moe`` architecture, the ``openpangu-ultra-moe-718b``
configuration and the cell ``reason`` hold what ``test_architectures.py`` asks
of one: the harness resolves them by name, ``reference.check_engine`` holds the
drive to the file's own ``logits`` with the shared control and the drive's three
(the pages' rotary lanes zeroed, a page's latent from the other sequence, the
router cut to the experts held) all missing the tolerance, the costs give the
cut's sizes by hand, the three new readers read hand-made contexts and give
nothing for the two older cells, and the additions are new files and entries
of their own.  CPU."""

import json
import os

import catalog
import reference
from conftest import ROOT

CELL = "openpangu-ultra-moe-718b.reason"
CATALOG_ROW = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 16,
           "vocab_size": 19200}


def test_the_cell_resolves_and_the_configuration_is_the_rows_but_for_the_cut():
    bench = catalog.load_benchmark()
    cell = catalog.Cell(bench, CELL)
    assert cell.architecture.__name__.endswith("pangu_ultra_moe") and cell.chips == 1
    hf, conf = cell.hf_config, cell.config
    assert not set(hf) & {"architecture", "reduced", "published", "assumed", "rehearsal"}
    for key, want in CATALOG_ROW.items():
        assert hf[key] == REDUCED.get(key, want), key
    entry = cell.config_entry
    assert entry["reduced"] == conf["reduced"] == list(REDUCED)
    assert conf["published"] == {k: CATALOG_ROW[k] for k in REDUCED}
    # the router is never cut: its width stands beside the experts held
    assert (hf["router_num_experts"], hf["routed_expert_offset"]) == (256, 0)
    assert "sixteen chips share each layer" in conf["deployment"] and len(conf["assumed"]) >= 6
    assert cell.serve_args == ["--decode-horizon", "8"]
    t = cell.traffic
    assert (t["generator"], t["clients"], t["ramp_s"], t["pool_per_client"]) == \
        ("closed_loop", 64, 6, 24)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.7,
                                  "min": 128, "max": 2048}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.6,
                                  "min": 128, "max": 1024}
    names = {m["name"] for m in catalog.metrics_for(bench, CELL, "per_layer")}
    new = {"kernels.mla_decode_roofline_share", "kernels.moe_decode_roofline_share",
           "runner.moe_held_pick_share"}
    assert new <= names and "kernels.linattn_decode_roofline_share" not in names
    globals_ = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert globals_ <= names
    for older in ("qwen3-1.7b.eval", "olmo-hybrid-7b.gen"):
        assert not new & {m["name"] for m in catalog.metrics_for(bench, older, "per_layer")}


def test_the_program_loads_the_configuration_and_the_costs_are_the_hand_counts():
    from smg_tpu.models.config import ModelConfig
    from smg_tpu.models.pangu_moe import cache_lanes

    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    hf, arch = cell.hf_config, cell.architecture
    cfg = ModelConfig.from_hf_config(hf)
    assert (cfg.arch, cfg.num_layers, cfg.first_k_dense_replace) == ("pangu_ultra_moe", 5, 1)
    assert cfg.held_experts == (0, 16) and cfg.num_experts == 256 and cache_lanes(cfg) == 640
    attention = 7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256 + 128 * 128 * 7680
    expert = 3 * 7680 * 2048
    assert abs(attention - 196.6e6) < 0.1e6 and abs(expert - 47.19e6) < 0.01e6
    p = arch.param_count(hf)
    always = 5 * attention + 3 * 7680 * 18432 + 4 * (expert + 7680 * 256)
    assert p["always"] == always and p["routed"] == 4 * 16 * expert
    assert p["embed"] == p["lm_head"] == 19200 * 7680
    assert abs(p["total"] - 4.92e9) < 0.01e9
    # what every token passes here whatever the routing, and the routed experts' share of it
    assert abs(always - 1.605e9) < 0.005e9
    assert arch.latent_entry_bytes(hf) == 1152 and arch.kv_bytes_per_token(hf) == 5760
    assert arch.expert_bytes(hf) == 2 * expert and abs(arch.expert_bytes(hf) - 94.4e6) < 0.1e6
    assert arch.expert_flops_per_row(hf) == 6 * 7680 * 2048
    assert arch.mla_decode_flops_per_token(hf) == 2 * 128 * (576 + 512)
    assert arch.attention_layers(hf) == 5
    peak = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
    # a 64-lane column at 800 cached tokens a lane: 3.5 GB whatever the routing
    least = arch.decode_min_seconds(hf, 1, 64 * 800, 1, peak, 2)
    fixed = 2 * (always + 19200 * 7680)
    assert abs(fixed - 3.5e9) < 0.02e9
    assert abs(least - (fixed + 5760 * 64 * 800) / 819e9) < 1e-12
    flops = 2 * always * 1000 + 2 * 128 * (192 + 128) * 5 * 500500
    assert abs(arch.prefill_min_seconds(hf, 1000, 500500, 1, peak) - flops / 197e12) < 1e-15


def test_the_shared_verdict_holds_the_drive_and_every_control_misses():
    """The rehearsal's engine (tiny widths, float32), as ``run.py --rehearsal``
    builds it, through ``reference.check_engine``."""
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.models.config import ModelConfig
    from smg_tpu.tokenizer import MockTokenizer

    cell = catalog.Cell(catalog.load_benchmark(), CELL, rehearsal=True)
    model = ModelConfig.from_hf_config(cell.hf_config, dtype="float32")
    assert model.held_experts == (4, 4) and model.num_experts == 16
    engine = Engine(EngineConfig(
        model=model, dtype="float32",
        cache=CacheConfig(page_size=16, num_pages=256, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(max_seq_len=1024, max_prefill_tokens=256, decode_horizon=8)),
        tokenizer=MockTokenizer())
    check = reference.check_engine(engine, cell, 34, True)
    assert check["ok"] and check["worst"] < 1e-3
    assert set(check["errors"]) == {"xla", "pallas_interpret"}
    assert set(check["control_errors"]) == {
        f"{impl}{name}" for impl in ("xla", "pallas_interpret")
        for name in ("", ".rotary_lanes_zeroed", ".latent_of_other_sequence",
                     ".router_cut_to_held")}
    assert all(e > check["tolerance"] for e in check["control_errors"].values())


def ctx(cell=CELL, **kw):
    c = catalog.Cell(catalog.load_benchmark(), cell)
    return {"hf": c.hf_config, "costs": c.architecture, "chips": 1, "kv_dtype_bytes": 2,
            "device": {"kind": "TPU v5 lite"}, "trace": None, "trace_window": None,
            "requests": [], "steps": [], **kw}


MLA = "%smg.attn.decode.9 = bf16[64,128,512] custom-call(...)"
MOE = "%smg.moe.experts.5 = bf16[512,7680] custom-call(...)"
TRACE = {"devices": {"/device:TPU:0": {
    "modules": [["jit_multi(7)", 0.0, 1.0], ["jit_step(3)", 2.0, 1.0]],
    "ops": [["%while.1", 0.0, 1.0],  # encloses the rest: not a leaf
            *[[MLA, 0.01 * i, 0.002] for i in range(10)],  # two columns of five layers
            [MOE, 0.30, 0.05], [MOE, 0.40, 0.05],
            [MOE, 2.10, 0.30],  # the same kernel in a prefill launch: not counted
            ["%fusion.3", 0.50, 0.4]]}}, "host": []}
STEPS = [{"kind": "decode", "t": 5.0, "horizon": 8, "decode_tokens": 128, "columns_run": 2,
          "moe_picks_held": 64, "moe_experts_hit": 100},
         {"kind": "decode", "t": 99.0, "horizon": 8, "decode_tokens": 128, "columns_run": 8,
          "moe_picks_held": 64, "moe_experts_hit": 100},
         {"kind": "prefill", "t": 5.5, "horizon": 0, "decode_tokens": 0, "columns_run": 0}]
REQS = [{"first": 0.0, "done": 10.0, "prompt_tokens": 600, "output_tokens": 400}] * 64


def test_mla_roofline_share_reads_the_decode_kernel_inside_decode_launches():
    read = catalog.layer_metric_reader("kernels.mla_decode_roofline_share").read
    got = read(ctx(trace=TRACE, trace_window=(4.0, 7.0), steps=STEPS, requests=REQS))
    # two columns (ten kernel runs over five layers); 64 lanes holding 600 + 0.55 x 400 tokens
    lane_tokens = 2 * 64 * (600 + 0.55 * 400) * 5
    least = max(lane_tokens * 1152 / 819e9, lane_tokens * 2 * 128 * 1088 / 197e12)
    assert abs(got - 100 * least / 0.02) < 1e-9 and 0 < got < 100
    assert lane_tokens * 2 * 128 * 1088 / 197e12 > lane_tokens * 1152 / 819e9  # the ridge
    assert read(ctx(steps=STEPS, requests=REQS)) is None  # no trace
    no_kernel = {"devices": {"d": {"modules": [["jit_multi(7)", 0.0, 1.0]],
                                   "ops": [["%fusion.3", 0.3, 0.5]]}}, "host": []}
    assert read(ctx(trace=no_kernel, trace_window=(4.0, 7.0), steps=STEPS, requests=REQS)) is None


def test_moe_roofline_share_reads_the_ring_and_the_kernel_inside_decode_launches():
    read = catalog.layer_metric_reader("kernels.moe_decode_roofline_share").read
    got = read(ctx(trace=TRACE, trace_window=(4.0, 7.0), steps=STEPS))
    by_bytes, by_flops = 100 * 94371840 / 819e9, 64 * 6 * 7680 * 2048 / 197e12
    assert by_bytes > by_flops and abs(got - 100 * by_bytes / 0.10) < 1e-9 and 0 < got < 100
    # a program whose ring lacks the counters (the parent's): nothing
    bare = [{k: v for k, v in s.items() if not k.startswith("moe_")} for s in STEPS]
    assert read(ctx(trace=TRACE, trace_window=(4.0, 7.0), steps=bare)) is None
    assert read(ctx(steps=STEPS)) is None


def test_held_pick_share_reads_the_counters():
    read = catalog.layer_metric_reader("runner.moe_held_pick_share").read
    before = {"moe": {"picks": 1000, "picks_held": 60}}
    after = {"moe": {"picks": 17000, "picks_held": 1060}}
    assert read({"loads_before": before, "loads_after": after}) == 6.25
    assert read({"loads_before": {}, "loads_after": {}}) is None
    assert read({"loads_before": before, "loads_after": before}) is None


def test_the_new_readers_give_nothing_for_the_two_older_cells():
    for older in ("qwen3-1.7b.eval", "olmo-hybrid-7b.gen"):
        c = ctx(older, trace=TRACE, trace_window=(4.0, 7.0), steps=STEPS, requests=REQS,
                loads_before={"computed_prompt_tokens": 0}, loads_after={"computed_prompt_tokens": 9})
        for name in ("kernels.mla_decode_roofline_share", "kernels.moe_decode_roofline_share",
                     "runner.moe_held_pick_share"):
            assert catalog.layer_metric_reader(name).read(c) is None, (older, name)


def test_the_new_files_are_new_and_the_entries_are_there():
    """By name and not by place: the next configuration is appended behind
    this one (``test_olmo_hybrid_cell.py`` pins the end of the lists and
    fails since this cell was added; it is the accepted benchmark's file)."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = {c["name"]: c for c in bench["configs"]}["openpangu-ultra-moe-718b"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in ("kernels.mla_decode_roofline_share", "kernels.moe_decode_roofline_share",
                 "runner.moe_held_pick_share"):
        m = metrics[name]
        assert m["workloads"] == [CELL]
        meta = catalog.layer_metric_reader(name).META
        assert (m["layer"], m["moves"], m["unit"]) == (meta["layer"], "output_tok_per_s", meta["unit"])
        assert meta["source"].startswith(m["source"])
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    listing = catalog.listing()
    assert "pangu_ultra_moe" in listing["architectures"] and "reason" in listing["traffic"]
    assert "_kernel_time" not in listing["layer_metrics"]

"""The ``kimi_linear`` architecture and the ``kimi-linear-48b-a3b`` configuration
hold what ``test_architectures.py`` asks of one: the harness resolves them by
name, the cell's files are new files and appended entries only, the
configuration is the catalog row with four keys cut, ``reference.check_engine``
holds the drive to the file's own ``logits`` with the shared control and the
drive's six all missing the tolerance (and the state in bfloat16 a reading far
under it), the costs give the cut's sizes and shares under 100 % on a hand-made
trace, and the new readers give nothing where there is nothing to read.  CPU."""

import json
import os
import subprocess

import catalog
import reference
from conftest import ROOT

CELL = "kimi-linear-48b-a3b.reason"
NEW = ["kernels.kda_decode_roofline_share", "kernels.nope_mla_decode_roofline_share",
       "kernels.kda_moe_decode_roofline_share", "runner.kda_time_share",
       "runner.kda_moe_held_pick_share", "scheduler.kda_state_recompute_share"]
PUBLISHED = {"hidden_size": 2304, "num_attention_heads": 32, "qk_nope_head_dim": 128,
             "qk_rope_head_dim": 64, "v_head_dim": 128, "kv_lora_rank": 512, "q_lora_rank": None,
             "intermediate_size": 9216, "moe_intermediate_size": 1024, "router_num_experts": 256,
             "num_experts_per_token": 8, "num_shared_experts": 1,
             "moe_router_activation_func": "sigmoid", "routed_scaling_factor": 2.446,
             "first_k_dense_replace": 1, "mla_use_nope": True, "rms_norm_eps": 1e-05}
PEAK = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
CONTROLS = ("other_sequences_slot", "conv_tail_zeroed", "decay_averaged_over_channels",
            "shared_key_rotated", "held_experts_give_nothing", "selection_bias_dropped")


def test_the_cell_resolves_and_the_configuration_keeps_every_published_width():
    bench = catalog.load_benchmark()
    cell = catalog.Cell(bench, CELL)
    assert cell.architecture.__name__.endswith("kimi_linear") and cell.chips == 1
    hf, conf = cell.hf_config, cell.config
    assert not set(hf) & {"architecture", "reduced", "published", "assumed", "rehearsal"}
    for key, want in PUBLISHED.items():
        assert hf[key] == want, key
    lin = hf["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["kda_layers"] == [1, 2, 3, 5, 6, 7, 9, 10, 11] and lin["full_attn_layers"] == [4, 8, 12]
    assert (hf["model_type"], hf["num_hidden_layers"], hf["num_experts"], hf["vocab_size"]) == (
        "kimi_linear", 12, 32, 20480)
    entry = cell.config_entry
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "linear_attn_config", "num_experts", "vocab_size"]
    assert entry["why"].startswith("drawn by the driver")
    pub = conf["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"], pub["vocab_size"]) == (27, 256, 163840)
    assert len(pub["linear_attn_config"]["kda_layers"]) == 20 \
        and pub["linear_attn_config"]["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert "8 TPU v5e chips share each layer" in conf["deployment"] \
        and "pipeline stages" in conf["deployment"] and len(conf["assumed"]) >= 10
    assert cell.serve_args == ["--decode-horizon", "8"]
    assert cell.traffic["generator"] == "closed_loop" and cell.traffic["clients"] == 64
    names = {m["name"] for m in catalog.metrics_for(bench, CELL, "per_layer")}
    assert set(NEW) <= names and "kernels.linattn_decode_roofline_share" not in names
    old = {m["name"] for m in catalog.metrics_for(bench, bench["workloads"][0]["name"], "per_layer")}
    assert not set(NEW) & old


def test_every_number_of_the_catalog_row_is_in_the_file_under_its_own_key():
    row_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(row_file):
        import pytest

        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(l) for l in open(row_file)
               if '"name": "Kimi-Linear-48B-A3B-Instruct"' in l)
    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    differ = [k for k, v in row["config"].items() if cell.config.get(k, "absent") != v]
    assert sorted(differ) == sorted(cell.config["reduced"])
    # the nested group is the row's but for its two lists, cut to layers 1-12
    ours, theirs = cell.config["linear_attn_config"], row["config"]["linear_attn_config"]
    assert {k: v for k, v in ours.items() if not k.endswith("_layers")} == {
        k: v for k, v in theirs.items() if not k.endswith("_layers")}
    for k in ("kda_layers", "full_attn_layers"):
        assert ours[k] == [l for l in theirs[k] if l <= 12]
    assert cell.config["published"]["linear_attn_config"] == theirs
    assert cell.config_entry["source"] == row["source_url"]


def test_the_program_loads_the_configuration_and_the_costs_give_the_cuts_sizes():
    from smg_tpu.models.config import ModelConfig
    from smg_tpu.models.kimi_linear import init_params, state_shapes

    import jax

    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    hf, arch = cell.hf_config, cell.architecture
    cfg = ModelConfig.from_hf_config(hf)
    assert (cfg.arch, cfg.num_layers, cfg.num_cache_layers, cfg.rope_theta) == (
        "kimi_linear", 12, 3, 0.0)
    assert cfg.held_experts == (0, 32) and cfg.num_experts == 256
    p = arch.param_count(hf)
    assert abs(p["total"] - 3.177e9) < 0.002e9 and p["embed"] == p["lm_head"] == 20480 * 2304
    assert p["routed"] == 11 * 32 * 3 * 2304 * 1024
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert abs(held - p["total"]) < 0.002 * p["total"]  # norms, taps, per-head and per-channel vectors
    assert arch.kv_bytes_per_token(hf, 2) == 3 * 576 * 2 and arch.latent_entry_bytes(hf, 2) == 1152
    assert arch.kda_layers(hf) == 9 and arch.attention_layers(hf) == 3
    assert arch.expert_bytes(hf, 2) == 3 * 2304 * 1024 * 2
    s_shape, c_shape = state_shapes(cfg, 73)
    lane = arch.kda_lane_bytes(hf, 2)
    assert lane == 2 * 32 * 128 * 128 * 4 + 2 * 3 * 12288 * 2 + 4 * (12288 + 4096 + 32)
    slot = s_shape[2] * s_shape[3] * 4 + c_shape[2] * 2
    assert slot == 2_097_152 + 73_728 and abs(9 * slot - 19.54e6) < 0.01e6 and lane > 2 * slot
    # a column reads what every token passes and the three latent layers' entries
    least = arch.decode_min_seconds(hf, 1, 64 * 900, 1, PEAK, 2)
    assert abs(least - (2 * (p["always"] + p["lm_head"]) + 3 * 1152 * 57600) / 819e9) < 1e-9
    assert 1.2e9 < 2 * (p["always"] + p["lm_head"]) < 1.35e9  # the issue's 1.28 GB
    kda = 32 * (2 * 128 * 64 + 64 * 64 / 3 + 2 * 64 * 128 + 128 * 64 + 6 * 128 * 128)
    flops = 2 * p["always"] * 1000 + 2 * 32 * 320 * 3 * 500500 + kda * 9 * 1000
    assert abs(arch.prefill_min_seconds(hf, 1000, 500500, 1, PEAK) - flops / 197e12) < 1e-12


def rehearsal_engine():
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.models.config import ModelConfig
    from smg_tpu.tokenizer import MockTokenizer

    cell = catalog.Cell(catalog.load_benchmark(), CELL, rehearsal=True)
    model = ModelConfig.from_hf_config(cell.hf_config, dtype="float32")
    return cell, Engine(EngineConfig(
        model=model, dtype="float32",
        cache=CacheConfig(page_size=16, num_pages=256, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(max_seq_len=1024, max_prefill_tokens=256, decode_horizon=8)),
        tokenizer=MockTokenizer())


def test_the_shared_verdict_holds_the_drive_and_every_control_misses():
    """The rehearsal's engine (eight layers of tiny widths, float32), as
    ``run.py --rehearsal`` builds it, through ``reference.check_engine``: the
    drive's six controls and the wrong page miss the tolerance under both
    implementations; the state rounded to bfloat16 is a reading far under it."""
    import types

    cell, engine = rehearsal_engine()
    arch = cell.architecture
    drives = []

    class Kept(arch.Drive):
        def __init__(self, *a):
            super().__init__(*a)
            drives.append(self)

    cell.architecture = types.SimpleNamespace(**{**vars(arch), "drive": Kept})
    check = reference.check_engine(engine, cell, 47, True)
    assert check["ok"] and check["worst"] < 1e-3
    assert set(check["errors"]) == {"xla", "pallas_interpret"}
    assert set(check["control_errors"]) == {
        f"{impl}{name}" for impl in ("xla", "pallas_interpret")
        for name in ("", *(f".{c}" for c in CONTROLS))}
    assert all(e > check["tolerance"] for e in check["control_errors"].values())
    assert len(drives) == 2 and all(0 < d.rounded_state_reading < 0.05 for d in drives)
    loads = engine.loads()
    assert loads["state_slots_total"] > 0 and loads["latent_cache"]["entry_bytes_laid_out"] == 512
    assert engine.runner.v_cache.size == 0 and loads["kda_decode"] == "xla"


def ctx(**kw):
    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    return {"hf": cell.hf_config, "costs": cell.architecture, "chips": 1, "kv_dtype_bytes": 2,
            "device": {"kind": "TPU v5 lite"}, "trace": None, "trace_window": None, **kw}


KDA = "%smg.kda.decode.7 = (f32[64,1,4096], f32[9,73,128,4096]) custom-call(...)"
OLMO = "%smg.linattn.decode.2 = (f32[64,1,4096], f32[9,73,128,4096]) custom-call(...)"
EXPERTS = "%smg.moe.experts.3 = bf16[512,1024] custom-call(...)"
ATTN = "%smg.attn.decode.5 = bf16[64,32,512] custom-call(...)"
TRACE = {"devices": {"/device:TPU:0": {
    "modules": [["jit_multi(123)", 0.0, 1.0], ["jit_step(9)", 2.0, 0.5]],
    "ops": [["%while.1", 0.0, 1.0],  # encloses the rest: not a leaf
            [KDA, 0.10, 0.03], [KDA, 0.20, 0.03], [OLMO, 0.25, 0.01],
            [EXPERTS, 0.30, 0.05], [EXPERTS, 0.40, 0.05],
            *([ATTN, 0.5 + 0.01 * i, 0.004] for i in range(24)),  # 8 columns x 3 layers
            ["%fusion.3", 0.80, 0.1], [EXPERTS, 2.10, 0.2]]}}, "host": []}
STEPS = [{"kind": "decode", "t": 5.0, "horizon": 8, "decode_tokens": 512, "state_lanes": 64,
          "moe_experts_hit": 2400, "moe_picks_held": 5600},
         {"kind": "decode", "t": 99.0, "horizon": 8, "decode_tokens": 512, "state_lanes": 64,
          "moe_experts_hit": 2400, "moe_picks_held": 5600},
         {"kind": "prefill", "t": 5.5, "horizon": 0, "decode_tokens": 0, "state_lanes": 0}]
REQUESTS = [{"first": 3.0, "done": 9.0, "prompt_tokens": 500, "output_tokens": 600, "due": 1.0}
            for _ in range(64)]


def test_the_roofline_shares_read_the_kernels_and_stay_under_a_hundred():
    window = {"trace": TRACE, "trace_window": (4.0, 7.0), "steps": STEPS, "requests": REQUESTS}
    kda = catalog.layer_metric_reader("kernels.kda_decode_roofline_share").read
    arch = catalog.Cell(catalog.load_benchmark(), CELL).architecture
    hf = ctx()["hf"]
    least = 512 * 9 * arch.kda_lane_bytes(hf, 2) / 819e9
    got = kda(ctx(**window))
    # the other rule's kernel is not in the time
    assert abs(got - 100 * least / 0.06) < 1e-9 and 0 < got < 100
    experts = catalog.layer_metric_reader("kernels.kda_moe_decode_roofline_share").read
    got = experts(ctx(**window))
    by_bytes = 2400 * arch.expert_bytes(hf, 2) / 819e9
    assert abs(got - 100 * by_bytes / 0.10) < 1e-9 and 0 < got < 100  # the prefill's is not in it
    share = catalog.layer_metric_reader("runner.kda_time_share").read
    assert abs(share(ctx(**window)) - 100 * 0.06 / 1.0) < 1e-9
    latent = catalog.layer_metric_reader("kernels.nope_mla_decode_roofline_share").read
    got = latent(ctx(**window))
    # 8 columns x 64 lanes x ~800 live tokens x 3 layers x 1,152 B over 0.096 s
    assert got is not None and 0 < got < 100
    generic = catalog.layer_metric_reader("kernels.decode_roofline_share").read(ctx(**window))
    assert generic is not None and 0 < generic < 100
    # nothing to read: no trace, a ring without state lanes, no kernel, another architecture
    for read in (kda, experts, share, latent):
        assert read(ctx(steps=STEPS, requests=REQUESTS)) is None
        other = ctx(**window)
        other["hf"] = {**other["hf"], "model_type": "olmo_hybrid"}
        other["costs"] = catalog.architecture("llama")
        assert read(other) is None
    bare = [{k: v for k, v in s.items() if k not in ("state_lanes", "moe_experts_hit")}
            for s in STEPS]
    assert kda(ctx(**{**window, "steps": bare})) is None
    assert experts(ctx(**{**window, "steps": bare})) is None
    no_kernel = {"devices": {"d": {"modules": [["jit_multi(1)", 0.0, 1.0]],
                                   "ops": [["%fusion.3", 0.3, 0.5]]}}, "host": []}
    for read in (kda, experts, share, latent):
        assert read(ctx(**{**window, "trace": no_kernel})) is None


def test_the_counter_readers_read_loads_and_nothing_on_a_program_without_them():
    held = catalog.layer_metric_reader("runner.kda_moe_held_pick_share").read
    before = {"moe": {"picks": 1000, "picks_held": 120}, "computed_prompt_tokens": 1000,
              "state_recomputed_tokens": 50}
    after = {"moe": {"picks": 5000, "picks_held": 620}, "computed_prompt_tokens": 5000,
             "state_recomputed_tokens": 250}
    hf = ctx()["hf"]
    assert held({"hf": hf, "loads_before": before, "loads_after": after}) == 12.5
    again = catalog.layer_metric_reader("scheduler.kda_state_recompute_share").read
    assert again({"hf": hf, "loads_before": before, "loads_after": after}) == 5.0
    parent = {"computed_prompt_tokens": 9}
    for read in (held, again):
        assert read({"hf": hf, "loads_before": parent, "loads_after": parent}) is None
        assert read({"hf": {"model_type": "llama"}, "loads_before": before,
                     "loads_after": after}) is None


def test_the_new_files_are_new_and_the_entries_are_appended():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["per_layer"]]
    at = metrics.index(NEW[0])
    assert metrics[at:at + len(NEW)] == NEW  # appended together, in the issue's order
    for m in bench["per_layer"][at:at + len(NEW)]:
        assert m["workloads"] == [CELL] and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        meta = catalog.layer_metric_reader(m["name"]).META
        assert (m["layer"], m["moves"], m["unit"]) == (meta["layer"], "output_tok_per_s", meta["unit"])
        assert meta["source"].startswith(m["source"])
    why = bench["workloads"][cells.index(CELL)]["why"]
    assert len(why) <= 200 and "state" in why and "1/8" in why
    # against the commit before the cell: nothing that was there is edited or gone
    git = lambda *a: subprocess.run(["git", "-C", ROOT, *a], capture_output=True, text=True)
    if git("rev-parse", "HEAD").returncode:
        return
    back = 0
    parent = json.loads(git("show", "HEAD:BENCHMARK.json").stdout)
    while CELL in [w["name"] for w in parent["workloads"]]:  # committed: go behind the PR
        back += 1
        parent = json.loads(git("show", f"HEAD~{back}:BENCHMARK.json").stdout)
    for key in ("command", "paths", "run_seconds", "end_to_end", "trace_in_run"):
        assert bench[key] == parent[key]
    for key in ("configs", "workloads", "per_layer"):
        assert bench[key][: len(parent[key])] == parent[key]
    assert configs.index("kimi-linear-48b-a3b") == len(parent["configs"])
    assert cells.index(CELL) == len(parent["workloads"]) and at == len(parent["per_layer"])
    if back == 0:
        changed = git("diff", "--name-status", "HEAD", "--", "benchmark").stdout.splitlines()
        assert all(line.startswith("A") for line in changed), changed

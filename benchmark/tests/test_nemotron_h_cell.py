"""The ``nemotron_h`` architecture and the ``nemotron-3-super-120b-a12b``
configuration hold what ``test_architectures.py`` asks of one: the harness
resolves them by name, the cell's files are new files and appended entries
only, ``reference.check_engine`` holds the drive to the file's own ``logits``
with the shared control and the drive's four all missing the tolerance (and
the state in bfloat16, a reading, not missing it), the costs give the cut's
sizes and shares under 100 % on a hand-made trace, and the new readers give
nothing where there is nothing to read.  CPU."""

import json
import os
import subprocess

import catalog
import reference
from conftest import ROOT

CELL = "nemotron-3-super-120b-a12b.reason"
NEW = ["kernels.ssm_decode_roofline_share", "kernels.latent_moe_decode_roofline_share",
       "runner.ssm_time_share", "runner.latent_moe_held_pick_share",
       "scheduler.ssm_state_recompute_share"]
PUBLISHED = {"hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64,
             "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4, "chunk_size": 128,
             "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
             "moe_latent_size": 1024, "moe_intermediate_size": 2688,
             "moe_shared_expert_intermediate_size": 5376, "router_num_experts": 512,
             "num_experts_per_tok": 22, "routed_scaling_factor": 5, "norm_eps": 1e-05}
PEAK = {"bytes_per_s": 819e9, "flops_per_s": 197e12}


def test_the_cell_resolves_and_the_configuration_keeps_every_published_width():
    bench = catalog.load_benchmark()
    cell = catalog.Cell(bench, CELL)
    assert cell.architecture.__name__.endswith("nemotron_h") and cell.chips == 1
    hf, conf = cell.hf_config, cell.config
    assert not set(hf) & {"architecture", "reduced", "published", "assumed", "rehearsal"}
    for key, want in PUBLISHED.items():
        assert hf[key] == want, key
    assert hf["model_type"] == "nemotron_h" and hf["hybrid_override_pattern"] == "MEMEMEM*EME"
    assert (hf["num_hidden_layers"], hf["n_routed_experts"], hf["vocab_size"],
            hf["num_nextn_predict_layers"]) == (11, 128, 32768, 0)
    entry = cell.config_entry
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    pub = conf["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"], pub["vocab_size"]) == (
        88, 512, 131072)
    assert pub["hybrid_override_pattern"].startswith("MEMEMEM*EMEMEMEM*E") \
        and "1 next-token module" in pub["num_nextn_predict_layers"]
    assert "4 TPU v5e chips share each layer" in conf["deployment"] \
        and "8 pipeline stages" in conf["deployment"] and len(conf["assumed"]) >= 8
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
               if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in l)
    conf = catalog.Cell(catalog.load_benchmark(), CELL).config
    differ = [k for k, v in row["config"].items() if conf.get(k) != v]
    assert sorted(differ) == sorted(conf["reduced"])
    assert catalog.Cell(catalog.load_benchmark(), CELL).config_entry["source"] == row["source_url"]


def test_the_program_loads_the_configuration_and_the_costs_give_the_cuts_sizes():
    from smg_tpu.models.config import ModelConfig
    from smg_tpu.models.nemotron_h import init_params, state_shapes

    import jax

    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    hf, arch = cell.hf_config, cell.architecture
    cfg = ModelConfig.from_hf_config(hf)
    assert (cfg.arch, cfg.num_layers, cfg.num_cache_layers, cfg.rope_theta) == (
        "nemotron_h", 11, 1, 0.0)
    p = arch.param_count(hf)
    assert abs(p["total"] - 4.648e9) < 0.001e9 and p["embed"] == p["lm_head"] == 32768 * 4096
    assert p["routed"] == 5 * 128 * 2 * 1024 * 2688
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert abs(held - p["total"]) < 0.002 * p["total"]  # norms, taps, per-head vectors, biases
    assert arch.kv_bytes_per_token(hf, 2) == 2 * 2 * 128 * 2  # 1 KB a token
    assert arch.ssm_layers(hf) == 5 and arch.attention_layers(hf) == 1
    assert arch.expert_bytes(hf, 2) == 2 * 1024 * 2688 * 2
    s_shape, c_shape = state_shapes(cfg, 73)
    lane = arch.ssm_lane_bytes(hf, 2)
    assert lane == 2 * 128 * 64 * 128 * 4 + 2 * 3 * 10240 * 2 + 4 * (10240 + 128)
    slot = (s_shape[2] * s_shape[3] * 4 + c_shape[2] * 2)
    assert abs(5 * slot - 21.3e6) < 0.1e6 and lane > 2 * slot
    # a column reads what every token passes and the one attention layer's keys and values
    least = arch.decode_min_seconds(hf, 1, 64 * 1000, 1, PEAK, 2)
    assert abs(least - (2 * (p["always"] + p["lm_head"]) + 1024 * 64000) / 819e9) < 1e-9
    scan = 8 * 128 * 128 + 128 * 64 * 128 + 4 * 128 * 64 * 128
    flops = 2 * p["always"] * 1000 + 4 * 32 * 128 * 500500 + scan * 5 * 1000
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
    """The rehearsal's engine (seven layers of tiny widths, float32), as
    ``run.py --rehearsal`` builds it, through ``reference.check_engine``: the
    drive's five controls and the wrong page miss the tolerance under both
    implementations.  The state in bfloat16 is among them by the drive's hold
    on the state and not by the logits: a float32 state has next to none of
    its elements on bfloat16's grid, the rounded one all of them, and logits
    decoded from the rounded state itself stay far under the tolerance."""
    import types

    import numpy as np

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
        for name in ("", ".other_sequences_slot", ".conv_tail_zeroed",
                     ".held_experts_give_nothing", ".selection_bias_dropped",
                     ".state_in_bfloat16")}
    assert all(e > check["tolerance"] for e in check["control_errors"].values())
    for d in drives:
        # four sound columns, the wrong page, four controls on a sound state;
        # then the rounded state, both lanes of it
        *sound, rounded = d.coarse_shares
        assert len(sound) == 9 and max(max(c) for c in sound) < 1e-3
        assert rounded == [1.0, 1.0]

    # the same rounded state without the hold: the logits do not hear it
    monkey = arch.STATE_COARSE_LIMIT
    arch.STATE_COARSE_LIMIT = 2.0
    try:
        unheld = reference.check_engine(engine, cell, 47, True)["control_errors"]
    finally:
        arch.STATE_COARSE_LIMIT = monkey
    assert all(0 < v < 0.01 for k, v in unheld.items() if k.endswith("state_in_bfloat16"))
    assert np.isfinite(list(unheld.values())).all()


def ctx(**kw):
    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    return {"hf": cell.hf_config, "costs": cell.architecture, "chips": 1, "kv_dtype_bytes": 2,
            "device": {"kind": "TPU v5 lite"}, "trace": None, "trace_window": None, **kw}


SSM = "%smg.ssm.decode.7 = (f32[64,1,8192], f32[5,73,128,8192]) custom-call(...)"
EXPERTS = "%smg.moe.experts.3 = bf16[1408,2688] custom-call(...)"
TRACE = {"devices": {"/device:TPU:0": {
    "modules": [["jit_multi(123)", 0.0, 1.0], ["jit_step(9)", 2.0, 0.5]],
    "ops": [["%while.1", 0.0, 1.0],  # encloses the rest: not a leaf
            [SSM, 0.10, 0.02], [SSM, 0.20, 0.02], [EXPERTS, 0.30, 0.05], [EXPERTS, 0.40, 0.05],
            ["%fusion.3", 0.50, 0.3], [EXPERTS, 2.10, 0.2]]}}, "host": []}
STEPS = [{"kind": "decode", "t": 5.0, "horizon": 8, "decode_tokens": 512, "state_lanes": 64,
          "moe_experts_hit": 4800, "moe_picks_held": 14000},
         {"kind": "decode", "t": 99.0, "horizon": 8, "decode_tokens": 512, "state_lanes": 64,
          "moe_experts_hit": 4800, "moe_picks_held": 14000},
         {"kind": "prefill", "t": 5.5, "horizon": 0, "decode_tokens": 0, "state_lanes": 0}]


def test_the_roofline_shares_read_the_kernels_and_stay_under_a_hundred():
    window = {"trace": TRACE, "trace_window": (4.0, 7.0), "steps": STEPS}
    ssm = catalog.layer_metric_reader("kernels.ssm_decode_roofline_share").read
    arch = catalog.Cell(catalog.load_benchmark(), CELL).architecture
    hf = ctx()["hf"]
    least = 512 * 5 * arch.ssm_lane_bytes(hf, 2) / 819e9
    got = ssm(ctx(**window))
    assert abs(got - 100 * least / 0.04) < 1e-9 and 0 < got < 100
    experts = catalog.layer_metric_reader("kernels.latent_moe_decode_roofline_share").read
    got = experts(ctx(**window))
    by_bytes = 4800 * arch.expert_bytes(hf, 2) / 819e9
    assert abs(got - 100 * by_bytes / 0.10) < 1e-9 and 0 < got < 100  # the prefill's is not in it
    share = catalog.layer_metric_reader("runner.ssm_time_share").read
    assert abs(share(ctx(**window)) - 100 * 0.04 / 1.0) < 1e-9
    # nothing to read: no trace, a ring without state lanes, no kernel, another architecture
    for read in (ssm, experts, share):
        assert read(ctx(steps=STEPS)) is None
        other = ctx(**window)
        other["hf"] = {**other["hf"], "model_type": "olmo_hybrid"}
        other["costs"] = catalog.architecture("llama")
        assert read(other) is None
    bare = [{k: v for k, v in s.items() if k not in ("state_lanes", "moe_experts_hit")}
            for s in STEPS]
    assert ssm(ctx(**{**window, "steps": bare})) is None
    assert experts(ctx(**{**window, "steps": bare})) is None
    no_kernel = {"devices": {"d": {"modules": [["jit_multi(1)", 0.0, 1.0]],
                                   "ops": [["%fusion.3", 0.3, 0.5]]}}, "host": []}
    for read in (ssm, experts, share):
        assert read(ctx(**{**window, "trace": no_kernel})) is None


def test_the_counter_readers_read_loads_and_nothing_on_a_program_without_them():
    held = catalog.layer_metric_reader("runner.latent_moe_held_pick_share").read
    before = {"moe": {"picks": 1000, "picks_held": 240}, "computed_prompt_tokens": 1000,
              "state_recomputed_tokens": 50}
    after = {"moe": {"picks": 5000, "picks_held": 1240}, "computed_prompt_tokens": 5000,
             "state_recomputed_tokens": 250}
    hf = ctx()["hf"]
    assert held({"hf": hf, "loads_before": before, "loads_after": after}) == 25.0
    again = catalog.layer_metric_reader("scheduler.ssm_state_recompute_share").read
    assert again({"hf": hf, "loads_before": before, "loads_after": after}) == 5.0
    parent = {"computed_prompt_tokens": 9}
    for read in (held, again):
        assert read({"hf": hf, "loads_before": parent, "loads_after": parent}) is None
        assert read({"hf": {"model_type": "llama"}, "loads_before": before,
                     "loads_after": after}) is None


def test_the_new_files_are_new_and_the_entries_are_appended():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert [c["name"] for c in bench["configs"]][-1] == "nemotron-3-super-120b-a12b"
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert [m["name"] for m in bench["per_layer"]][-5:] == NEW
    for m in bench["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        meta = catalog.layer_metric_reader(m["name"]).META
        assert (m["layer"], m["moves"], m["unit"]) == (meta["layer"], "output_tok_per_s", meta["unit"])
        assert meta["source"].startswith(m["source"])
    why = bench["workloads"][-1]["why"]
    assert len(why) <= 200 and "state" in why and "1/4" in why
    # against the parent commit: nothing that was there is edited or gone
    git = lambda *a: subprocess.run(["git", "-C", ROOT, *a], capture_output=True, text=True)
    if git("rev-parse", "HEAD").returncode:
        return
    parent = json.loads(git("show", "HEAD:BENCHMARK.json").stdout)
    if parent["workloads"][-1]["name"] == CELL:  # the PR is committed: its own parent
        parent = json.loads(git("show", "HEAD~1:BENCHMARK.json").stdout)
    for key in ("command", "paths", "run_seconds", "end_to_end", "trace_in_run"):
        assert bench[key] == parent[key]
    for key in ("configs", "workloads", "per_layer"):
        assert bench[key][: len(parent[key])] == parent[key]
    changed = git("diff", "--name-status", "HEAD", "--", "benchmark").stdout.splitlines()
    assert all(line.startswith("A") for line in changed), changed

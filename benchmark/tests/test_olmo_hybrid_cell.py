"""The ``olmo_hybrid`` architecture and the ``olmo-hybrid-7b`` configuration
hold what ``test_architectures.py`` asks of one: the harness resolves them by
name, ``reference.check_engine`` holds the drive to the file's own ``logits``
with the shared control and the drive's two (a sequence decoding from another
sequence's slot, a zeroed slot) all missing the tolerance, the costs give the
cut's sizes, and both new readers read a hand-made context and give nothing
where there is nothing to read.  CPU."""

import json
import os

import catalog
import reference
from conftest import ROOT

CELL = "olmo-hybrid-7b.gen"
PUBLISHED = {"hidden_size": 3840, "intermediate_size": 11008, "vocab_size": 100352,
             "num_attention_heads": 30, "num_key_value_heads": 30, "linear_num_key_heads": 30,
             "linear_num_value_heads": 30, "linear_key_head_dim": 96,
             "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
             "max_position_embeddings": 65536, "rms_norm_eps": 1e-06}


def test_the_cell_resolves_and_the_configuration_is_the_published_one_cut_in_depth():
    bench = catalog.load_benchmark()
    cell = catalog.Cell(bench, CELL)
    assert cell.architecture.__name__.endswith("olmo_hybrid") and cell.chips == 1
    assert catalog.Cell(bench, bench["workloads"][0]["name"]).architecture.__name__.endswith("llama")
    hf, conf = cell.hf_config, cell.config
    assert not set(hf) & {"architecture", "reduced", "published", "assumed", "rehearsal"}
    for key, want in PUBLISHED.items():
        assert hf[key] == want, key
    assert hf["model_type"] == "olmo_hybrid" and hf["rope_parameters"] == {"rope_theta": None}
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert hf["num_hidden_layers"] == 16 and hf["layer_types"] == period * 4
    entry = cell.config_entry
    assert entry["reduced"] == conf["reduced"] and "num_hidden_layers" in entry["reduced"]
    assert conf["published"]["num_hidden_layers"] == 32 and len(conf["assumed"]) >= 4
    assert cell.serve_args == ["--decode-horizon", "8"]
    assert cell.traffic["generator"] == "closed_loop" and cell.traffic["clients"] == 16
    names = [m["name"] for m in catalog.metrics_for(bench, CELL, "per_layer")]
    assert {"kernels.linattn_decode_roofline_share", "scheduler.state_recompute_share",
            "kernels.decode_roofline_share", "kernels.prefill_roofline_share"} <= set(names)
    old = [m["name"] for m in catalog.metrics_for(bench, bench["workloads"][0]["name"], "per_layer")]
    assert "kernels.linattn_decode_roofline_share" not in old


def test_the_program_loads_the_configuration_and_the_costs_give_the_cuts_sizes():
    from smg_tpu.models.config import ModelConfig

    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    hf, arch = cell.hf_config, cell.architecture
    cfg = ModelConfig.from_hf_config(hf)
    assert (cfg.arch, cfg.num_layers, cfg.num_cache_layers, cfg.rope_theta) == ("olmo_hybrid", 16, 4, 0.0)
    p = arch.param_count(hf)
    assert abs(p["total"] - 4.10e9) < 0.01e9 and p["embed"] == p["lm_head"] == 100352 * 3840
    assert abs(p["layers"] / 4 - 832.5e6) < 0.2e6  # a period
    assert arch.kv_bytes_per_token(hf, 2) == 61440
    assert arch.linattn_state_bytes(hf) == 30 * 192 * 96 * 4 and arch.linear_layers(hf) == 12
    # what the program lays out for a slot is what the reader counts, and the convolution's tail
    from smg_tpu.models.olmo_hybrid import state_shapes

    s_shape, c_shape = state_shapes(cfg, 73)
    assert s_shape == (12, 73, 96, 30 * 192) and c_shape == (12, 73, 3 * 11520)
    per_slot = 12 * (30 * 192 * 96 * 4 + 3 * 11520 * 2)
    assert abs(per_slot - 27.4e6) < 0.1e6
    peak = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
    # a column reads the matmul weights once and the 4 full layers' keys and values
    least = arch.decode_min_seconds(hf, 1, 16 * 1400, 1, peak, 2)
    assert abs(least - (2 * p["matmul"] + 61440 * 16 * 1400) / 819e9) < 1e-9
    flops = 2 * p["layers"] * 1000 + 4 * 30 * 128 * 4 * 500500 + 4 * 30 * 96 * 192 * 12 * 1000
    assert abs(arch.prefill_min_seconds(hf, 1000, 500500, 1, peak) - flops / 197e12) < 1e-12


def test_the_shared_verdict_holds_the_drive_and_every_control_misses():
    """The rehearsal's engine (two periods of tiny widths, float32), as
    ``run.py --rehearsal`` builds it, through ``reference.check_engine``."""
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.models.config import ModelConfig
    from smg_tpu.tokenizer import MockTokenizer

    cell = catalog.Cell(catalog.load_benchmark(), CELL, rehearsal=True)
    model = ModelConfig.from_hf_config(cell.hf_config, dtype="float32")
    engine = Engine(EngineConfig(
        model=model, dtype="float32",
        cache=CacheConfig(page_size=16, num_pages=256, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(max_seq_len=1024, max_prefill_tokens=256, decode_horizon=8)),
        tokenizer=MockTokenizer())
    check = reference.check_engine(engine, cell, 29, True)
    assert check["ok"] and check["worst"] < 1e-3
    assert set(check["errors"]) == {"xla", "pallas_interpret"}
    assert set(check["control_errors"]) == {
        f"{impl}{name}" for impl in ("xla", "pallas_interpret")
        for name in ("", ".other_sequences_slot", ".zeroed_slot")}
    assert all(e > check["tolerance"] for e in check["control_errors"].values())


def ctx(**kw):
    cell = catalog.Cell(catalog.load_benchmark(), CELL)
    return {"hf": cell.hf_config, "costs": cell.architecture, "chips": 1,
            "device": {"kind": "TPU v5 lite"}, "trace": None, "trace_window": None, **kw}


def test_linattn_roofline_share_reads_the_kernels_operations_and_the_ring():
    read = catalog.layer_metric_reader("kernels.linattn_decode_roofline_share").read
    op = "%smg.linattn.decode.7 = (f32[16,1,5760], f32[12,73,96,5760]) custom-call(...)"
    trace = {"devices": {"/device:TPU:0": {"modules": [], "ops": [
        ["%while.1", 0.0, 1.0],                # encloses the rest: not a leaf
        [op, 0.10, 0.02], [op, 0.20, 0.02], ["%fusion.3", 0.30, 0.5]]}}, "host": []}
    steps = [{"kind": "decode", "t": 5.0, "horizon": 8, "decode_tokens": 128, "state_lanes": 16},
             {"kind": "decode", "t": 6.0, "horizon": 8, "decode_tokens": 64, "state_lanes": 8},
             {"kind": "decode", "t": 99.0, "horizon": 8, "decode_tokens": 128, "state_lanes": 16},
             {"kind": "prefill", "t": 5.5, "horizon": 0, "decode_tokens": 0, "state_lanes": 0}]
    got = read(ctx(trace=trace, trace_window=(4.0, 7.0), steps=steps))
    least = 192 * 12 * 2 * (30 * 192 * 96 * 4) / 819e9
    assert abs(got - 100 * least / 0.04) < 1e-9 and 0 < got < 100
    # nothing to read: no trace, a ring without state lanes (the parent's), another architecture
    assert read(ctx(steps=steps)) is None
    bare = [{k: v for k, v in s.items() if k != "state_lanes"} for s in steps]
    assert read(ctx(trace=trace, trace_window=(4.0, 7.0), steps=bare)) is None
    no_kernel = {"devices": {"d": {"modules": [], "ops": [["%fusion.3", 0.3, 0.5]]}}, "host": []}
    assert read(ctx(trace=no_kernel, trace_window=(4.0, 7.0), steps=steps)) is None
    llama = ctx(trace=trace, trace_window=(4.0, 7.0), steps=steps)
    llama["costs"] = catalog.architecture("llama")
    assert read(llama) is None


def test_state_recompute_share_reads_the_counters_and_nothing_on_a_program_without_them():
    read = catalog.layer_metric_reader("scheduler.state_recompute_share").read
    before = {"computed_prompt_tokens": 1000, "state_recomputed_tokens": 50}
    after = {"computed_prompt_tokens": 5000, "state_recomputed_tokens": 250}
    assert read({"loads_before": before, "loads_after": after}) == 5.0
    assert read({"loads_before": before, "loads_after": {**after, "state_recomputed_tokens": 50}}) == 0.0
    assert read({"loads_before": {"computed_prompt_tokens": 0},
                 "loads_after": {"computed_prompt_tokens": 9}}) is None


def test_the_new_files_are_new_and_the_entries_are_appended():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert [c["name"] for c in bench["configs"]][-1] == "olmo-hybrid-7b"
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert [m["name"] for m in bench["per_layer"]][-2:] == [
        "kernels.linattn_decode_roofline_share", "scheduler.state_recompute_share"]
    for m in bench["per_layer"][-2:]:
        assert m["workloads"] == [CELL]
        meta = catalog.layer_metric_reader(m["name"]).META
        assert (m["layer"], m["moves"], m["unit"]) == (meta["layer"], "output_tok_per_s", meta["unit"])
        assert meta["source"].startswith(m["source"])
    why = bench["workloads"][-1]["why"]
    assert len(why) <= 200 and "state" in why

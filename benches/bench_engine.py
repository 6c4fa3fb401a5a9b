#!/usr/bin/env python
"""Deterministic CPU engine-step gate.

Fixed seeds end to end (weights, prompts, sampling) on the CPU backend with
eight virtual devices, whatever the machine has: a regression canary for what
the engine computes and counts, not a record of speed (its wall-clock fields
are CPU seconds of a toy model).  Prints ONE JSON line::

  {"bench": "engine_gate", "decode_tok_s": ..., "prefill_ms_64tok": ...,
   "spec_accept_rate": ..., "stream_fingerprint": ..., ...}

``stream_fingerprint`` digests every generated token id across the
scenarios — a regression canary far stricter than throughput: ANY
behavioral drift in scheduler/runner/sampler flips it (intentional changes
update BENCH_r{N}.json with the new value alongside the explaining commit).

Run: ``python benches/bench_engine.py``
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# a CPU gate by definition: it sets its own platform and device count (8
# virtual devices so the tp scaling probe can build real meshes)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"


def main() -> dict:
    import jax

    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.models.config import tiny_test_config
    from smg_tpu.protocols.sampling import SamplingParams

    cfg = EngineConfig(
        model=tiny_test_config(),
        cache=CacheConfig(page_size=16, num_pages=256, auto_size=False,
                          dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=4, max_seq_len=256, max_prefill_tokens=64,
            prefill_token_buckets=(64,), decode_batch_buckets=(4,),
            decode_horizon=4,
        ),
        dtype="float32", seed=0,
    )
    eng = Engine(cfg)
    eng.start()  # background loop: submit() callbacks need it
    fingerprint = hashlib.blake2b(digest_size=8)

    # ---- scenario 1: batched greedy decode throughput (compile amortized)
    prompts = [[(7 * i + j) % 400 + 5 for j in range(48)] for i in range(4)]
    r = eng.generate(prompt_ids=prompts[0], sampling=SamplingParams(
        temperature=0.0, max_new_tokens=8, ignore_eos=True))  # compile
    fingerprint.update(bytes(str(r.token_ids), "utf8"))
    eng.flush_cache()
    done = {}
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        eng.submit(p, SamplingParams(temperature=0.0, max_new_tokens=24,
                                     ignore_eos=True),
                   rid=f"d{i}", on_output=lambda o, i=i: done.setdefault(i, []).append(o))
    import threading

    deadline = time.perf_counter() + 120
    while time.perf_counter() < deadline:
        if len([k for k, v in done.items() if v and v[-1].finished]) == len(prompts):
            break
        time.sleep(0.005)
    dt = time.perf_counter() - t0
    n_tok = sum(len(o.new_token_ids) for v in done.values() for o in v)
    decode_tok_s = n_tok / dt
    for i in sorted(done):
        ids = [t for o in done[i] for t in o.new_token_ids]
        fingerprint.update(bytes(str(ids), "utf8"))

    # ---- scenario 2: warm prefill latency (64-token prompt, cache flushed)
    eng.flush_cache()
    p64 = [(11 * j) % 400 + 5 for j in range(64)]
    eng.generate(prompt_ids=p64, sampling=SamplingParams(
        temperature=0.0, max_new_tokens=1, ignore_eos=True))  # compile
    eng.flush_cache()
    t0 = time.perf_counter()
    r = eng.generate(prompt_ids=p64, sampling=SamplingParams(
        temperature=0.0, max_new_tokens=1, ignore_eos=True))
    prefill_ms = (time.perf_counter() - t0) * 1e3
    fingerprint.update(bytes(str(r.token_ids), "utf8"))

    # ---- scenario 3: speculative (n-gram) drafter-correctness gate.  The
    # fingerprint feed is unchanged (rep/24 greedy, the historical stream);
    # the GATE around it is no longer the vacuous always-accepts readout: a
    # non-spec twin must produce the byte-identical stream, and a longer
    # known-repetitive workload must land acceptance in a meaningful band
    # (drafts really fire AND the fused verify really rejects sometimes).
    def spec_sched(**kw) -> SchedulerConfig:
        return SchedulerConfig(
            max_batch_size=4, max_seq_len=256, max_prefill_tokens=64,
            prefill_token_buckets=(64,), decode_batch_buckets=(4,), **kw,
        )

    spec_eng = Engine(cfg.replace(scheduler=spec_sched(
        speculative=True, spec_max_draft=6)))
    rep = [5, 6, 7, 8] * 8
    r = spec_eng.generate(prompt_ids=rep, sampling=SamplingParams(
        temperature=0.0, max_new_tokens=24, ignore_eos=True))
    fingerprint.update(bytes(str(r.token_ids), "utf8"))
    nospec_eng = Engine(cfg.replace(scheduler=spec_sched()))
    r_base = nospec_eng.generate(prompt_ids=rep, sampling=SamplingParams(
        temperature=0.0, max_new_tokens=24, ignore_eos=True))
    assert r.token_ids == r_base.token_ids, (
        "spec gate: temp-0 stream diverged from non-spec "
        f"({r.token_ids} vs {r_base.token_ids})"
    )
    # drafter-correctness workload: repetitive enough to draft heavily, long
    # and varied enough that acceptance cannot be trivially total
    gate_jobs = [rep, [9, 9, 9, 9, 9, 9, 9, 9], list(range(40, 70)) + [5, 6, 7, 8] * 4]
    for p in gate_jobs:
        rs = spec_eng.generate(prompt_ids=p, sampling=SamplingParams(
            temperature=0.0, max_new_tokens=32, ignore_eos=True))
        rb = nospec_eng.generate(prompt_ids=p, sampling=SamplingParams(
            temperature=0.0, max_new_tokens=32, ignore_eos=True))
        assert rs.token_ids == rb.token_ids, f"spec gate parity broke on {p[:8]}"
    drafted = spec_eng.scheduler.num_spec_drafted
    accepted = spec_eng.scheduler.num_spec_accepted
    accept_rate = accepted / drafted if drafted else None
    assert drafted >= 24, f"spec gate: drafter barely fired ({drafted} tokens)"
    assert accept_rate is not None and 0.05 <= accept_rate <= 1.0, (
        f"spec gate: acceptance {accept_rate} outside the meaningful band"
    )
    spec_gate = {
        "parity": "byte-identical",
        "drafted": drafted,
        "accepted": accepted,
        "accept_rate": round(accept_rate, 3),
    }
    eng.stop()
    spec_eng.stop()
    nospec_eng.stop()

    # ---- scenario 4: host-overlap probe (NOT part of the fingerprint —
    # wall-clock only).  Decode device-calls/s with a synthetic 2ms host
    # postprocess delay PER REQUEST per step (the delay sits in the output
    # callback — exactly where real detokenize/stop-string/serialize work
    # runs, and it scales with concurrent streams like the real thing),
    # overlap on vs off.  The sync path pays device compute + host delay
    # serially; the overlapped pipeline hides the host side behind the
    # in-flight device step.  Shape notes: 4 concurrent streams x 2ms puts
    # the host side in the same band as a horizon-4 decode call of the
    # probe model on an idle CPU — the balanced regime where pipelining is
    # visible (a TPU decode step dwarfs its host work the same way).
    # Best of 3 interleaved rounds per mode filters ambient load spikes.
    from smg_tpu.models.config import ModelConfig

    probe_model = ModelConfig(
        vocab_size=512, hidden_size=256, intermediate_size=768,
        num_layers=4, num_heads=8, num_kv_heads=2, head_dim=32,
        rope_theta=10000.0, max_position_embeddings=2048,
        eos_token_ids=(0,), bos_token_id=1, dtype="float32",
    )
    host_delay_s = 0.002
    probe_horizon = 4
    probe_new_tokens = 96
    probe_prompts = [
        [(13 * j + 7 * i) % 400 + 5 for j in range(32)] for i in range(4)
    ]

    def probe_engine(overlap: bool) -> Engine:
        # page pool sized to the workload (4 streams x 128 tokens), not to
        # max_seq_len: the overlap engine skips KV donation on CPU (see
        # engine/donation.py), so an oversized cache would tax only the
        # overlapped side with copy bandwidth the workload never uses
        return Engine(EngineConfig(
            model=probe_model,
            cache=CacheConfig(page_size=16, num_pages=128, auto_size=False,
                              dtype="float32"),
            scheduler=SchedulerConfig(
                max_batch_size=4, max_seq_len=1024, max_prefill_tokens=64,
                prefill_token_buckets=(64,), decode_batch_buckets=(4,),
                decode_horizon=probe_horizon, overlap_schedule=overlap,
            ),
            dtype="float32", seed=0,
        ))

    def probe_round(e: Engine, tag: str) -> float:
        sp = SamplingParams(temperature=0.0, max_new_tokens=probe_new_tokens,
                            ignore_eos=True)
        finished: set = set()

        def cb(out):
            time.sleep(host_delay_s)  # synthetic per-request postprocess
            if out.finished:
                finished.add(out.rid)

        for i, p in enumerate(probe_prompts):
            e.submit(p, sp, rid=f"{tag}-{i}", on_output=cb)
        t0 = time.perf_counter()
        while len(finished) < len(probe_prompts):
            e.step()
            if time.perf_counter() - t0 > 180:
                raise TimeoutError("overlap probe stuck")
        dt = time.perf_counter() - t0
        while e.scheduler.has_work():
            e.step()
        e.flush_cache()
        return (probe_new_tokens / probe_horizon) / dt  # device calls/s

    try:
        e_on, e_off = probe_engine(True), probe_engine(False)
        probe_round(e_on, "warm")  # compile
        probe_round(e_off, "warm")
        # interleaved rounds equalize exposure to ambient load spikes
        on_rounds, off_rounds = [], []
        for r in range(3):
            on_rounds.append(probe_round(e_on, f"on{r}"))
            off_rounds.append(probe_round(e_off, f"off{r}"))
        overlap_on = max(on_rounds)
        overlap_off = max(off_rounds)
        probe = {
            "host_delay_ms": host_delay_s * 1e3,
            "streams": len(probe_prompts),
            "decode_horizon": probe_horizon,
            "overlap_on_steps_s": round(overlap_on, 1),
            "overlap_off_steps_s": round(overlap_off, 1),
            "speedup": round(overlap_on / overlap_off, 3),
        }
    except Exception as err:  # the probe must not void the gate
        probe = {"error": f"{type(err).__name__}: {err}"[:200]}

    # ---- scenario 5: steady-state retrace/transfer probe (NOT part of the
    # fingerprint).  After warmup, N decode steps run under
    # jax.transfer_guard("disallow") with an XLA-compile counter: the
    # recompile count is reported as a NUMBER so BENCH diffs catch a
    # retrace regression even when ambient load hides the stall, and any
    # implicit host<->device transfer raises.  Pairs with the smglint
    # HOTSYNC/RETRACE static rules (smg_tpu/analysis/).
    try:
        from smg_tpu.analysis.runtime_guards import steady_state_guard

        g_eng = probe_engine(True)
        sp = SamplingParams(temperature=0.0, max_new_tokens=64, ignore_eos=True)
        for i, p in enumerate(probe_prompts):
            g_eng.submit(p, sp, rid=f"g{i}")
        for _ in range(6):  # prefill + pipeline priming + compiles
            g_eng.step()
        guarded_steps = 8
        with steady_state_guard(max_compiles=10_000) as cc:  # report, don't raise
            for _ in range(guarded_steps):
                g_eng.step()
        while g_eng.scheduler.has_work():
            g_eng.step()
        steady = {
            "guarded_steps": guarded_steps,
            "recompiles": cc.count,  # MUST be 0; BENCH diffs gate on it
            "transfer_guard": "clean",  # implicit transfer would have raised
        }
    except Exception as err:  # the probe must not void the gate
        steady = {"error": f"{type(err).__name__}: {err}"[:200]}

    # ---- scenario 6: long-prefill interference probe (NOT part of the
    # fingerprint — wall-clock only).  Decode ITL p95 of a running batch
    # WHILE a long prompt admits, budgeted (stall-free per-step prefill
    # budget: one chunk per step, decode every step) vs legacy
    # drain-the-queue (all chunks back-to-back inside one step).  The
    # stall-free bound to verify: p95 during admission ~ one chunk's
    # latency, not the whole prompt's.
    def interference_round(policy: str) -> dict:
        e = Engine(EngineConfig(
            model=probe_model,
            cache=CacheConfig(page_size=16, num_pages=256, auto_size=False,
                              dtype="float32"),
            scheduler=SchedulerConfig(
                max_batch_size=4, max_seq_len=1024, max_prefill_tokens=64,
                prefill_token_buckets=(64,), decode_batch_buckets=(4,),
                prefill_mix_policy=policy,
            ),
            dtype="float32", seed=0,
        ))
        long_prompt = [(11 * j) % 400 + 5 for j in range(512)]  # 8 chunks
        long_sp = SamplingParams(temperature=0.0, max_new_tokens=2,
                                 ignore_eos=True)
        stamps: dict[str, list[float]] = {}

        def cb(out):
            stamps.setdefault(out.rid, []).append(time.perf_counter())

        # warmup: compile every prefill/decode variant this round will hit
        # (incl. the KV-only chunk program the budgeted policy uses)
        e.submit(long_prompt, long_sp, rid="warm", on_output=cb)
        while e.scheduler.has_work():
            e.step()
        e.flush_cache()
        base_sp = SamplingParams(temperature=0.0, max_new_tokens=192,
                                 ignore_eos=True)
        for i in range(3):
            e.submit([(13 * j + 7 * i) % 400 + 5 for j in range(32)],
                     base_sp, rid=f"b{i}", on_output=cb)
        for _ in range(24):  # settle into steady-state decode
            e.step()
        t_submit = time.perf_counter()
        e.submit([t + 1 for t in long_prompt], long_sp, rid="L", on_output=cb)
        deadline = time.perf_counter() + 120
        while "L" not in stamps:
            e.step()
            if time.perf_counter() > deadline:
                raise TimeoutError("interference probe stuck")
        t_first = stamps["L"][0]
        while e.scheduler.has_work():
            e.step()
        # decode ITL of the running streams across the admission window: any
        # inter-token gap OVERLAPPING [submit, first-token] counts, so the
        # legacy drain's single admission-spanning stall is measured rather
        # than clipped (its base streams emit nothing INSIDE the window)
        gaps = []
        for i in range(3):
            ts = stamps[f"b{i}"]
            gaps.extend(
                b - a for a, b in zip(ts, ts[1:])
                if b >= t_submit and a <= t_first
            )
        gaps.sort()
        p95 = gaps[min(len(gaps) - 1, (len(gaps) * 95) // 100)] if gaps else 0.0
        return {
            "itl_p95_ms": round(p95 * 1e3, 2),
            "admission_ms": round((t_first - t_submit) * 1e3, 1),
            "decode_outputs_in_window": sum(
                1 for i in range(3)
                for t in stamps[f"b{i}"] if t_submit <= t <= t_first
            ),
        }

    try:
        budgeted = interference_round("stall-free")
        legacy = interference_round("throughput")
        interference = {
            "prompt_tokens": 512, "chunk_tokens": 64, "n_chunks": 8,
            "budgeted": budgeted, "legacy": legacy,
            "itl_p95_ratio_legacy_over_budgeted": round(
                legacy["itl_p95_ms"] / budgeted["itl_p95_ms"], 2
            ) if budgeted["itl_p95_ms"] else None,
        }
    except Exception as err:  # the probe must not void the gate
        interference = {"error": f"{type(err).__name__}: {err}"[:200]}

    # ---- scenario 7: flight-recorder overhead (NOT part of the fingerprint
    # — wall-clock only).  The recorder must be cheap enough to stay always
    # on: pure inline step loop (no synthetic host delay — the regime where
    # per-step recording overhead is MOST visible), recorder on vs off,
    # best-of-3 interleaved rounds.  Budget: <= 2% step-loop overhead.
    def recorder_engine(flight: bool) -> Engine:
        return Engine(EngineConfig(
            model=probe_model,
            cache=CacheConfig(page_size=16, num_pages=128, auto_size=False,
                              dtype="float32"),
            scheduler=SchedulerConfig(
                max_batch_size=4, max_seq_len=1024, max_prefill_tokens=64,
                prefill_token_buckets=(64,), decode_batch_buckets=(4,),
                decode_horizon=probe_horizon,
            ),
            dtype="float32", seed=0,
            flight_recorder=flight,
        ))

    def recorder_round(e: Engine, tag: str) -> float:
        sp = SamplingParams(temperature=0.0, max_new_tokens=probe_new_tokens,
                            ignore_eos=True)
        done: set = set()
        for i, p in enumerate(probe_prompts):
            e.submit(p, sp, rid=f"{tag}-{i}",
                     on_output=lambda o: done.add(o.rid) if o.finished else None)
        t0 = time.perf_counter()
        while len(done) < len(probe_prompts):
            e.step()
            if time.perf_counter() - t0 > 180:
                raise TimeoutError("recorder overhead probe stuck")
        dt = time.perf_counter() - t0
        while e.scheduler.has_work():
            e.step()
        e.flush_cache()
        return dt

    try:
        e_rec, e_bare = recorder_engine(True), recorder_engine(False)
        recorder_round(e_rec, "warm")  # compile
        recorder_round(e_bare, "warm")
        rec_rounds, bare_rounds = [], []
        for r in range(3):
            rec_rounds.append(recorder_round(e_rec, f"rec{r}"))
            bare_rounds.append(recorder_round(e_bare, f"bare{r}"))
        t_rec, t_bare = min(rec_rounds), min(bare_rounds)
        overhead_pct = (t_rec - t_bare) / t_bare * 100.0
        ring_len = len(e_rec.dump_flight()["ring"])
        e_rec.stop()
        e_bare.stop()
        recorder = {
            "on_best_s": round(t_rec, 4),
            "off_best_s": round(t_bare, 4),
            "overhead_pct": round(overhead_pct, 2),
            "budget_pct": 2.0,
            "within_budget": overhead_pct <= 2.0,
            "ring_records": ring_len,
        }
    except Exception as err:  # the probe must not void the gate
        recorder = {"error": f"{type(err).__name__}: {err}"[:200]}

    # ---- scenario 8: megastep probe (NOT part of the fingerprint).  Host
    # -overhead amortization of the scan-fused K-step decode loop: every
    # scheduler step costs one host round trip (dispatch + deferred fetch +
    # bookkeeping), so per-token host overhead is (host_cost_per_step *
    # steps / decode_tokens) — the megastep divides steps/token by ~K.  The
    # workload staggers max_new_tokens so length finishes land MID-horizon:
    # the device done mask must early-exit (waste stays near zero) instead
    # of computing K-1 overshoot columns per finish.  Reported per K:
    # scheduler steps, decode tokens, synthetic per-token host overhead at
    # the scenario-4 2ms/step host cost, wasted-token ratio, and the
    # amortization factor vs K=1.  The probe runs the SYNCHRONOUS schedule:
    # with overlap on, a finish also discards the in-flight lookahead frame
    # (counted at full width as an upper bound — its results are never
    # fetched), which would fold pipeline bookkeeping into the number this
    # scenario isolates: how much the done mask's early exit actually saves.
    def megastep_round(K: int) -> dict:
        e = Engine(EngineConfig(
            model=probe_model,
            cache=CacheConfig(page_size=16, num_pages=256, auto_size=False,
                              dtype="float32"),
            scheduler=SchedulerConfig(
                max_batch_size=4, max_seq_len=1024, max_prefill_tokens=64,
                prefill_token_buckets=(64,), decode_batch_buckets=(4,),
                decode_horizon=K, overlap_schedule=False,
            ),
            dtype="float32", seed=0,
        ))
        # staggered lengths: finishes inside the horizon for every K > 1
        new_toks = [89, 96, 91, 93]
        done: set = set()
        for i, p in enumerate(probe_prompts):
            e.submit(p, SamplingParams(temperature=0.0,
                                       max_new_tokens=new_toks[i],
                                       ignore_eos=True),
                     rid=f"k{K}-{i}",
                     on_output=lambda o: done.add(o.rid) if o.finished else None)
        steps = 0
        t0 = time.perf_counter()
        while len(done) < len(probe_prompts):
            e.step()
            steps += 1
            if time.perf_counter() - t0 > 180:
                raise TimeoutError("megastep probe stuck")
        while e.scheduler.has_work():
            e.step()
            steps += 1
        dt = time.perf_counter() - t0
        sched = e.scheduler
        toks = sched.num_decode_tokens
        wasted = sched.num_wasted_decode_tokens
        e.stop()
        return {
            "K": K,
            "steps": steps,
            "decode_tokens": toks,
            "wall_s": round(dt, 3),
            "wasted_tokens": wasted,
            "wasted_ratio": round(wasted / (toks + wasted), 4) if toks else None,
            "early_exits": sched.num_megastep_early_exits,
            # host round trips per token * the scenario-4 host cost: the
            # quantity the megastep amortizes, from MEASURED step counts
            "host_overhead_ms_per_token": round(
                host_delay_s * 1e3 * steps / toks, 4
            ) if toks else None,
        }

    try:
        rounds = {K: megastep_round(K) for K in (1, 4, 8, 16)}
        o1 = rounds[1]["host_overhead_ms_per_token"]
        megastep = {
            "host_cost_ms_per_step": host_delay_s * 1e3,
            "rounds": list(rounds.values()),
            "amortization_x_at_8": round(
                o1 / rounds[8]["host_overhead_ms_per_token"], 2
            ),
            "amortization_x_at_16": round(
                o1 / rounds[16]["host_overhead_ms_per_token"], 2
            ),
            "max_wasted_ratio": max(
                r["wasted_ratio"] or 0.0 for r in rounds.values()
            ),
        }
    except Exception as err:  # the probe must not void the gate
        megastep = {"error": f"{type(err).__name__}: {err}"[:200]}

    # ---- scenario 9: spec probe (NOT part of the fingerprint).  Accepted
    # -tokens-per-decode-step of the fused draft-verify path vs the plain
    # K=1 baseline on repetitive workloads — a STEP-COUNT metric (wall-clock
    # on this box swings ±3x with ambient load; device round trips per token
    # do not).  Workloads emulate where prompt-lookup drafting pays:
    # "json_mode" = a tight cyclic token pattern (structured output repeats
    # its own keys), "code_edit" = a long passage the generation re-emits
    # (edit-style workloads copy most of their input).  Both engines run
    # decode_horizon=1 so the number isolates speculation's step-count win
    # from the megastep's.
    def spec_round(speculative: bool, prompt: "list[int]", n_new: int) -> dict:
        e = Engine(EngineConfig(
            model=probe_model,
            cache=CacheConfig(page_size=16, num_pages=256, auto_size=False,
                              dtype="float32"),
            scheduler=SchedulerConfig(
                max_batch_size=4, max_seq_len=1024, max_prefill_tokens=128,
                prefill_token_buckets=(128,), decode_batch_buckets=(4,),
                decode_horizon=1, overlap_schedule=False,
                speculative=speculative, spec_max_draft=8,
            ),
            dtype="float32", seed=0,
        ))
        done: list = []
        e.submit(prompt, SamplingParams(temperature=0.0, max_new_tokens=n_new,
                                        ignore_eos=True),
                 rid="sp", on_output=lambda o: done.append(o.finished))
        steps = 0
        decode_steps = 0
        t0 = time.perf_counter()
        while not (done and done[-1]):
            before = e.scheduler.num_decode_tokens
            e.step()
            steps += 1
            if e.scheduler.num_decode_tokens > before:
                decode_steps += 1
            if time.perf_counter() - t0 > 180:
                raise TimeoutError("spec probe stuck")
        sched = e.scheduler
        toks = sched.num_decode_tokens
        out = {
            "speculative": speculative,
            "decode_tokens": toks,
            "decode_steps": decode_steps,
            "tokens_per_step": round(toks / decode_steps, 3) if decode_steps else None,
            "drafted": sched.num_spec_drafted,
            "accepted": sched.num_spec_accepted,
            "accept_rate": round(
                sched.num_spec_accepted / sched.num_spec_drafted, 3
            ) if sched.num_spec_drafted else None,
        }
        e.stop()
        return out

    try:
        json_prompt = [17, 40, 61, 17, 52, 61, 17, 40, 61, 17, 52, 61] * 4
        code_prompt = [(7 * j) % 200 + 5 for j in range(48)] * 2
        spec_probe = {}
        for name, prompt, n_new in (
            ("json_mode", json_prompt, 96),
            ("code_edit", code_prompt, 96),
        ):
            on = spec_round(True, prompt, n_new)
            off = spec_round(False, prompt, n_new)
            spec_probe[name] = {
                "spec": on, "baseline": off,
                "step_speedup": round(
                    on["tokens_per_step"] / off["tokens_per_step"], 2
                ) if on["tokens_per_step"] and off["tokens_per_step"] else None,
            }
        spec_probe["accepted_tokens_per_step"] = max(
            v["spec"]["tokens_per_step"] or 0.0
            for v in spec_probe.values() if isinstance(v, dict) and "spec" in v
        )
    except Exception as err:  # the probe must not void the gate
        spec_probe = {"error": f"{type(err).__name__}: {err}"[:200]}

    # ---- scenario 10: tp scaling probe (NOT part of the fingerprint).
    # Tensor-parallel sharded decode vs mesh size on the virtual CPU mesh.
    # Wall-clock on this box is untrustworthy (±3x ambient swing, and a CPU
    # "mesh" is 8 slices of the same socket, so tok/s does not scale), so
    # the record leads with STEP-COUNT and host-side dispatch metrics: the
    # things that must hold for the TP story — token parity with mesh=1,
    # unchanged scheduler step count (the sharded program is still ONE
    # launch per megastep), and the per-step dispatch-enqueue overhead the
    # mesh adds (what a real TPU deployment pays on the host thread).
    def tp_round(n: int) -> dict:
        from smg_tpu.engine.config import ParallelConfig

        devs = jax.devices("cpu")[:n]
        e = Engine(EngineConfig(
            model=probe_model,
            parallel=ParallelConfig(tp=n),
            cache=CacheConfig(page_size=16, num_pages=256, auto_size=False,
                              dtype="float32"),
            scheduler=SchedulerConfig(
                max_batch_size=4, max_seq_len=1024, max_prefill_tokens=64,
                prefill_token_buckets=(64,), decode_batch_buckets=(4,),
                decode_horizon=4, overlap_schedule=False,
            ),
            dtype="float32", seed=0,
        ), devices=devs)
        # warm: compile the prefill bucket + megastep trace so the measured
        # window is steady-state dispatch, not trace+compile
        e.generate(prompt_ids=probe_prompts[0], sampling=SamplingParams(
            temperature=0.0, max_new_tokens=8, ignore_eos=True))
        e.flush_cache()
        sched = e.scheduler
        phases = lambda: e.loads(include_audit=False)["step_phases"]["seconds"]
        p0 = phases()
        t0_tok = sched.num_decode_tokens
        done: dict = {}
        for i, p in enumerate(probe_prompts):
            e.submit(p, SamplingParams(temperature=0.0, max_new_tokens=64,
                                       ignore_eos=True),
                     rid=f"tp{n}-{i}",
                     on_output=lambda o, i=i: done.setdefault(i, []).append(o))
        steps = 0
        t0 = time.perf_counter()
        while e.scheduler.has_work():
            e.step()
            steps += 1
            if time.perf_counter() - t0 > 180:
                raise TimeoutError("tp probe stuck")
        dt = time.perf_counter() - t0
        toks = sched.num_decode_tokens - t0_tok
        p1 = phases()
        dispatch_s = p1["launch_dispatch"] - p0["launch_dispatch"]
        fetch_s = p1["consume_fetch"] - p0["consume_fetch"]
        streams = [
            [t for o in done[i] for t in o.new_token_ids]
            for i in sorted(done)
        ]
        e.stop()
        return {
            "mesh": n,
            "steps": steps,
            "decode_tokens": toks,
            "decode_tok_s_wall": round(toks / dt, 1),  # informational only
            "dispatch_enqueue_s": round(dispatch_s, 4),
            "fetch_wait_s": round(fetch_s, 4),
            "dispatch_ms_per_step": round(
                dispatch_s * 1e3 / steps, 4
            ) if steps else None,
            "_streams": streams,
        }

    try:
        n_cpu = len(jax.devices("cpu"))
        sizes = [n for n in (1, 2, 4, 8) if n <= n_cpu]
        skipped = [n for n in (1, 2, 4, 8) if n > n_cpu]
        tp_rounds = [tp_round(n) for n in sizes]
        base = tp_rounds[0]
        tp_probe = {
            "mesh_sizes": sizes,
            "skipped_mesh_sizes": skipped,  # no silent caps
            "token_parity_vs_single": all(
                r["_streams"] == base["_streams"] for r in tp_rounds[1:]
            ),
            "steps_invariant": all(
                r["steps"] == base["steps"] for r in tp_rounds[1:]
            ),
            "rounds": [
                {k: v for k, v in r.items() if k != "_streams"}
                for r in tp_rounds
            ],
        }
    except Exception as err:  # the probe must not void the gate
        tp_probe = {"error": f"{type(err).__name__}: {err}"[:200]}

    # ---- scenario 11: compiled-program audit (NOT part of the fingerprint).
    # The runtime half of the smglint JAX-discipline rules: arm the program
    # auditor after warmup, run steady-state traffic at tp=1 and tp=8, then
    # ASSERT the audit verdict from the compiled representation — zero
    # uncommitted/mismatched steady-state inputs (no implicit per-launch
    # reshard), every intended donation actually aliased in the compiled
    # HLO (input_output_alias), and zero recompiles while armed.  A debug
    # surface becoming an asserted invariant, same as the steady-state probe.
    def audit_round(n: int) -> dict:
        from smg_tpu.analysis.runtime_guards import program_audit
        from smg_tpu.engine.config import ParallelConfig

        devs = jax.devices("cpu")[:n]
        e = Engine(EngineConfig(
            model=probe_model,
            parallel=ParallelConfig(tp=n) if n > 1 else ParallelConfig(),
            cache=CacheConfig(page_size=16, num_pages=256, auto_size=False,
                              dtype="float32"),
            scheduler=SchedulerConfig(
                max_batch_size=4, max_seq_len=1024, max_prefill_tokens=64,
                prefill_token_buckets=(64,), decode_batch_buckets=(4,),
                decode_horizon=4, overlap_schedule=False,
            ),
            dtype="float32", seed=0,
        ), devices=devs)
        e.generate(prompt_ids=probe_prompts[0], sampling=SamplingParams(
            temperature=0.0, max_new_tokens=8, ignore_eos=True))  # warmup
        e.runner._programs.arm()
        e.generate(prompt_ids=probe_prompts[1], sampling=SamplingParams(
            temperature=0.0, max_new_tokens=24, ignore_eos=True))
        report = program_audit(e)
        assert report["clean"], f"tp={n} program audit dirty: {report}"
        assert report["recompiles"] == 0, report
        donated = [p for p in report["programs"] if p.get("donation")]
        assert donated and all(
            p["donation"]["verified"] for p in donated
        ), report
        e.stop()
        return {
            "mesh": n,
            "audited_programs": sum(
                1 for p in report["programs"] if p["audited"]
            ),
            "donation_verified": len(donated),
            "recompiles": report["recompiles"],
            "clean": report["clean"],
        }

    try:
        sizes = [n for n in (1, 8) if n <= len(jax.devices("cpu"))]
        audit_probe = {
            "mesh_sizes": sizes,
            "rounds": [audit_round(n) for n in sizes],
        }
    except Exception as err:  # the probe must not void the gate
        audit_probe = {"error": f"{type(err).__name__}: {err}"[:200]}

    return {
        "bench": "engine_gate",
        "tp_scaling_probe": tp_probe,
        "program_audit_probe": audit_probe,
        "decode_tok_s": round(decode_tok_s, 1),
        "prefill_ms_64tok": round(prefill_ms, 1),
        "spec_accept_rate": round(accepted / drafted, 3) if drafted else None,
        "spec_drafted": drafted,
        "spec_gate": spec_gate,
        "spec_probe": spec_probe,
        "overlap_probe": probe,
        "steady_state_probe": steady,
        "interference_probe": interference,
        "flight_recorder_probe": recorder,
        "megastep_probe": megastep,
        "stream_fingerprint": fingerprint.hexdigest(),
        "seeds": {"weights": 0, "sampler": "seed ^ 0x5EED"},
        "deterministic": True,
    }


if __name__ == "__main__":
    print(json.dumps(main()))

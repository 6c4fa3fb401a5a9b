"""Tensor-parallel sharded decode as a first-class runner mode.

Byte-parity: a tp>1 engine on the forced 8-device CPU mesh must emit
token streams BYTE-IDENTICAL to the single-device engine — across the
overlapped pipeline, the megastep horizon, chunked prefill, and fused
speculation, at temperature 0 and 0.8 (same sampling-key fold order, same
program semantics; GSPMD only changes where the math runs).  Logprobs may
differ by float association across shards, bounded at 1e-3.

Hygiene: steady-state decode on the mesh is transfer-guard clean and
0-recompile (DecodeState buffers and every launch upload are committed to
the mesh's replicated sharding — no per-launch resharding), adaptive-K
churn reuses one trace per batch bucket, and sharded traffic leaves a
zero-leak ``Engine.audit()``.

Policy: KV donation is an explicit per-backend/per-mode table
(``engine/donation.py``), not a runner-internal heuristic.
"""

import jax
import numpy as np
import pytest

from smg_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ParallelConfig,
    SchedulerConfig,
)
from smg_tpu.engine.donation import kv_donation_policy
from smg_tpu.engine.engine import Engine
from smg_tpu.models.config import tiny_test_config
from smg_tpu.protocols.sampling import SamplingParams
from smg_tpu.tokenizer import MockTokenizer

PROMPT = list(range(5, 30))
# cyclic pattern so the n-gram drafter actually drafts (prompt lookup hits)
SPEC_PROMPT = [17, 40, 61, 17, 52, 61, 17, 40, 61, 17, 52, 61] * 3


def make_engine(parallel=None, devices=None, *, overlap=True, horizon=1,
                horizon_max=0, adaptive=False, spec=False,
                max_prefill_tokens=64, buckets=(32, 64), pages=96,
                max_seq_len=256):
    cfg = EngineConfig(
        model=tiny_test_config(),
        parallel=parallel or ParallelConfig(),
        cache=CacheConfig(page_size=16, num_pages=pages, auto_size=False,
                          dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=4, max_seq_len=max_seq_len,
            max_prefill_tokens=max_prefill_tokens,
            prefill_token_buckets=buckets, decode_batch_buckets=(4,),
            overlap_schedule=overlap, decode_horizon=horizon,
            decode_horizon_max=horizon_max, adaptive_horizon=adaptive,
            speculative=spec,
        ),
        dtype="float32",
    )
    return Engine(cfg, tokenizer=MockTokenizer(), devices=devices)


def gen(eng, temp=0.0, n=24, prompt=PROMPT):
    return eng.generate(
        prompt_ids=prompt,
        sampling=SamplingParams(temperature=temp, max_new_tokens=n,
                                ignore_eos=True),
    )


def assert_pair(cpu_devices, tp, temp, *, prompt=PROMPT, n=24, **kw):
    ref = gen(make_engine(ParallelConfig(), cpu_devices[:1], **kw),
              temp=temp, n=n, prompt=prompt)
    got = gen(make_engine(ParallelConfig(tp=tp), cpu_devices[:tp], **kw),
              temp=temp, n=n, prompt=prompt)
    assert got.token_ids == ref.token_ids
    np.testing.assert_allclose(got.logprobs, ref.logprobs, atol=1e-3)


# ---- byte-parity vs single-device (fast pairwise slice; full grid: slow)

@pytest.mark.parametrize("overlap,horizon,temp", [
    (True, 1, 0.0),
    (False, 4, 0.8),
    (True, 4, 0.0),
])
def test_tp2_stream_parity(cpu_devices, overlap, horizon, temp):
    assert_pair(cpu_devices, 2, temp, overlap=overlap, horizon=horizon)


@pytest.mark.slow
@pytest.mark.parametrize("overlap", [True, False])
@pytest.mark.parametrize("horizon", [1, 4])
@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_tp2_stream_parity_full_grid(cpu_devices, overlap, horizon, temp):
    assert_pair(cpu_devices, 2, temp, overlap=overlap, horizon=horizon)


def test_tp2_chunked_prefill_parity(cpu_devices):
    """A 96-token prompt under a 32-token per-step budget prefills in
    resumable chunks (non-final chunks through the KV-only extend path);
    the sharded engine must chunk AND sample identically."""
    long_prompt = [(7 * j) % 300 + 5 for j in range(96)]
    assert_pair(
        cpu_devices, 2, 0.0, prompt=long_prompt,
        max_prefill_tokens=32, buckets=(32,), pages=128, max_seq_len=512,
    )


@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_tp2_speculative_parity(cpu_devices, temp):
    """Fused draft-verify on the mesh: same drafts, same acceptance, same
    stream as the single-device spec engine."""
    assert_pair(cpu_devices, 2, temp, prompt=SPEC_PROMPT, n=32, spec=True)


def test_tp4_kv_heads_replication_fallback(cpu_devices):
    """tiny model has 2 kv heads: tp=4 cannot shard the wk/wv head dim and
    must fall back to replicating it (shape-aware tree_shardings) while
    still sharding q/ffn/vocab — and stay byte-identical."""
    assert_pair(cpu_devices, 4, 0.0, horizon=4)


# ---- steady-state hygiene on the full 8-device mesh

def test_tp8_steady_state_guard_clean(cpu_devices):
    """0 recompiles + no implicit transfers at steady state on an 8-device
    mesh: every decode input is either a resident mesh-committed DecodeState
    buffer or an explicit replicated upload."""
    from smg_tpu.analysis.runtime_guards import steady_state_guard

    eng = make_engine(ParallelConfig(tp=8), cpu_devices[:8], horizon=4)
    done = {}
    prompts = [[(7 * i + j) % 90 + 5 for j in range(16)] for i in range(2)]
    for i, p in enumerate(prompts):
        eng.submit(p, SamplingParams(temperature=0.0, max_new_tokens=64,
                                     ignore_eos=True),
                   rid=f"r{i}",
                   on_output=lambda o, i=i: done.setdefault(i, []).append(o))
    for _ in range(8):  # warmup: prefill + prime the pipeline + compiles
        eng.step()
    with steady_state_guard() as cc:
        for _ in range(8):
            eng.step()
    assert cc.count == 0
    while eng.scheduler.has_work():
        eng.step()
    lens = {i: sum(len(o.new_token_ids) for o in v) for i, v in done.items()}
    assert lens == {0: 64, 1: 64}


def test_tp2_adaptive_k_single_trace(cpu_devices):
    """Adaptive-K churn (staggered finishes move the horizon) rides ONE
    compiled trace per batch bucket: K is a device scalar, not a cache key."""
    from smg_tpu.analysis.runtime_guards import steady_state_guard

    eng = make_engine(ParallelConfig(tp=2), cpu_devices[:2],
                      adaptive=True, horizon=2, horizon_max=4)
    done = {}
    lengths = [40, 46, 52, 58]  # finishes land at different horizons
    for i, n in enumerate(lengths):
        eng.submit([(5 * i + j) % 90 + 5 for j in range(16)],
                   SamplingParams(temperature=0.0, max_new_tokens=n,
                                  ignore_eos=True),
                   rid=f"a{i}",
                   on_output=lambda o, i=i: done.setdefault(i, []).append(o))
    for _ in range(10):
        eng.step()
    with steady_state_guard() as cc:
        while eng.scheduler.has_work():
            eng.step()
    assert cc.count == 0
    lens = {i: sum(len(o.new_token_ids) for o in v) for i, v in done.items()}
    assert lens == {i: n for i, n in enumerate(lengths)}


def test_tp2_zero_leak_audit(cpu_devices):
    """Sharded traffic leaves no leaked pages / radix pins / stranded
    frames: the loadgen quiescence contract holds on a mesh."""
    eng = make_engine(ParallelConfig(tp=2), cpu_devices[:2], horizon=2)
    for k in range(3):
        gen(eng, temp=0.8 if k % 2 else 0.0, n=16)
    audit = eng.audit()
    assert audit["quiescent"] is True
    assert audit["clean"] is True
    assert audit["leaked_pages"] == 0


# ---- donation policy (explicit per-backend/per-mode table)

def test_kv_donation_policy_table():
    assert kv_donation_policy("cpu", overlap_active=True).donate_kv is False
    assert kv_donation_policy("cpu", overlap_active=False).donate_kv is True
    assert kv_donation_policy("tpu", overlap_active=True).donate_kv is True
    assert kv_donation_policy("tpu", overlap_active=False).donate_kv is True
    assert kv_donation_policy("gpu", overlap_active=True).donate_kv is True
    # unknown platforms get the accelerator rule (donate), never the CPU
    # special case
    assert kv_donation_policy("neuron", overlap_active=True).donate_kv is True
    p = kv_donation_policy("cpu", overlap_active=True, sharded=True)
    assert p.sharded and "CPU PJRT" in p.reason
    assert "sharded" in p.describe()


def test_runner_resolves_donation_policy(cpu_devices):
    on = make_engine(ParallelConfig(tp=2), cpu_devices[:2], overlap=True)
    off = make_engine(ParallelConfig(tp=2), cpu_devices[:2], overlap=False)
    assert on.runner.donation.donate_kv is False  # CPU + overlap
    assert on.runner.donation.sharded is True
    assert off.runner.donation.donate_kv is True  # sync CPU keeps aliasing


# ---- observability surfaces of the TP runner mode

def test_mesh_surfaces(cpu_devices):
    from smg_tpu.engine.flight_recorder import SCHEMA_VERSION, STEP_RECORD_KEYS

    eng = make_engine(ParallelConfig(tp=2), cpu_devices[:2])
    gen(eng, n=8)
    loads = eng.loads()
    mesh = loads["mesh"]
    assert mesh["devices"] == 2
    assert mesh["shape"]["tp"] == 2
    assert mesh["platform"] == "cpu"
    assert mesh["donate_kv"] is False  # overlap on a CPU mesh
    assert loads["step_phases"]["seconds"]["launch_dispatch"] > 0.0
    # flight ring: every step record carries the mesh device count (since v4)
    assert SCHEMA_VERSION >= 4
    assert "mesh" in STEP_RECORD_KEYS
    dump = eng.dump_flight("test")
    recs = dump["ring"]
    assert recs and all(r["mesh"] == 2 for r in recs)
    # metric gauge set at construction
    sample = list(eng.metrics.mesh_devices.collect())[0].samples[0]
    assert sample.value == 2.0


def test_single_device_mesh_surfaces():
    eng = make_engine()
    gen(eng, n=4)
    loads = eng.loads()
    assert loads["mesh"]["devices"] == 1
    dump = eng.dump_flight("test")
    assert all(r["mesh"] == 1 for r in dump["ring"])

"""Policy unit tests (reference: per-policy tests in model_gateway/src/policies/)."""

from dataclasses import dataclass, field

import pytest

from smg_tpu.policies import RequestContext, get_policy
from smg_tpu.protocols.events import BlockStored, KvEventBatch


@dataclass
class FakeWorker:
    worker_id: str
    model_id: str = "m"
    load: int = 0
    healthy: bool = True

    def is_available(self) -> bool:
        return self.healthy


def workers(n=4, **kw):
    return [FakeWorker(worker_id=f"w{i}", **kw) for i in range(n)]


def ctx(**kw):
    return RequestContext(**kw)


def test_round_robin_cycles():
    p = get_policy("round_robin")
    ws = workers(3)
    picks = [p.select_worker(ws, ctx()).worker_id for _ in range(6)]
    assert picks == ["w0", "w1", "w2", "w0", "w1", "w2"]


def test_round_robin_skips_unhealthy():
    p = get_policy("round_robin")
    ws = workers(3)
    ws[1].healthy = False
    picks = {p.select_worker(ws, ctx()).worker_id for _ in range(4)}
    assert "w1" not in picks


def test_no_workers_returns_none():
    for name in ("round_robin", "random", "least_load", "power_of_two", "cache_aware"):
        assert get_policy(name).select_worker([], ctx()) is None


def test_least_load():
    p = get_policy("least_load", seed=0)
    ws = workers(3)
    ws[0].load = 5
    ws[1].load = 1
    ws[2].load = 3
    assert p.select_worker(ws, ctx()).worker_id == "w1"


def test_power_of_two_prefers_lower_load():
    p = get_policy("power_of_two", seed=0)
    ws = workers(2)
    ws[0].load = 10
    picks = [p.select_worker(ws, ctx()).worker_id for _ in range(10)]
    assert all(x == "w1" for x in picks)


def test_manual_sticky():
    p = get_policy("manual", seed=0)
    ws = workers(4)
    a = p.select_worker(ws, ctx(routing_key="user-1")).worker_id
    for _ in range(5):
        assert p.select_worker(ws, ctx(routing_key="user-1")).worker_id == a
    p.on_worker_removed(a)
    ws = [w for w in ws if w.worker_id != a]
    b = p.select_worker(ws, ctx(routing_key="user-1")).worker_id
    assert b != a


def test_consistent_hashing_stable_and_minimal_disruption():
    p = get_policy("consistent_hashing")
    ws = workers(4)
    keys = [f"key-{i}" for i in range(50)]
    before = {k: p.select_worker(ws, ctx(routing_key=k)).worker_id for k in keys}
    after_same = {k: p.select_worker(ws, ctx(routing_key=k)).worker_id for k in keys}
    assert before == after_same
    ws2 = ws[:3]  # w3 removed
    after = {k: p.select_worker(ws2, ctx(routing_key=k)).worker_id for k in keys}
    moved = sum(1 for k in keys if before[k] != after[k] and before[k] != "w3")
    assert moved == 0  # only keys on the removed worker move


def test_prefix_hash_same_prefix_same_worker():
    p = get_policy("prefix_hash", prefix_tokens=4)
    ws = workers(4)
    a = p.select_worker(ws, ctx(token_ids=[1, 2, 3, 4, 99]))
    b = p.select_worker(ws, ctx(token_ids=[1, 2, 3, 4, 42, 77]))
    assert a.worker_id == b.worker_id


def test_bucket_separates_length_bands():
    p = get_policy("bucket", boundaries=(10,))
    ws = workers(4)
    short = p.select_worker(ws, ctx(token_ids=list(range(5))))
    long = p.select_worker(ws, ctx(token_ids=list(range(50))))
    assert short.worker_id != long.worker_id


def test_cache_aware_approx_affinity():
    p = get_policy("cache_aware", mode="approx_token", match_threshold=0.3, seed=0)
    ws = workers(4)
    prefix = list(range(100))
    first = p.select_worker(ws, ctx(token_ids=prefix))
    # same long prefix + small suffix: must stick to the same worker
    for i in range(5):
        again = p.select_worker(ws, ctx(token_ids=prefix + [200 + i]))
        assert again.worker_id == first.worker_id


def test_cache_aware_imbalance_falls_back_to_shortest_queue():
    p = get_policy("cache_aware", mode="approx_token", imbalance_abs=4, imbalance_rel=1.2, seed=0)
    ws = workers(2)
    prefix = list(range(64))
    first = p.select_worker(ws, ctx(token_ids=prefix))
    first.load = 50  # heavy imbalance toward the cached worker
    other = [w for w in ws if w is not first][0]
    pick = p.select_worker(ws, ctx(token_ids=prefix))
    assert pick.worker_id == other.worker_id


def test_cache_aware_event_mode():
    p = get_policy("cache_aware", mode="event", match_threshold=0.4, page_size=4, seed=0)
    ws = workers(3)
    tokens = list(range(16))
    # simulate w2 holding the first 3 pages of this prompt
    from smg_tpu.kv_index.positional import chain_hash

    hashes, parent = [], 0
    for i in range(3):
        parent = chain_hash(parent, tuple(tokens[i * 4 : (i + 1) * 4]))
        hashes.append(parent)
    p.apply_kv_events(
        "w2",
        KvEventBatch(
            sequence_number=1,
            events=[BlockStored(block_hashes=hashes, token_ids=tokens[:12], block_size=4)],
        ),
    )
    assert p.select_worker(ws, ctx(token_ids=tokens)).worker_id == "w2"


def test_radix_tree_prefix_match():
    from smg_tpu.kv_index import RadixTree

    t = RadixTree()
    t.insert("hello world", "w0")
    t.insert("hello there", "w1")
    m = t.prefix_match("hello world!")
    assert m["w0"] == len("hello world")
    assert m["w1"] == len("hello ")
    t.remove_worker("w0")
    m2 = t.prefix_match("hello world!")
    assert "w0" not in m2


def test_native_radix_parity_with_python():
    """Native C++ tree and Python tree agree on random workloads
    (skipped when no toolchain built the native library)."""
    import random

    from smg_tpu.kv_index import RadixTree
    from smg_tpu.kv_index.native import native_available, NativeRadixTree

    if not native_available():
        pytest.skip("native radix library not built")
    rng = random.Random(0)
    py = RadixTree()
    nat = NativeRadixTree()
    seqs = []
    for i in range(200):
        base = seqs[rng.randrange(len(seqs))][: rng.randrange(1, 20)] if seqs and rng.random() < 0.5 else []
        seq = base + [rng.randrange(64) for _ in range(rng.randrange(1, 30))]
        seqs.append(seq)
        w = f"w{rng.randrange(4)}"
        py.insert(seq, w)
        nat.insert(seq, w)
    for _ in range(100):
        probe = seqs[rng.randrange(len(seqs))] + [rng.randrange(64)]
        assert py.prefix_match(probe) == nat.prefix_match(probe)
    py.remove_worker("w1")
    nat.remove_worker("w1")
    for _ in range(50):
        probe = seqs[rng.randrange(len(seqs))]
        assert py.prefix_match(probe) == nat.prefix_match(probe)


def test_native_radix_is_rebuilt_when_its_source_is_newer(tmp_path, monkeypatch):
    """The library is a build product of csrc/radix_index.cpp: one that is
    older than the source is rebuilt before it is loaded, never loaded as
    found."""
    import os
    import shutil

    from smg_tpu.kv_index import native

    if shutil.which("make") is None or shutil.which(os.environ.get("CXX", "g++")) is None:
        pytest.skip("no make / C++ compiler here")
    csrc = tmp_path / "csrc"
    csrc.mkdir()
    for name in ("radix_index.cpp", "Makefile"):
        shutil.copy(os.path.join(native._CSRC, name), csrc / name)
    stale = csrc / "libsmg_native.so"
    stale.write_bytes(b"not a shared object")
    os.utime(stale, (1, 1))  # older than the source
    monkeypatch.setattr(native, "_CSRC", str(csrc))
    monkeypatch.setattr(native, "_LIB_PATH", str(stale))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.delenv("SMG_NATIVE_RADIX", raising=False)
    assert native._out_of_date()
    assert native._load_lib() is not None  # the junk file would not have loaded
    assert not native._out_of_date()
    assert stale.stat().st_size > 1000


# ---- routing decision records (gateway/route_observability.py consumes) ----


ALL_POLICY_NAMES = (
    "round_robin", "random", "least_load", "power_of_two", "passthrough",
    "manual", "consistent_hashing", "prefix_hash", "bucket", "cache_aware",
)


def test_every_policy_emits_schema_stable_decision():
    """select() returns (worker, RouteDecision) for EVERY registered policy,
    and to_dict() holds exactly the pinned schema keys (dashboards pin
    against DECISION_KEYS; extend, never rename)."""
    from smg_tpu.policies import DECISION_KEYS, RouteDecision

    for name in ALL_POLICY_NAMES:
        p = get_policy(name)
        ws = workers(4)
        w, d = p.select(
            ws, ctx(token_ids=list(range(32)), routing_key="k", request_id="r1")
        )
        assert w is not None, name
        assert isinstance(d, RouteDecision), name
        assert d.policy == name
        assert d.chosen == w.worker_id, name
        assert d.outcome not in ("", "none"), name
        assert d.decision_us > 0, name
        assert d.request_id == "r1"
        # candidate snapshot covers the full pool
        assert {c[0] for c in d.candidates} == {x.worker_id for x in ws}, name
        assert set(d.to_dict()) == set(DECISION_KEYS), name


@pytest.mark.parametrize("name", ALL_POLICY_NAMES)
def test_decision_no_worker_outcome(name):
    """EVERY policy labels an empty-pool selection 'no_worker' — dashboards
    alert on that outcome, so a policy stamping its own name before the
    availability check (the random/passthrough regression) hides outages."""
    p = get_policy(name)
    ws = workers(2)
    for w in ws:
        w.healthy = False
    w, d = p.select(ws, ctx(token_ids=list(range(8)), routing_key="k"))
    assert w is None, name
    assert d.chosen is None, name
    assert d.outcome == "no_worker", name


def test_cache_oblivious_policy_predicts_zero_reuse():
    """round_robin has no cache model: its implicit prediction is 0 cached
    tokens, so reconciliation measures what cache-oblivious routing leaves
    on the table."""
    p = get_policy("round_robin")
    w, d = p.select(workers(2), ctx(token_ids=list(range(16))))
    assert d.predicted_match_tokens == 0
    # text-only requests have no token-space prediction to reconcile
    _, d2 = p.select(workers(2), ctx(text="hello"))
    assert d2.predicted_match_tokens is None


def test_cache_aware_decision_prefix_hit_fields():
    p = get_policy("cache_aware", mode="approx_token", match_threshold=0.3, seed=0)
    ws = workers(4)
    prefix = list(range(100))
    first, d0 = p.select(ws, ctx(token_ids=prefix))
    assert d0.mode == "approx_token"
    assert d0.outcome in ("no_match", "below_threshold")  # cold tree
    assert d0.predicted_match_tokens in (0, None) or d0.predicted_match_tokens >= 0
    again, d = p.select(ws, ctx(token_ids=prefix + [500]))
    assert again.worker_id == first.worker_id
    assert d.outcome == "prefix_hit"
    assert d.prefix_matches[first.worker_id] == 100
    assert d.predicted_match_tokens == 100
    assert 0.9 < d.predicted_match_fraction <= 1.0
    assert d.match_threshold == 0.3
    assert d.tie_break in ("unique_best",) or d.tie_break.startswith("load_then_id")


def test_cache_aware_decision_imbalance_override():
    p = get_policy(
        "cache_aware", mode="approx_token", imbalance_abs=4, imbalance_rel=1.2, seed=0
    )
    ws = workers(2)
    prefix = list(range(64))
    first, _ = p.select(ws, ctx(token_ids=prefix))
    first.load = 50
    pick, d = p.select(ws, ctx(token_ids=prefix))
    assert pick.worker_id != first.worker_id
    assert d.imbalanced is True
    assert d.outcome == "imbalance_override"
    # the override skips the index walk: no prediction exists, so the
    # decision must NOT reconcile (an implicit 0 would corrupt the
    # per-worker index-staleness EMA with decisions the index never made)
    assert d.predicted_match_tokens is None


def test_cache_aware_decision_below_threshold():
    p = get_policy("cache_aware", mode="approx_token", match_threshold=0.9, seed=0)
    ws = workers(2)
    p.select(ws, ctx(token_ids=list(range(100))))
    # 32/132 ≈ 24% overlap < 90% threshold: match exists but is rejected
    _, d = p.select(ws, ctx(token_ids=list(range(32)) + list(range(900, 1000))))
    assert d.outcome == "below_threshold"
    assert d.predicted_match_tokens is not None


def test_cache_aware_approx_string_scales_prediction_to_tokens():
    p = get_policy("cache_aware", mode="approx_string", match_threshold=0.1, seed=0)
    ws = workers(2)
    toks = list(range(40))
    first, _ = p.select(ws, ctx(text="abcd" * 25, token_ids=toks))
    _, d = p.select(ws, ctx(text="abcd" * 25, token_ids=toks))
    if d.outcome == "prefix_hit":
        # char-space match rescaled through the tokenized length
        assert d.predicted_match_tokens == len(toks)


def test_decision_sink_receives_records_and_failures_never_break_routing():
    from smg_tpu.policies import RouteDecision

    class Sink:
        def __init__(self):
            self.records = []

        def record(self, d):
            self.records.append(d)

    p = get_policy("least_load", seed=0)
    sink = Sink()
    p._decision_sink = sink
    w, d = p.select(workers(3), ctx())
    assert sink.records == [d]

    class BrokenSink:
        def record(self, d):
            raise RuntimeError("observability must never fail routing")

    p._decision_sink = BrokenSink()
    w2, _ = p.select(workers(3), ctx())
    assert w2 is not None

"""CLI flag surface + cross-field config validation (VERDICT r4 #7:
~140-flag reference validator parity for the in-tree-meaningful groups,
``config/validation.rs`` analog)."""

import pytest

from smg_tpu.cli import build_parser
from smg_tpu.config.validation import (
    ConfigError,
    raise_on_errors,
    validate_cli_args,
)


def _args(*extra):
    return build_parser().parse_args(["launch", *extra])


def _errors(args):
    return [i for i in validate_cli_args(args) if i.severity == "error"]


def _warns(args):
    return [i for i in validate_cli_args(args) if i.severity == "warn"]


def test_default_launch_args_validate_clean():
    args = _args()
    assert _errors(args) == []


def test_flag_surface_breadth():
    """The reference exposes ~140 flags; the in-tree-meaningful groups must
    be present (spot the group representatives)."""
    args = _args()
    for field in [
        "host", "port", "health_check_port", "policy", "cache_threshold",
        "balance_abs_threshold", "balance_rel_threshold", "max_tree_size",
        "block_size", "prefix_token_count", "dp_aware", "enable_igw",
        "retry_max_retries", "retry_initial_backoff_ms", "retry_max_backoff_ms",
        "disable_retries", "cb_failure_threshold", "cb_success_threshold",
        "cb_timeout_duration_secs", "disable_circuit_breaker",
        "health_check_interval_secs", "health_check_timeout_secs",
        "health_failure_threshold", "health_success_threshold",
        "disable_health_check", "worker_startup_timeout_secs",
        "priority_scheduler_enabled", "priority_slots",
        "rate_limit_tokens_per_second", "rate_limit_burst",
        "api_keys", "jwt_secret", "jwt_jwks_uri", "jwt_issuer", "jwt_audience",
        "trust_tenant_header", "tenant_header_name",
        "service_discovery", "service_discovery_namespace", "selectors",
        "prefill_selectors", "decode_selectors", "service_discovery_port",
        "tls_cert_path", "tls_key_path", "max_payload_size",
        "request_timeout_secs", "cors_allowed_origins", "request_id_headers",
        "harmony", "reasoning_parser", "tool_call_parser", "mcp_config_path",
        "log_json", "prometheus_host", "mesh_port", "mesh_seeds",
        "storage", "otel_endpoint", "kv_connector", "provider_config",
    ]:
        assert hasattr(args, field), f"missing flag field {field}"


def test_serve_engine_flags():
    p = build_parser()
    args = p.parse_args([
        "serve", "--model-preset", "tiny", "--speculative",
        "--draft-model-preset", "tiny", "--tp", "2",
    ])
    assert args.draft_model_preset == "tiny" and args.speculative


# ---- cross-field rules (one test per rule family) ----


def test_tls_needs_both_halves():
    assert any("tls" in str(i) for i in _errors(_args("--tls-cert-path", "/c.pem")))
    assert _errors(_args("--tls-cert-path", "/c.pem", "--tls-key-path", "/k.pem")) == []


def test_probe_port_must_differ():
    bad = _args("--port", "30000", "--health-check-port", "30000")
    assert any("probe port" in i.message for i in _errors(bad))
    ok = _args("--port", "30000", "--health-check-port", "30100")
    assert _errors(ok) == []


def test_retry_backoff_ordering():
    bad = _args("--retry-initial-backoff-ms", "5000",
                "--retry-max-backoff-ms", "1000")
    assert any("backoff" in i.message for i in _errors(bad))


def test_breaker_and_health_thresholds_positive():
    assert _errors(_args("--cb-failure-threshold", "0"))
    assert _errors(_args("--health-success-threshold", "0"))


def test_health_timeout_vs_interval_warns():
    w = _warns(_args("--health-check-timeout-secs", "10",
                     "--health-check-interval-secs", "5"))
    assert any("pile up" in i.message for i in w)


def test_no_retries_no_breaker_warns():
    w = _warns(_args("--disable-retries", "--disable-circuit-breaker"))
    assert any("transient" in i.message for i in w)


def test_cache_threshold_range_and_policy_scope():
    assert _errors(_args("--cache-threshold", "1.5"))
    w = _warns(_args("--policy", "round_robin", "--cache-threshold", "0.7"))
    assert any("ignored by policy" in i.message for i in w)


def test_rate_limit_rules():
    assert _errors(_args("--rate-limit-tokens-per-second", "-1"))
    w = _warns(_args("--rate-limit-tokens-per-second", "100",
                     "--rate-limit-burst", "10"))
    assert any("burst" in i.message for i in w)


def test_api_key_spec_and_jwt_claims():
    assert _errors(_args("--api-key", ":tenant"))
    w = _warns(_args("--jwt-issuer", "https://idp"))
    assert any("jwks" in i.message.lower() for i in w)


def test_trust_tenant_header_without_auth_warns():
    w = _warns(_args("--trust-tenant-header"))
    assert any("redundant" in i.message for i in w)


def test_harmony_overrides_parsers_warns():
    w = _warns(_args("--harmony", "on", "--reasoning-parser", "deepseek_r1"))
    assert any("harmony" in i.message for i in w)


def test_selectors_without_discovery_warn():
    w = _warns(_args("--selector", "app=x"))
    assert any("service-discovery" in i.message for i in w)


def test_draft_model_requires_speculative():
    p = build_parser()
    args = p.parse_args(["serve", "--model-preset", "tiny",
                         "--draft-model-preset", "tiny"])
    assert any("speculative" in i.message for i in _errors(args))


def test_mesh_tls_all_or_nothing():
    bad = _args("--mesh-port", "7946", "--mesh-tls-cert", "/c.pem")
    assert any("mTLS" in i.message for i in _errors(bad))


def test_pd_roles_both_required_still_enforced():
    bad = _args("--prefill-worker", "http://p:1")
    assert any("PD" in i.message for i in _errors(bad))


def test_raise_on_errors_collects_all():
    bad = _args("--tls-cert-path", "/c.pem", "--cb-failure-threshold", "0")
    with pytest.raises(ConfigError) as ei:
        raise_on_errors(validate_cli_args(bad))
    assert len(ei.value.issues) >= 2


def test_dp_aware_default_preserves_min_token():
    """--dp-aware defaults ON: restarting an existing deployment must not
    silently lose min-token DP replica pinning."""
    assert _args().dp_aware is True
    assert _args("--no-dp-aware").dp_aware is False


def test_request_timeout_and_cors_middleware():
    """--request-timeout-secs cuts hung handlers; --cors-allowed-origins
    emits CORS headers + preflight."""
    import asyncio
    import threading

    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from smg_tpu.gateway.server import AppContext, build_app

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(timeout=60)

    ctx = AppContext(policy="round_robin", request_timeout_secs=0.2,
                     cors_allowed_origins=["https://app.example"])

    async def go():
        app = build_app(ctx)

        async def slow(request):
            await asyncio.sleep(5)
            return web.json_response({})

        app.router.add_get("/slow-test", slow)
        tc = TestClient(TestServer(app))
        await tc.start_server()
        r1 = await tc.get("/slow-test")
        out_timeout = (r1.status, (await r1.json())["error"]["type"])
        r2 = await tc.get("/health", headers={"Origin": "https://app.example"})
        cors = r2.headers.get("Access-Control-Allow-Origin")
        r3 = await tc.options("/v1/models",
                              headers={"Origin": "https://app.example"})
        preflight = r3.status
        r4 = await tc.get("/health", headers={"Origin": "https://evil.example"})
        no_cors = r4.headers.get("Access-Control-Allow-Origin")
        await tc.close()
        return out_timeout, cors, preflight, no_cors

    out_timeout, cors, preflight, no_cors = run(go())
    loop.call_soon_threadsafe(loop.stop)
    assert out_timeout == (408, "timeout_error")
    assert cors == "https://app.example"
    assert preflight == 204
    assert no_cors is None


def test_tenant_trust_is_per_context():
    """Tenant-header trust lives on AppContext (not module globals): one
    authed gateway and one open gateway in the same process keep their own
    settings."""
    from smg_tpu.gateway.auth import AuthConfig, Principal
    from smg_tpu.gateway.server import AppContext

    open_ctx = AppContext(policy="round_robin")
    authed = AppContext(
        policy="round_robin",
        auth_config=AuthConfig(enabled=True,
                               api_keys={"k": Principal(id="u", tenant="t1")}),
    )
    assert open_ctx.trust_tenant_header is True
    assert authed.trust_tenant_header is False
    override = AppContext(policy="round_robin", trust_tenant_header=True,
                          auth_config=AuthConfig(enabled=True))
    assert override.trust_tenant_header is True


# ---- tensor-parallel mesh flags (--tensor-parallel-size / --mesh-shape) ----


def _serve(*extra):
    return build_parser().parse_args(
        ["serve", "--model-preset", "tiny", *extra]
    )


def test_tensor_parallel_size_alias():
    """--tensor-parallel-size is the same flag as --tp (reference naming)."""
    assert _serve("--tensor-parallel-size", "4").tp == 4
    assert _serve("--tp", "4").tp == 4


def test_mesh_shape_parses_over_base():
    from smg_tpu.engine.config import ParallelConfig

    p = ParallelConfig.from_spec("dp=2,tp=4")
    assert (p.dp, p.tp, p.sp, p.ep, p.pp) == (2, 4, 1, 1, 1)
    assert p.world_size == 8
    # base values survive for unnamed axes
    p2 = ParallelConfig.from_spec("tp=2", base=ParallelConfig(pp=2))
    assert (p2.tp, p2.pp) == (2, 2)


@pytest.mark.parametrize("bad", ["xx=2", "tp", "tp=zero", "tp=0", "tp=-1",
                                 "tp=2,tp=4"])
def test_mesh_shape_rejects_malformed(bad):
    from smg_tpu.engine.config import ParallelConfig

    with pytest.raises(ValueError):
        ParallelConfig.from_spec(bad)


def test_mesh_shape_flag_conflict_is_error():
    # conflicting axis sizes between --mesh-shape and a per-axis flag
    bad = _serve("--mesh-shape", "tp=4", "--tp", "2")
    assert any("mesh_shape" in i.field for i in _errors(bad))
    # agreement (or the per-axis flag left at its default) is fine
    assert _errors(_serve("--mesh-shape", "tp=4", "--tp", "4")) == []
    assert _errors(_serve("--mesh-shape", "dp=2,tp=4")) == []
    # axes the spec does NOT name merge from the per-axis flags at launch —
    # never a conflict
    assert _errors(_serve("--mesh-shape", "tp=4", "--dp", "2")) == []
    # malformed string surfaces as a startup error, not a trace-time one
    assert any("mesh_shape" in i.field
               for i in _errors(_serve("--mesh-shape", "bogus=2")))


def test_launch_pins_its_process_to_the_cpu_platform(monkeypatch):
    """One process per host owns the chips (the worker).  `launch` runs
    jax.numpy for image preprocessing, so it must put itself on the CPU
    platform, whatever it inherits, before anything imports jax."""
    import os

    from smg_tpu.gateway import launch

    seen = {}

    async def fake_gateway(args):
        seen["platforms"] = os.environ.get("JAX_PLATFORMS")
        return 0

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(launch, "_run_gateway", fake_gateway)
    args = build_parser().parse_args(["launch", "--port", "0"])
    assert launch.run_command(args) == 0
    assert seen["platforms"] == "cpu"
    # `serve` owns the chips itself and keeps what it was given
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    args = build_parser().parse_args(["serve", "--model-preset", "tiny"])
    assert launch.run_command(args) == 0
    assert seen["platforms"] == "tpu"

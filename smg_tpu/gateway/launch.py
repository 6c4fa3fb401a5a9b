"""Process assembly for the CLI: launch (gateway), serve (engine+gateway),
worker (bare engine behind gRPC).

Reference: ``server.rs startup()`` orchestration (SURVEY.md §3.1) and the
Python wrapper's serve flow (``bindings/python/src/smg/serve.py``).
"""

from __future__ import annotations

import asyncio

from aiohttp import web

from smg_tpu.utils import get_logger

logger = get_logger("gateway.launch")


def build_engine_from_args(args):
    from smg_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from smg_tpu.engine.engine import Engine
    from smg_tpu.models.config import PRESETS, ModelConfig

    if args.model_path:
        model = ModelConfig.from_pretrained(args.model_path)
    elif args.model_preset:
        model = PRESETS[args.model_preset]()
    else:
        raise SystemExit("need --model-path or --model-preset")

    draft_model = None
    if getattr(args, "draft_model_path", None):
        draft_model = ModelConfig.from_pretrained(args.draft_model_path)
    elif getattr(args, "draft_model_preset", None):
        draft_model = PRESETS[args.draft_model_preset]()

    parallel = ParallelConfig(
        dp=args.dp, tp=args.tp,
        pp=getattr(args, "pp", 1), sp=getattr(args, "sp", 1),
        ep=getattr(args, "ep", 1),
    )
    if getattr(args, "mesh_shape", None):
        # --mesh-shape names the topology in one string; validate_cli_args
        # already rejected conflicts with differing per-axis flags
        parallel = ParallelConfig.from_spec(args.mesh_shape, base=parallel)
    if parallel.world_size > 1:
        import jax

        n_dev = len(jax.devices())
        if n_dev < parallel.world_size:
            raise SystemExit(
                f"mesh {parallel.axis_sizes()} needs {parallel.world_size} "
                f"devices, found {n_dev} (CPU dryruns: set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N)"
            )
        logger.info(
            "parallel mesh: %s over %d devices",
            parallel.axis_sizes(), parallel.world_size,
        )

    cfg = EngineConfig(
        model=model,
        model_path=args.model_path,
        tokenizer_path=args.tokenizer_path or args.model_path,
        parallel=parallel,
        cache=CacheConfig(
            page_size=args.page_size,
            # KV follows the compute dtype unless the operator overrides
            # (bf16 cache under f32 compute would silently mix precisions)
            dtype=getattr(args, "kv_dtype", None) or getattr(args, "dtype", "bfloat16"),
        ),
        scheduler=SchedulerConfig(
            max_batch_size=args.max_batch_size, max_seq_len=args.max_seq_len,
            max_prefill_tokens=getattr(args, "max_prefill_tokens", 4096),
            prefill_mix_policy=getattr(args, "prefill_mix_policy", "stall-free"),
            decode_horizon=getattr(args, "decode_horizon", 1),
            adaptive_horizon=getattr(args, "adaptive_horizon", "off") == "on",
            decode_horizon_max=getattr(args, "decode_horizon_max", 0),
            speculative=getattr(args, "speculative", False),
            spec_max_draft=getattr(args, "spec_max_draft", 8),
            speculative_tier=getattr(args, "speculative_tier", "auto"),
            overlap_schedule=getattr(args, "overlap_schedule", "on") != "off",
            max_queued_requests=getattr(args, "max_queued_requests", 0),
            max_queued_tokens=getattr(args, "max_queued_tokens", 0),
        ),
        model_id=args.model_path or args.model_preset,
        dtype=getattr(args, "dtype", "bfloat16"),
        draft_model=draft_model,
        metrics_window_secs=getattr(args, "metrics_window_secs", 30.0),
        device_metrics_interval_secs=getattr(
            args, "device_metrics_interval_secs", 10.0
        ),
        step_watchdog_secs=getattr(args, "step_watchdog_secs", 0.0),
        flight_recorder=getattr(args, "flight_recorder", "on") != "off",
        flight_ring_size=getattr(args, "flight_ring_size", 256),
        flight_dump_dir=getattr(args, "flight_dump_dir", None),
        flight_dump_min_interval_secs=getattr(
            args, "flight_dump_min_interval_secs", 5.0
        ),
    )
    params = None
    vision_params = None
    if args.model_path:
        from smg_tpu.models.weights import load_params, load_vision_params

        params = load_params(cfg)
        if model.vision is not None:
            vision_params = load_vision_params(cfg)
    if cfg.tokenizer_path:
        tokenizer = load_tokenizer(cfg.tokenizer_path)
    else:
        # preset models (tests/bench): a vocab-matched mock keeps worker-side
        # detokenize/stop/constrained paths live and the GetTokenizer bundle
        # meaningful
        from smg_tpu.tokenizer import MockTokenizer

        tokenizer = MockTokenizer(
            vocab_size=model.vocab_size,
            eos_token_id=(model.eos_token_ids or (0,))[0],
            bos_token_id=model.bos_token_id if model.bos_token_id is not None else 1,
        )
    return Engine(cfg, params=params, tokenizer=tokenizer,
                  vision_params=vision_params)


def load_tokenizer(path: str | None):
    if path is None:
        from smg_tpu.tokenizer import MockTokenizer

        logger.warning("no tokenizer path; using MockTokenizer")
        return MockTokenizer()
    from smg_tpu.tokenizer.hf import HFTokenizer

    return HFTokenizer(path)


def run_command(args) -> int:
    if args.command == "worker":
        return run_worker(args)
    if args.command == "launch":
        # A host's chips belong to one process, its worker.  The gateway
        # runs jax.numpy for image preprocessing (multimodal/image.py), and
        # on a TPU host the first such call would reach for a chip the
        # worker owns: keep this process on the CPU platform, before
        # anything here can import jax.
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
        logger.info("gateway process pinned to the CPU platform")
    return asyncio.run(_run_gateway(args))


def run_worker(args) -> int:
    from smg_tpu.rpc.server import serve_worker

    engine = build_engine_from_args(args)
    engine.warmup()  # before the port binds: fail here, not per request
    engine.start()
    return serve_worker(engine, port=args.grpc_port)


async def _run_gateway(args) -> int:
    from smg_tpu.gateway.server import AppContext, build_app
    from smg_tpu.gateway.workers import Worker

    from smg_tpu.gateway.router import RouterConfig

    # ---- flag groups -> sub-configs (reference: main.rs:157-816 flag
    # groups through RouterConfig construction) ----
    harmony_flag = {None: None, "auto": None, "on": True, "off": False}[
        getattr(args, "harmony", None)
    ]
    router_config = RouterConfig(
        kv_connector=getattr(args, "kv_connector", "auto"),
        max_retries=(0 if getattr(args, "disable_retries", False)
                     else getattr(args, "retry_max_retries", 3)),
        retry_backoff_base=getattr(args, "retry_initial_backoff_ms", 100) / 1e3,
        retry_backoff_max=getattr(args, "retry_max_backoff_ms", 2000) / 1e3,
        reasoning_parser=getattr(args, "reasoning_parser", None),
        tool_parser=getattr(args, "tool_call_parser", None),
        harmony=harmony_flag,
        # min-token replica pinning is the long-standing default;
        # --no-dp-aware opts into worker-local balancing
        dp_rank_policy=("dp_min_token" if getattr(args, "dp_aware", True)
                       else "dp_passthrough"),
        # the remaining request budget rides every worker dispatch so the
        # engine expires abandoned work instead of decoding into the void
        request_timeout_secs=getattr(args, "request_timeout_secs", None),
    )
    policy_kwargs = {}
    if args.policy == "cache_aware":
        policy_kwargs = {
            "match_threshold": getattr(args, "cache_threshold", 0.5),
            "imbalance_abs": getattr(args, "balance_abs_threshold", 32),
            "imbalance_rel": getattr(args, "balance_rel_threshold", 1.5),
            "max_tree_size": getattr(args, "max_tree_size", 2**20),
            "page_size": getattr(args, "block_size", 16),
        }
    elif args.policy == "prefix_hash":
        policy_kwargs = {
            "prefix_tokens": getattr(args, "prefix_token_count", 256),
        }
    auth_config = None
    api_keys = getattr(args, "api_keys", [])
    if api_keys or getattr(args, "jwt_secret", None) or getattr(args, "jwt_jwks_uri", None):
        from smg_tpu.gateway.auth import AuthConfig, JwksVerifier, Principal

        keys = {}
        for spec in api_keys:
            key, _, rest = spec.partition(":")
            tenant, _, role = rest.partition(":")
            keys[key] = Principal(
                id=f"key-{key[:6]}", tenant=tenant or "default",
                roles=(role,) if role else ("user",),
            )
        jwks = None
        if getattr(args, "jwt_jwks_uri", None):
            uri = args.jwt_jwks_uri

            def _fetch_jwks(uri=uri):
                import json as _json
                import urllib.request

                with urllib.request.urlopen(uri, timeout=10) as r:
                    return _json.loads(r.read())

            jwks = JwksVerifier(
                _fetch_jwks,
                issuer=getattr(args, "jwt_issuer", None),
                audience=getattr(args, "jwt_audience", None),
            )
        auth_config = AuthConfig(
            enabled=True, api_keys=keys,
            jwt_secret=getattr(args, "jwt_secret", None), jwks=jwks,
        )
    rate_limit_config = None
    if getattr(args, "rate_limit_tokens_per_second", 0.0):
        from smg_tpu.gateway.rate_limit import RateLimitConfig

        rate_limit_config = RateLimitConfig(
            capacity=getattr(args, "rate_limit_burst", 256.0),
            refill_per_sec=args.rate_limit_tokens_per_second,
            max_concurrent=args.max_concurrent_requests,
        )
    priority_config = None
    if getattr(args, "priority_scheduler_enabled", False):
        from smg_tpu.gateway.priority import PriorityConfig

        priority_config = PriorityConfig(slots=getattr(args, "priority_slots", 256))
    from smg_tpu.gateway.health import HealthConfig

    # disable = an interval no deployment outlives (the monitor machinery
    # stays constructed so /health handlers keep working)
    health_config = HealthConfig(
        interval_secs=(1e9 if getattr(args, "disable_health_check", False)
                       else getattr(args, "health_check_interval_secs", 10.0)),
        timeout_secs=getattr(args, "health_check_timeout_secs", 5.0),
        failure_threshold=getattr(args, "health_failure_threshold", 3),
        success_threshold=getattr(args, "health_success_threshold", 2),
    )
    # circuit-breaker knobs are PER-CONTEXT (two gateways in one process
    # keep their own settings): applied to workers as the registry adds them
    cb_config = (
        (10**9 if getattr(args, "disable_circuit_breaker", False)
         else getattr(args, "cb_failure_threshold", 5)),
        getattr(args, "cb_success_threshold", 2),
        getattr(args, "cb_timeout_duration_secs", 30.0),
    )
    slo_specs = None
    if getattr(args, "slo_spec", None):
        from smg_tpu.gateway.slo_enforcement import load_slo_specs

        # file read off the serving loop, like --mcp-config-path below; a
        # malformed spec must fail startup loudly, not at first evaluation
        raw_slo = await asyncio.to_thread(load_slo_specs, args.slo_spec)
        slo_specs = raw_slo
        logger.info("SLO enforcement on: %s", [s.name for s in slo_specs])
    ctx = AppContext(
        policy=args.policy,
        router_config=router_config,
        max_concurrent_requests=args.max_concurrent_requests,
        policy_kwargs=policy_kwargs,
        auth_config=auth_config,
        rate_limit_config=rate_limit_config,
        priority_config=priority_config,
        health_config=health_config,
        storage=getattr(args, "storage", None),
        otel_endpoint=getattr(args, "otel_endpoint", None),
        otel_service_name=getattr(args, "otel_service_name", "smg-tpu"),
        request_id_headers=list(getattr(args, "request_id_headers", []) or []),
        tenant_header=getattr(args, "tenant_header_name", "X-Tenant-Id"),
        # without auth the tenant header is all there is; with auth it must
        # be explicitly trusted
        trust_tenant_header=(getattr(args, "trust_tenant_header", False)
                             or auth_config is None),
        request_timeout_secs=getattr(args, "request_timeout_secs", None),
        cors_allowed_origins=list(getattr(args, "cors_allowed_origins", []) or []),
        circuit_breaker_config=cb_config,
        slo_specs=slo_specs,
    )
    if getattr(args, "mcp_config_path", None):
        import json as _json
        from pathlib import Path as _Path

        from smg_tpu.mcp import HttpMcpServer

        # startup runs on the serving loop already (aiohttp runner): config
        # reads go through a thread so a cold NFS/volume mount can't wedge
        # signal handling or health probes registered before this point
        raw = await asyncio.to_thread(_Path(args.mcp_config_path).read_text)
        for spec in _json.loads(raw):
            ctx.mcp.add(HttpMcpServer(
                name=spec.get("name", spec["url"]), url=spec["url"],
                headers=spec.get("headers"),
            ))
    if getattr(args, "provider_config", None):
        ctx.providers.load_config(args.provider_config)
    if getattr(args, "mm_transport", None):
        # process-wide transport policy for every gRPC worker client
        # (reference: --multimodal-* flags, main.rs:319-328)
        from smg_tpu.rpc.client import GrpcWorkerClient

        GrpcWorkerClient.mm_transport = args.mm_transport
        GrpcWorkerClient.mm_shm_min_bytes = getattr(
            args, "mm_shm_min_bytes", 1 << 20
        )
    if getattr(args, "worker_stream_idle_timeout_secs", None) is not None:
        # process-wide per-chunk idle bound for gRPC generate streams
        # (0 disables); same class-attr pattern as mm_transport above
        from smg_tpu.rpc.client import GrpcWorkerClient

        GrpcWorkerClient.idle_timeout_secs = (
            args.worker_stream_idle_timeout_secs or None
        )
    if getattr(args, "plugins", None):
        ctx.load_plugins(args.plugins,
                         fail_open=not getattr(args, "plugin_fail_closed", False))

    if args.command == "serve":
        from smg_tpu.gateway.worker_client import InProcWorkerClient

        engine = build_engine_from_args(args)
        # before the port binds: a server that cannot run its own largest
        # programs must not report healthy.  Blocking the loop is fine here,
        # nothing is listening yet.
        engine.warmup()
        tokenizer = load_tokenizer(args.tokenizer_path or args.model_path)
        ctx.tokenizers.register(engine.config.model_id, tokenizer, default=True)
        client = InProcWorkerClient(engine)
        client.drain_timeout_secs = getattr(
            args, "engine_drain_timeout_secs", 10.0
        )
        ctx.registry.add(
            Worker(
                worker_id="inproc-0", client=client, model_id=engine.config.model_id,
                page_size=engine.config.cache.page_size,
            )
        )
    explicit_tok = getattr(args, "gateway_tokenizer_path", None) or getattr(
        args, "tokenizer_path", None
    )
    if args.command == "launch" and explicit_tok:
        tokenizer = load_tokenizer(explicit_tok)
        ctx.tokenizers.register("default", tokenizer, default=True)
    # an operator-configured tokenizer wins over worker bundles outright
    fetch_bundles = not explicit_tok

    from smg_tpu.gateway.workers import WorkerType

    role_urls = (
        [(u, WorkerType.REGULAR) for u in getattr(args, "workers", [])]
        + [(u, WorkerType.PREFILL) for u in getattr(args, "prefill_workers", [])]
        + [(u, WorkerType.DECODE) for u in getattr(args, "decode_workers", [])]
    )
    async def _register_worker(url: str, wtype, timeout: float) -> None:
        """Register one worker through the registration workflow (reference:
        registration rides the job queue + workflow engine,
        server.rs:1107-1135) — model_info retries with backoff so a worker
        still starting up must not kill (or serialize) the gateway, and a
        failed registration stays resumable via POST /workflows/{id}/resume.
        """
        from smg_tpu.gateway.registration import WORKER_REGISTRATION

        iid = await ctx.workflows.start(WORKER_REGISTRATION, {
            "url": url,
            "worker_type": wtype.value,
            "skip_tokenizer": not fetch_bundles,
        })
        inst = await ctx.workflows.wait(iid, timeout=timeout)
        if inst.status.value != "completed":
            logger.error(
                "worker %s registration %s at startup (%s); resumable as %s",
                url, inst.status.value, inst.error, iid,
            )

    if role_urls:
        # the wait must outlast the workflow's model_info retry budget
        # (~36s of backoff for a cold-booting worker) or a late success
        # races the mock-fallback default below
        budget = getattr(args, "worker_startup_timeout_secs", 75.0)
        await asyncio.gather(
            *(_register_worker(url, wtype, budget) for url, wtype in role_urls)
        )

    discoveries = []
    if getattr(args, "service_discovery", False):
        from smg_tpu.gateway.discovery import DiscoveryConfig, ServiceDiscovery

        ns = getattr(args, "service_discovery_namespace", None) or "default"
        port = getattr(args, "service_discovery_port", 30001)
        # one watcher per role selector group: pods matched by a role
        # selector default to that role even without a smg.ai/role label
        groups = [(",".join(getattr(args, "selectors", [])) or "app=smg-worker",
                   "regular")]
        if getattr(args, "prefill_selectors", []):
            groups.append((",".join(args.prefill_selectors), "prefill"))
        if getattr(args, "decode_selectors", []):
            groups.append((",".join(args.decode_selectors), "decode"))
        for selector, role in groups:
            d = ServiceDiscovery(
                ctx.registry,
                DiscoveryConfig(namespace=ns, selector=selector,
                                default_port=port, default_role=role),
            )
            d.start()
            discoveries.append(d)
            logger.info("k8s service discovery on (selector %s, role %s)",
                        selector, role)

    if args.command == "launch" and ctx.tokenizers.get(None) is None:
        # nothing explicit and no worker handed one over: mock fallback.
        # Marked so a worker tokenizer arriving later (resumed/async
        # registration) promotes itself to default over the mock.
        fallback = load_tokenizer(None)
        fallback._smg_fallback = True
        ctx.tokenizers.register("default", fallback, default=True)

    mesh_node = None
    if getattr(args, "mesh_port", None) is not None:
        from smg_tpu.mesh import GossipConfig, GossipNode
        from smg_tpu.mesh.adapters import TreeSyncAdapter, WorkerSyncAdapter

        mesh_node = GossipNode(
            GossipConfig(host="0.0.0.0", port=args.mesh_port,
                         seeds=list(getattr(args, "mesh_seeds", [])),
                         tls_cert_file=getattr(args, "mesh_tls_cert", None),
                         tls_key_file=getattr(args, "mesh_tls_key", None),
                         tls_ca_file=getattr(args, "mesh_tls_ca", None))
        )
        await mesh_node.start()
        WorkerSyncAdapter(ctx.registry, mesh_node.state)
        TreeSyncAdapter(ctx.policies, mesh_node.state)
        logger.info("HA mesh enabled on port %d", args.mesh_port)

    app = build_app(ctx, client_max_size=getattr(args, "max_payload_size",
                                                 256 * 2**20))
    runner = web.AppRunner(app)
    await runner.setup()
    ssl_ctx = None
    if getattr(args, "tls_cert_path", None):
        import ssl

        ssl_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ssl_ctx.load_cert_chain(args.tls_cert_path, args.tls_key_path)
    site = web.TCPSite(runner, args.host, args.port, ssl_context=ssl_ctx)
    await site.start()
    logger.info("gateway listening on %s:%d%s", args.host, args.port,
                " (TLS)" if ssl_ctx else "")
    probe_runner = None
    if getattr(args, "health_check_port", None):
        # dedicated probe listener: /health /liveness /readiness stay
        # reachable even when the main port saturates (reference:
        # --health-check-port's isolated probe runtime).  PROBE-ONLY app:
        # the full API must not leak onto an unauthenticated/plaintext port
        from smg_tpu.gateway.server import h_health, h_readiness

        papp = web.Application()
        papp["ctx"] = ctx
        papp.router.add_get("/health", h_health)
        papp.router.add_get("/liveness", h_health)
        papp.router.add_get("/readiness", h_readiness)
        probe_runner = web.AppRunner(papp)
        await probe_runner.setup()
        await web.TCPSite(probe_runner, args.host, args.health_check_port).start()
        logger.info("probe listener on %s:%d", args.host, args.health_check_port)
    metrics_runner = None
    if getattr(args, "prometheus_port", None):
        # metrics-only listener (scrapers shouldn't reach inference routes)
        from smg_tpu.gateway.server import h_metrics

        mapp = web.Application()
        mapp["ctx"] = ctx
        mapp.router.add_get("/metrics", h_metrics)
        metrics_runner = web.AppRunner(mapp)
        await metrics_runner.setup()
        await web.TCPSite(
            metrics_runner, getattr(args, "prometheus_host", "0.0.0.0"),
            args.prometheus_port,
        ).start()
        logger.info("prometheus exporter on %s:%d",
                    getattr(args, "prometheus_host", "0.0.0.0"),
                    args.prometheus_port)
    # graceful shutdown (reference: the drain-settle path on SIGTERM,
    # main.rs:550-556): the signal stops SELECTION first (workers flip to
    # draining so health/readiness report it), then every worker client is
    # closed — for in-proc engines that is engine.stop(drain=True): queued
    # requests get terminal aborts and running lanes finish within the
    # --engine-drain-timeout-secs budget before the process exits
    stop_event = asyncio.Event()
    loop = asyncio.get_running_loop()
    try:
        import signal as _signal

        for _sig in (_signal.SIGTERM, _signal.SIGINT):
            loop.add_signal_handler(_sig, stop_event.set)
    except (NotImplementedError, RuntimeError, ValueError):
        pass  # non-main thread / platform without signal support
    try:
        await stop_event.wait()
        logger.info("shutdown signal received; draining workers")
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        for d in discoveries:
            await d.aclose()
        if mesh_node is not None:
            await mesh_node.stop()
        for w in ctx.registry.list():
            w.draining = True  # no new selections while streams settle
        for w in ctx.registry.list():
            try:
                await w.client.close()
            except Exception:
                logger.exception("worker %s close failed during shutdown",
                                 w.worker_id)
        if metrics_runner is not None:
            await metrics_runner.cleanup()
        if probe_runner is not None:
            await probe_runner.cleanup()
        await runner.cleanup()
    return 0

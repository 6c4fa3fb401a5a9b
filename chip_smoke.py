#!/usr/bin/env python3
"""chip_smoke.py: does the serving path still start, and compute the right
thing, on a TPU?

    python3 chip_smoke.py            # every phase, on the chips JAX finds

Drives the system once through the entry points a user would call
(``python -m smg_tpu.cli serve|worker|launch`` over HTTP) at the full width of
the ``llama3.2-1b`` preset, random weights from a seed, and checks what comes
out by the repository's own means: finish reasons and token counts per
request, the engine's failure counters and leak audit, launch counts per
attention implementation, device memory in use, and logits of both attention
implementations against the dense float32 forward of the same tokens.  It is
not a benchmark: it states no rate and no latency, only set-up time.

Phases, each one (group of) child process(es) that has exited before the next
starts, because a chip belongs to one process at a time:

  a  one chip, ``serve``: four waves of streaming chat requests (below)
  b  one chip, numbers: prefill and decode logits, XLA and Pallas, vs dense
  c  one chip, two processes: ``worker`` holds the chip, ``launch`` stays on
     the CPU in front of it
  d  four chips: ``serve --model-preset llama3-8b --mesh-shape tp=4``;
     reported as not run when JAX sees fewer than four chips

This parent process never imports JAX (it would take the chip from its
children).  Without a TPU it exits non-zero and prints no result: nothing here
falls back to the CPU.  ``--rehearsal`` is the one way onto the CPU: the same
code at the ``tiny`` preset with kernels in interpret mode, to catch typos
before chip time is spent; it says ``"rehearsal": true`` in its result and
cannot be reached through the environment.

The last line of standard output is the verdict and nothing else,

    {"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}

with the device as JAX reports it.  The line above it (``chip_smoke: report:
{...}``) and ``<out>/report.json`` carry the rest: jax, jaxlib and libtpu
versions, a verdict and set-up time per phase, and which radix index the
``cache_aware`` policy got.  The exit code is 0 only if every phase that ran
passed and phases a, b and c all ran.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.parse

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 21

# Prompt sizes in tokens.  They are chosen so that each wave reaches the
# program it is there for with as few distinct compiled shapes as possible
# (each one costs ~20 s of XLA compilation at these widths):
#   short   wave i: 8 at once; grouped prefill, decode bucket 8
#   prefix  wave ii: the same prefix twice, different tails; the second must
#           hit the radix cache and prefill behind a live prefix
#   long    wave iii: budget + budget/2, so a full chunk goes through
#           prefill_extend and the final chunk is a solo prefill over a
#           budget-long prefix (the Pallas prefill kernel on one chip)
#   many    wave iv: budget/2 each, so two fill a step exactly and every step
#           launches the same grouped-prefill shape, and the decode kernel
#           (which one chip picks at every shape) runs its widest programs
FULL = {"short": 32, "prefix": 1500, "tail": 32, "long": 6144,
        "many": 2048, "many_n": 40, "many_n_mesh": 12, "out": 16, "many_out": 32}
# the rehearsal serves tiny with --max-seq-len 1024 --max-prefill-tokens 256
REHEARSAL = {"short": 32, "prefix": 150, "tail": 20, "long": 384,
             "many": 128, "many_n": 12, "many_n_mesh": 12, "out": 8, "many_out": 8}
# the mock tokenizer's chat template adds "[user]" and "[assistant]"
TEMPLATE_TOKENS = 2

# Phase b: largest |logit difference| allowed between the serving path and
# the dense float32 forward, in units of the reference logits' standard
# deviation.  The serving path rounds activations and the cache to bfloat16
# in every one of 16 layers: on a v5e that alone measured 0.095 to 0.119 under
# XLA attention and 0.095 to 0.114 under the kernels, while one wrong page out
# of a sequence's 44 (the control in _numbers_child) measured 1.2 under either.
# 0.25 is twice the first and a fifth of the second.
LOGIT_TOLERANCE = 0.25


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


# --------------------------------------------------------------------------
# child processes


_PROCS: list["Proc"] = []


class Proc:
    """One child process with its output in a log file."""

    def __init__(self, name: str, argv: list[str], env: dict, out_dir: str):
        self.name = name
        self.log_path = os.path.join(out_dir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.p = subprocess.Popen(
            argv, env=env, cwd=ROOT, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        _PROCS.append(self)

    def tail(self, n: int = 15) -> str:
        with open(self.log_path, "rb") as f:
            lines = f.read().decode(errors="replace").splitlines()
        return "\n".join(lines[-n:])

    def alive(self) -> bool:
        return self.p.poll() is None

    def wait_listening(self, port: int, timeout: float, http_path: str | None) -> None:
        """Until the child answers on ``port`` (HTTP 200 on ``http_path``, or
        a TCP accept when it is None); fails if the child exits first."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not self.alive():
                raise PhaseFailed(
                    f"{self.name} exited {self.p.returncode} before listening:\n"
                    f"{self.tail()}"
                )
            try:
                if http_path is None:
                    socket.create_connection(("127.0.0.1", port), 1.0).close()
                    return
                status, _ = http_get(port, http_path, timeout=2.0)
                if status == 200:
                    return
            except OSError:
                pass
            time.sleep(0.5)
        raise PhaseFailed(f"{self.name} not listening after {timeout:.0f}s:\n{self.tail()}")

    def stop(self, sig: int, timeout: float = 120.0) -> int | None:
        """Signal the child and wait; returns its exit code, or None when it
        had to be killed."""
        if self.alive():
            self.p.send_signal(sig)
            try:
                self.p.wait(timeout)
            except subprocess.TimeoutExpired:
                self.kill()
                return None
        return self.p.returncode

    def kill(self) -> None:
        if self.alive():
            try:
                os.killpg(self.p.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.p.wait()
        self._log.close()


def kill_all() -> None:
    for proc in _PROCS:
        proc.kill()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# --------------------------------------------------------------------------
# HTTP (standard library only: the parent imports nothing of the repo)


def http_get(port: int, path: str, timeout: float = 30.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def chat(port: int, content: str, max_tokens: int, timeout: float) -> dict:
    """One streaming /v1/chat/completions request, read to its end."""
    body = json.dumps({
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens, "temperature": 0, "stream": True,
        "stream_options": {"include_usage": True},
    })
    out = {"finish": None, "deltas": 0, "usage": None, "error": None}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/chat/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            out["error"] = f"HTTP {resp.status}: {resp.read()[:300]!r}"
            return out
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                break
            chunk = json.loads(data)
            if chunk.get("error"):
                out["error"] = json.dumps(chunk["error"])[:300]
            if chunk.get("usage"):
                out["usage"] = chunk["usage"]
            for choice in chunk.get("choices", []):
                if (choice.get("delta") or {}).get("content"):
                    out["deltas"] += 1
                if choice.get("finish_reason"):
                    out["finish"] = choice["finish_reason"]
    except (OSError, ValueError, http.client.HTTPException) as e:
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return out


def words(seed: int, n_tokens: int) -> str:
    """A prompt of ``n_tokens`` mock-tokenizer tokens after the chat template
    (one ``w<id>`` word is one token)."""
    rng = random.Random(seed)
    return " ".join(f"w{rng.randrange(2, 500)}" for _ in range(n_tokens - TEMPLATE_TOKENS))


def run_wave(port: int, name: str, prompts: list[tuple[str, int, int]],
             timeout: float) -> list[dict]:
    """Send ``(content, prompt_tokens, max_tokens)`` requests at once; every
    one must end ``stop`` or ``length`` with tokens and the prompt length the
    server counted must be the one intended."""
    results: list[dict | None] = [None] * len(prompts)

    def one(i: int) -> None:
        results[i] = chat(port, prompts[i][0], prompts[i][2], timeout)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout + 30)
    for (_, n_prompt, _), r in zip(prompts, results):
        check(r is not None, f"wave {name}: a request never returned")
        check(r["error"] is None, f"wave {name}: {r['error']}")
        check(r["finish"] in ("stop", "length"),
              f"wave {name}: finish_reason {r['finish']!r}")
        usage = r["usage"] or {}
        check(usage.get("completion_tokens", 0) >= 1 and r["deltas"] >= 1,
              f"wave {name}: no tokens came back ({usage})")
        check(usage.get("prompt_tokens") == n_prompt,
              f"wave {name}: server counted {usage.get('prompt_tokens')} prompt "
              f"tokens, sent {n_prompt}")
    return results


def cached_tokens(result: dict) -> int:
    return ((result["usage"] or {}).get("prompt_tokens_details") or {}).get(
        "cached_tokens", 0)


def send_waves(port: int, waves: set[str], size: dict, many_n: int,
               timeout: float) -> None:
    if "i" in waves:
        run_wave(port, "i", [(words(100 + k, size["short"]), size["short"], size["out"])
                             for k in range(8)], timeout)
    if "ii" in waves:
        prefix = words(200, size["prefix"] + TEMPLATE_TOKENS)
        n = size["prefix"] + size["tail"]
        tails = [words(201 + k, size["tail"]) for k in range(2)]
        first = run_wave(port, "ii-cold", [(f"{prefix} {tails[0]}", n, size["out"])],
                         timeout)
        second = run_wave(port, "ii-warm", [(f"{prefix} {tails[1]}", n, size["out"])],
                          timeout)
        check(cached_tokens(second[0]) > 0,
              f"wave ii: the repeated prefix was not served from the radix cache "
              f"({second[0]['usage']}; first {first[0]['usage']})")
    if "iii" in waves:
        run_wave(port, "iii", [(words(300, size["long"]), size["long"], size["out"])],
                 timeout)
    if "iv" in waves:
        run_wave(port, "iv", [(words(400 + k, size["many"]), size["many"], size["many_out"])
                              for k in range(many_n)], timeout)


# --------------------------------------------------------------------------
# what the engine says about itself


def engine_report(port: int, worker_id: str, inproc: bool) -> dict:
    """The engine's own counters once it is quiet.  An in-process engine
    reports through ``/scheduler``; a remote worker's load message carries
    counters only, so its device and failure counts are read from the flight
    recorder's header instead."""
    if not inproc:
        status, body = http_get(
            port, "/debug/flight/" + urllib.parse.quote(worker_id, safe=""))
        check(status == 200, f"/debug/flight answered {status}: {body[:200]!r}")
        return json.loads(body)["dump"]["engine"]
    deadline = time.monotonic() + 60
    while True:
        status, body = http_get(port, "/scheduler")
        check(status == 200, f"/scheduler answered {status}")
        eng = json.loads(body)["engine"][worker_id]
        check("error" not in eng, f"/scheduler: {eng.get('error')}")
        if eng["audit"]["quiescent"] or time.monotonic() > deadline:
            return eng
        time.sleep(0.5)


def check_engine(eng: dict, platform: str, devices: int, attention: dict) -> None:
    check(eng["quarantined_requests"] == 0,
          f"quarantined_requests = {eng['quarantined_requests']}")
    check(eng["step_failures"] == 0, f"step_failures = {eng['step_failures']}")
    mesh = eng["mesh"]
    check(mesh["platform"] == platform,
          f"engine ran on {mesh['platform']!r}, expected {platform!r}")
    check(mesh["devices"] == devices,
          f"engine mesh has {mesh['devices']} devices, expected {devices}")
    got = eng["attention"]
    check(got["mode"] == attention["mode"],
          f"attention mode {got['mode']!r}, expected {attention['mode']!r}")
    for impl, launched in attention["launched"].items():
        n = got["launches"][impl]
        check((n > 0) == launched,
              f"attention {impl}: {n} launches, expected "
              f"{'some' if launched else 'none'} ({got['launches']})")
    if "audit" in eng:
        audit = eng["audit"]
        check(audit["quiescent"], f"engine never went quiet: {audit}")
        check(audit["clean"], f"leak audit not clean: {audit}")


def check_hbm(port: int, mesh: dict) -> dict:
    """Every device of the engine must hold at least its share of the
    parameters and the cache: the check that nothing serves from a default-
    sized cache and that a mesh did not put everything on device 0."""
    status, body = http_get(port, "/metrics")
    check(status == 200, f"/metrics answered {status}")
    in_use = {}
    for line in body.decode().splitlines():
        if line.startswith("smg_engine_hbm_bytes_in_use{"):
            labels, value = line.rsplit(" ", 1)
            in_use[labels.split('device="')[1].split('"')[0]] = float(value)
    need = mesh["param_bytes_per_device"] + mesh["kv_bytes_per_device"]
    check(len(in_use) == mesh["devices"],
          f"HBM gauges for {sorted(in_use)}; the mesh has {mesh['devices']} devices")
    for dev, used in in_use.items():
        check(used >= need, f"{dev} holds {used:.0f} bytes, its share is {need}")
    return {"devices": len(in_use), "share_bytes": need, "min_in_use_bytes": min(in_use.values())}


# --------------------------------------------------------------------------
# phases a, c, d: servers


def serve_flags(preset: str, rehearsal: bool) -> list[str]:
    flags = ["--model-preset", preset, "--decode-horizon", "8"]
    if rehearsal:
        # tiny on the CPU: float32 (its preset dtype) and a table small
        # enough that the start-up warm-up takes seconds
        flags += ["--dtype", "float32", "--max-seq-len", "1024",
                  "--max-prefill-tokens", "256"]
    return flags


def phase_serve(ctx: "Ctx", name: str, preset: str, mesh_flags: list[str],
                devices: int, waves: set[str], attention: dict) -> dict:
    port = free_port()
    t0 = time.monotonic()
    srv = Proc(f"{name}-serve", [
        sys.executable, "-m", "smg_tpu.cli", "serve", *serve_flags(preset, ctx.rehearsal),
        *mesh_flags, "--host", "127.0.0.1", "--port", str(port),
    ], ctx.chip_env, ctx.out)
    try:
        srv.wait_listening(port, ctx.startup_timeout, "/health")
        setup = time.monotonic() - t0
        many_n = ctx.size["many_n" if devices == 1 else "many_n_mesh"]
        send_waves(port, waves, ctx.size, many_n, ctx.request_timeout)
        eng = engine_report(port, "inproc-0", inproc=True)
        check_engine(eng, ctx.platform, devices, attention)
        report = {"setup_seconds": round(setup, 1), "attention": eng["attention"],
                  "total_pages": eng["total_pages"]}
        if not ctx.rehearsal:
            report["hbm"] = check_hbm(port, eng["mesh"])
        rc = srv.stop(signal.SIGTERM)
        check(rc == 0, f"serve exited {rc} after SIGTERM:\n{srv.tail()}")
        return report
    finally:
        srv.kill()


def chip_files(pid: int) -> list[str]:
    """Accelerator device files ``pid`` holds open."""
    held = []
    for fd in os.listdir(f"/proc/{pid}/fd"):
        try:
            target = os.readlink(f"/proc/{pid}/fd/{fd}")
        except OSError:
            continue
        if target.startswith(("/dev/accel", "/dev/vfio")):
            held.append(target)
    return sorted(set(held))


def phase_two_processes(ctx: "Ctx") -> dict:
    grpc_port, port = free_port(), free_port()
    t0 = time.monotonic()
    worker = Proc("c-worker", [
        sys.executable, "-m", "smg_tpu.cli", "worker",
        *serve_flags(ctx.preset, ctx.rehearsal), "--grpc-port", str(grpc_port),
    ], ctx.chip_env, ctx.out)
    gateway = None
    try:
        worker.wait_listening(grpc_port, ctx.startup_timeout, None)
        # the gateway gets the chip's platform in its environment on purpose:
        # it must pin itself to the CPU whatever it inherits
        gateway = Proc("c-launch", [
            sys.executable, "-m", "smg_tpu.cli", "launch",
            "--worker", f"127.0.0.1:{grpc_port}", "--host", "127.0.0.1",
            "--port", str(port),
        ], ctx.chip_env, ctx.out)
        gateway.wait_listening(port, 120, "/readiness")
        setup = time.monotonic() - t0
        send_waves(port, {"i"}, ctx.size, 0, ctx.request_timeout)
        eng = engine_report(port, f"127.0.0.1:{grpc_port}", inproc=False)
        check_engine(eng, ctx.platform, 1, {
            "mode": "xla" if ctx.rehearsal else "auto",
            "launched": {"xla": True},
        })
        report = {"setup_seconds": round(setup, 1)}
        if not ctx.rehearsal:
            held = chip_files(worker.p.pid)
            check(held, "the worker holds no /dev/accel* or /dev/vfio* file: "
                        "cannot tell which process owns the chip")
            stray = chip_files(gateway.p.pid)
            check(not stray, f"the launch process opened the chip: {stray}")
            report["worker_chip_files"] = len(held)
        with open(gateway.log_path, "rb") as f:
            check(b"pinned to the CPU platform" in f.read(),
                  "the launch process did not pin itself to the CPU")
        rc = gateway.stop(signal.SIGTERM)
        check(rc == 0, f"launch exited {rc} after SIGTERM:\n{gateway.tail()}")
        # the worker has no SIGTERM handler; SIGINT is its clean way out
        rc = worker.stop(signal.SIGINT)
        check(rc == 0, f"worker exited {rc} after SIGINT:\n{worker.tail()}")
        return report
    finally:
        if gateway is not None:
            gateway.kill()
        worker.kill()


# --------------------------------------------------------------------------
# phase b: numbers (runs in a child; imports JAX)


def _numbers_child(rehearsal: bool) -> int:
    """Logits of the serving forward, through the paged cache, against the
    dense float32 forward of the same tokens, for both attention
    implementations.  Two sequences of different lengths whose pages are
    interleaved in one shuffled pool; each is prefilled in two chunks (the
    second behind a live prefix: two query tiles and several prefix blocks
    at the full size) and then both are decoded together, beside six padded
    rows, for the steps of one horizon.

    The error of a case is max |logits - reference| over the vocabulary, in
    units of the reference row's standard deviation.  The control decodes
    one step through a table in which one live page of sequence 0 (16 of its
    700 tokens) is replaced by a page of sequence 1: the error a kernel
    reading one wrong page would make.  LOGIT_TOLERANCE must separate the
    two, and the phase fails if the control passes."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from smg_tpu.models.config import PRESETS, ModelConfig
    from smg_tpu.models.registry import get_model
    from smg_tpu.ops.rope import rope_frequencies

    if rehearsal:
        # the narrowest model both kernels accept (128 fused KV lanes)
        cfg = ModelConfig(vocab_size=512, hidden_size=256, intermediate_size=512,
                          num_layers=2, num_heads=8, num_kv_heads=2, head_dim=64,
                          tie_word_embeddings=True)
        pallas, lens, splits, T = "pallas_interpret", (88, 40), (48, 16), 64
    else:
        cfg = PRESETS["llama3.2-1b"]()
        pallas, lens, splits, T = "pallas", (700, 330), (380, 130), 512
    n_dec, ps, mp, B = 4, 16, 64, 8
    module = get_model(cfg.arch)
    params = jax.jit(partial(module.init_params, cfg))(jax.random.PRNGKey(SEED))
    inv_freq = jnp.asarray(
        rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))
    rng = np.random.default_rng(SEED)
    toks = [rng.integers(2, cfg.vocab_size, size=n + n_dec).astype(np.int32)
            for n in lens]

    dense = jax.jit(lambda p, t: module.forward_train(p, cfg, inv_freq, t[None])[0])
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref = [np.asarray(dense(params32, jnp.asarray(t))) for t in toks]
    del params32

    P = 2 * mp + 1  # page 0 is the garbage page
    perm = rng.permutation(np.arange(1, P)).astype(np.int32)
    tables = [perm[0::2], perm[1::2]]
    KD = cfg.num_kv_heads * cfg.head_dim
    dtype = jnp.dtype(cfg.dtype)

    def err(logits, want) -> float:
        diff = np.max(np.abs(np.asarray(logits, np.float32) - want))
        return round(float(diff / np.std(want)), 4)

    def prefill_both(prefill):
        kc = jnp.zeros((cfg.num_layers, P, ps, KD), dtype)
        vc = jnp.zeros_like(kc)
        errs = {}
        for s, (n, split) in enumerate(zip(lens, splits)):
            for lo, hi in ((0, split), (split, n)):
                chunk = np.zeros(T, np.int32)
                chunk[: hi - lo] = toks[s][lo:hi]
                logits, kc, vc = prefill(
                    params, jnp.asarray(chunk), jnp.int32(lo), jnp.int32(hi - lo),
                    kc, vc, jnp.asarray(tables[s]))
            errs[f"prefill[{s}]"] = err(logits, ref[s][n - 1])
        return kc, vc, errs

    def decode_both(decode, kc, vc, tables, steps):
        page_tables = np.zeros((B, mp), np.int32)
        entry = np.full(B, mp * ps, np.int32)  # padded rows sit past the table
        for s in range(2):
            page_tables[s], entry[s] = tables[s], lens[s]
        hk = jnp.zeros((cfg.num_layers, B, n_dec, KD), dtype)
        hv = jnp.zeros_like(hk)
        errs = {}
        for j in range(steps):
            cur = np.zeros(B, np.int32)
            for s in range(2):
                cur[s] = toks[s][lens[s] + j]
            logits, hk, hv = decode(
                params, jnp.asarray(cur), jnp.asarray(entry + j), jnp.asarray(entry),
                jnp.int32(j), kc, vc, jnp.asarray(page_tables), hk, hv)
            for s in range(2):
                errs[f"decode[{s}]+{j}"] = err(logits[s], ref[s][lens[s] + j])
        return errs

    # (swapping two live pages of one sequence would prove nothing: keys are
    # cached after rope, and attention does not care in what order it reads them)
    wrong = [tables[0].copy(), tables[1]]
    wrong[0][1] = tables[1][1]
    errors, control = {}, {}
    for impl in ("xla", pallas):
        prefill = jax.jit(lambda p, *a, impl=impl: module.forward_prefill(
            p, cfg, inv_freq, *a, attn_impl=impl))
        decode = jax.jit(lambda p, *a, impl=impl: module.forward_decode_horizon(
            p, cfg, inv_freq, *a, attn_impl=impl))
        kc, vc, errors[impl] = prefill_both(prefill)
        errors[impl].update(decode_both(decode, kc, vc, tables, n_dec))
        control[impl] = decode_both(decode, kc, vc, wrong, 1)["decode[0]+0"]
    ok = (all(np.isfinite(e) and e <= LOGIT_TOLERANCE
              for per_impl in errors.values() for e in per_impl.values())
          and all(e > LOGIT_TOLERANCE for e in control.values()))
    print(json.dumps({"ok": bool(ok), "tolerance": LOGIT_TOLERANCE,
                      "errors": errors, "control_errors": control}))
    return 0 if ok else 1


def _probe_child() -> int:
    import importlib.metadata

    import jax
    import jaxlib

    devs = jax.devices()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    print(json.dumps({
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
    }))
    return 0


def _radix_child() -> int:
    """Which prefix tree the cache_aware policy gets here (the native index is
    built from csrc/radix_index.cpp on first use)."""
    from smg_tpu.kv_index.native import native_available

    print(json.dumps({"tree": "native" if native_available() else "python"}))
    return 0


def run_child(ctx: "Ctx", name: str, child: str, env: dict, timeout: float) -> dict:
    """Run ``chip_smoke.py --child <child>`` and return the JSON object on the
    last line of its output."""
    argv = [sys.executable, os.path.abspath(__file__), "--child", child]
    if ctx.rehearsal:
        argv.append("--rehearsal")
    proc = Proc(name, argv, env, ctx.out)
    try:
        try:
            proc.p.wait(timeout)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{name} still running after {timeout:.0f}s") from None
        lines = proc.tail(1)
        try:
            result = json.loads(lines)
        except ValueError:
            raise PhaseFailed(
                f"{name} exited {proc.p.returncode} without a result:\n{proc.tail()}"
            ) from None
        result["exit_code"] = proc.p.returncode
        return result
    finally:
        proc.kill()


# --------------------------------------------------------------------------


class Ctx:
    def __init__(self, rehearsal: bool, out: str, cache_dir: str):
        self.rehearsal = rehearsal
        self.out = out
        self.size = REHEARSAL if rehearsal else FULL
        self.preset = "tiny" if rehearsal else "llama3.2-1b"
        self.mesh_preset = "tiny" if rehearsal else "llama3-8b"
        self.platform = "cpu" if rehearsal else "tpu"
        self.startup_timeout = 600.0
        self.request_timeout = 600.0
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["PYTHONUNBUFFERED"] = "1"
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        # set, never inherited: a sandbox that exports JAX_PLATFORMS=cpu would
        # otherwise serve every phase from the CPU and pass
        env["JAX_PLATFORMS"] = self.platform
        if rehearsal:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                                + " --xla_force_host_platform_device_count=4").strip()
        self.chip_env = env


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny preset on the CPU, kernels interpreted; proves "
                         "nothing about a chip")
    ap.add_argument("--phases", default="a,b,c,d",
                    help="comma-separated subset of a,b,c,d (default: all)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="directory for the children's logs")
    ap.add_argument("--child", choices=["probe", "numbers", "radix"],
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child == "probe":
        return _probe_child()
    if args.child == "numbers":
        return _numbers_child(args.rehearsal)
    if args.child == "radix":
        return _radix_child()

    if not os.path.isdir(os.path.join(ROOT, "smg_tpu")):
        print("chip_smoke: no smg_tpu package beside this script; run it from a "
              "checkout", file=sys.stderr)
        return 2
    selected = [p for p in args.phases.split(",") if p]
    if not set(selected) <= set("abcd"):
        ap.error(f"--phases {args.phases!r}: expected a subset of a,b,c,d")
    os.makedirs(args.out, exist_ok=True)
    cache_dir = tempfile.mkdtemp(prefix="smg-chip-smoke-xla-")
    ctx = Ctx(args.rehearsal, args.out, cache_dir)
    try:
        try:
            device = run_child(ctx, "probe", "probe", ctx.chip_env, 300)
        except PhaseFailed as e:
            print(f"chip_smoke: JAX found no {ctx.platform} device; no phase was run."
                  f"\n{e}", file=sys.stderr)
            return 3
        if device.pop("exit_code") != 0 or device["platform"] != ctx.platform:
            print(f"chip_smoke: JAX found {device}, not a {ctx.platform} device; "
                  "no phase was run.", file=sys.stderr)
            return 3
        one_chip = {"mode": "xla" if ctx.rehearsal else "auto", "launched": {
            "xla": True, "pallas_prefill": not ctx.rehearsal,
            "pallas_decode": not ctx.rehearsal}}
        mesh = {"mode": "xla", "launched": {
            "xla": True, "pallas_prefill": False, "pallas_decode": False}}
        runners = {
            "a": lambda: phase_serve(ctx, "a", ctx.preset, [], 1,
                                     {"i", "ii", "iii", "iv"}, one_chip),
            "b": lambda: run_child(ctx, "b-numbers", "numbers", ctx.chip_env, 900),
            "c": lambda: phase_two_processes(ctx),
            "d": lambda: phase_serve(ctx, "d", ctx.mesh_preset, ["--mesh-shape", "tp=4"],
                                     4, {"i", "iii", "iv"}, mesh),
        }
        phases: dict[str, dict] = {}
        for name in "abcd":
            if name not in selected:
                phases[name] = {"ran": False, "why": "not selected"}
                continue
            if name == "d" and device["count"] < 4:
                phases[name] = {"ran": False,
                                "why": f"needs 4 chips, JAX sees {device['count']}"}
                continue
            t0 = time.monotonic()
            try:
                report = runners[name]()
                passed = report.pop("exit_code", 0) == 0 and report.pop("ok", True)
                phases[name] = {"ran": True, "ok": bool(passed), **report}
            except PhaseFailed as e:
                phases[name] = {"ran": True, "ok": False, "error": str(e)[-2000:]}
            finally:
                kill_all()
            phases[name]["wall_seconds"] = round(time.monotonic() - t0, 1)
            print(f"chip_smoke: phase {name}: {json.dumps(phases[name])}",
                  file=sys.stderr, flush=True)
        radix = run_child(ctx, "radix", "radix", dict(ctx.chip_env, JAX_PLATFORMS="cpu"), 300)
        ok = (all(p["ok"] for p in phases.values() if p["ran"])
              and all(phases[n]["ran"] for n in "abc"))
        verdict = {
            "ok": ok,
            "device": {"platform": device["platform"], "kind": device["kind"],
                       "count": device["count"]},
        }
        if ctx.rehearsal:
            verdict["rehearsal"] = True
        report = {**verdict, "versions": device["versions"], "phases": phases,
                  "radix_index": radix.get("tree")}
        with open(os.path.join(ctx.out, "report.json"), "w") as f:
            json.dump(report, f, indent=1)
        print(f"chip_smoke: report: {json.dumps(report)}", flush=True)
        # the last line is the verdict and nothing else: whoever runs this
        # parses it, and the detail is in the line above and in report.json
        print(json.dumps(verdict), flush=True)
        return 0 if ok else 1
    finally:
        kill_all()
        shutil.rmtree(cache_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""What a grouped prefill's dispatch (``smg.step.admit.dispatch``) is made
of, timed alone: small host-to-device uploads as ``ModelRunner.upload`` makes
them, one packed upload of the same bytes (and of the larger groups' bytes),
a key fold, and a jitted call of as many arguments.  Then the real thing: the
runner ``serve`` would build for ``benchmark/configs/qwen3-1.7b.json``
(random weights) launches 300 grouped prefills at 1 x 512 and at 2 x 1,024
tokens through ``prefill_batched_async``, each fetched before the next, and
the step account's own ``admit_pack`` and ``admit_dispatch`` seconds
(``loads()["step_phases"]``) are divided by the launches.

One JSON line that names the device it ran on; a time means something on a
chip only (``PERF.md`` section 5; ROADMAP S3).  ``--rehearsal`` walks the
same code on the CPU at the configuration's rehearsal widths."""
import argparse, json, os, sys, time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)
ap = argparse.ArgumentParser()
ap.add_argument("--rehearsal", action="store_true")
ap.add_argument("--launches", type=int, default=300)
opts = ap.parse_args()
if opts.rehearsal:
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import jax, jax.numpy as jnp

def bench(fn, n=300):
    fn(); fn()
    t=time.perf_counter()
    for _ in range(n): out=fn()
    jax.block_until_ready(out)
    return (time.perf_counter()-t)/n*1e3

G,T,mp=2,1024,512
tokens=np.zeros((G,T),np.int32); vec_i=np.zeros(G,np.int32); vec_f=np.zeros(G,np.float32); pt=np.zeros((G,mp),np.int32)
res={"device":jax.devices()[0].device_kind}
res["upload_tokens_ms"]=bench(lambda: jnp.asarray(tokens))
res["upload_page_tables_ms"]=bench(lambda: jnp.asarray(pt))
res["upload_small_vec_ms"]=bench(lambda: jnp.asarray(vec_i))
def nine():
    return [jnp.asarray(tokens), jnp.asarray(vec_i), jnp.asarray(vec_i), jnp.asarray(pt), jnp.asarray(vec_f), jnp.asarray(vec_i), jnp.asarray(vec_f), jnp.asarray(vec_f)]
res["eight_uploads_ms"]=bench(nine)
packed=np.zeros(G*T+G*mp+6*G,np.int32)
res["one_packed_upload_ms"]=bench(lambda: jnp.asarray(packed))
# the packed inputs of larger groups: a group pads to G x T, both rounded up
for g, t in ((1, 512), (1, 4096), (4, 2048), (8, 1024), (8, 2048)):
    big_pack = np.zeros(g * t + g * mp + 6 * g + 1, np.int32)
    res[f"packed_upload_{g}x{t}_ms"] = bench(lambda: jnp.asarray(big_pack))
    res[f"packed_upload_{g}x{t}_bytes"] = big_pack.nbytes
key=jax.random.PRNGKey(0)
res["fold_in_ms"]=bench(lambda: jax.random.fold_in(key, 7))
big=jnp.zeros((1024,1024),jnp.bfloat16)
@jax.jit
def f(a,b,c,d,e,f_,g,h,i,j,k,l,m):
    return a.sum()+b.sum()+m.sum()
args=[big,big]+nine()+[big,big,key]
args=args[:13]
res["jit_call_13_args_ms"]=bench(lambda: f(*args))


def real_dispatch():
    """Host milliseconds a launch in the step account's two sub-spans."""
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.runner import ModelRunner
    from smg_tpu.models.config import ModelConfig

    with open(os.path.join(ROOT, "benchmark", "configs", "qwen3-1.7b.json")) as fh:
        conf = json.load(fh)
    if opts.rehearsal:
        conf = {**conf, **conf["rehearsal"]}
    own = {"assumed", "deployment", "chips", "serve_args", "rehearsal", "status",
           "architecture", "reduced", "published"}
    dtype = "float32" if opts.rehearsal else "bfloat16"
    model = ModelConfig.from_hf_config({k: v for k, v in conf.items() if k not in own}, dtype=dtype)
    config = EngineConfig(
        model=model, dtype=dtype,
        cache=CacheConfig(dtype=dtype, auto_size=not opts.rehearsal, num_pages=1024),
        scheduler=SchedulerConfig(decode_horizon=8, max_seq_len=1024 if opts.rehearsal else 8192,
                                  max_prefill_tokens=256 if opts.rehearsal else 4096))
    runner = ModelRunner(config)
    table = np.zeros(runner.max_pages_per_seq, np.int32)
    out = {"page_table_width": int(table.size)}
    for g, t in ((1, 128), (2, 128)) if opts.rehearsal else ((1, 512), (2, 1024)):
        group = [([0] * t, 0, table)] * g
        samp = (np.zeros(g, np.float32), np.full(g, -1, np.int32), np.ones(g, np.float32),
                np.zeros(g, np.float32))
        runner.prefill_batched(group, *samp)  # compiles
        acct = runner.account
        before = acct.sums()["seconds"]
        acct.begin_step()
        for _ in range(opts.launches):
            parts = runner.prefill_batched_async(group, *samp)
            runner.fetch_first_tokens(parts, g)  # the queue stays one launch deep
        acct.end_step(False)
        after = acct.sums()["seconds"]
        out[f"{g}x{t}"] = {"launches": opts.launches, **{
            f"{k}_ms": (after[k] - before[k]) / opts.launches * 1e3
            for k in ("admit_dispatch", "admit_pack")}}
    # a tree before the packed inputs has no such counter
    out["prefill_uploads"] = getattr(runner, "prefill_uploads", None)
    return out


res["prefill_batched_async"] = real_dispatch()
print(json.dumps(res))

#!/usr/bin/env python
"""What a grouped prefill's dispatch (``smg.step.admit.dispatch``) is made
of, timed alone: small host-to-device uploads as ``ModelRunner.upload`` makes
them, one packed upload of the same bytes, a key fold, and a jitted call of
as many arguments.  One JSON line that names the device it ran on; a time
means something on a chip only (``PERF.md`` section 5, PR 39; ROADMAP S3)."""
import json, time
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
key=jax.random.PRNGKey(0)
res["fold_in_ms"]=bench(lambda: jax.random.fold_in(key, 7))
big=jnp.zeros((1024,1024),jnp.bfloat16)
@jax.jit
def f(a,b,c,d,e,f_,g,h,i,j,k,l,m):
    return a.sum()+b.sum()+m.sum()
args=[big,big]+nine()+[big,big,key]
args=args[:13]
res["jit_call_13_args_ms"]=bench(lambda: f(*args))
print(json.dumps(res))

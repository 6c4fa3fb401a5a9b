"""What the ``test_tpu_compile*.py`` files share: the described TPU v5e, a
compile for it, and what they read off the compiled text.  One file a runner
family (the recurrent runner's models in two), so that ``--dist loadfile``
spreads the compiles over the workers;
each worker that is given one of them describes the topology itself, inside
the fixture, and the driver's ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` lets several do
so at once (without it all but the first skip their tests, and say so)."""

import collections
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

PS = 16
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises without support
        pytest.skip(f"libtpu cannot build the v5e:2x2 topology here: {e}")
    return list(topo.devices)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


_HLO_OP = re.compile(r"= \w+\[([\d,]*)\]\S* (\w[\w-]*)\((.*)")


def _relayouts(hlo: str, min_elements: int) -> list[str]:
    """Instructions of the compiled text that move an array of at least
    ``min_elements`` into another layout: every ``copy``, and every
    ``transpose`` whose permutation is not the identity."""
    found = []
    for line in hlo.splitlines():
        m = _HLO_OP.search(line)
        if not m or m.group(2) not in ("copy", "transpose"):
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        if math.prod(dims) < min_elements:
            continue
        perm = re.search(r"dimensions=\{([\d,]*)\}", m.group(3))
        if m.group(2) == "transpose" and perm and [
                int(d) for d in perm.group(1).split(",")] == list(range(len(dims))):
            continue
        found.append(line.strip()[:160])
    return found


def _collectives(hlo: str) -> collections.Counter:
    """Collective operations in the compiled text (an async pair counts
    once, at its ``-start``)."""
    return collections.Counter(re.findall(
        r" (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
        r"(?:-start)?\(", hlo))


def kernel_calls(hlo: str) -> collections.Counter:
    """The kernels of the compiled text, counted by their names."""
    return collections.Counter(re.findall(r"%(smg\.[\w.]+?)\.\d+ = \S+ custom-call", hlo))


def benchmark_cut(name: str):
    """The model of ``benchmark/configs/<name>.json`` at the benchmark's cut."""
    from smg_tpu.models.config import ModelConfig

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "benchmark",
                        "configs", name + ".json")
    own = {"assumed", "deployment", "chips", "serve_args", "rehearsal", "architecture",
           "reduced", "published"}
    with open(path) as f:
        return ModelConfig.from_hf_config(
            {k: v for k, v in json.load(f).items() if k not in own})


def assert_pool_updates_in_place(compiled, hlo: str, *pools, workspace: int = 0):
    """A prefill program still writes its rows into each donated pool where it
    lies, the tail pool ``cp`` (blocks or flat rows) and the float32 state pool
    ``sp`` (ISSUE 56) alike: the pool is an aliased output, a row goes in with
    a ``dynamic-update-slice`` (or is a kernel's aliased result), and nothing
    copies or transposes an array of the pool's size.  With ``workspace``, what
    the rows' own operands may take, the program's temporaries also stay under
    that and half the largest pool: a second state pool does not hide among
    them."""
    kind = {"bfloat16": "bf16", "float32": "f32"}
    for pool in pools:
        named = f"{kind[str(pool.dtype)]}[{','.join(str(d) for d in pool.shape)}]"
        assert re.search(rf"= {re.escape(named)}\S* dynamic-update-slice\(", hlo) or re.search(
            rf"= .*{re.escape(named)}\S* custom-call\(.*output_to_operand_aliasing", hlo)
        assert [line for line in _relayouts(hlo, pool.size) if named in line] == []
    memory = compiled.memory_analysis()
    nbytes = [pool.size * pool.dtype.itemsize for pool in pools]
    assert memory.alias_size_in_bytes >= sum(nbytes)
    if workspace:
        assert memory.temp_size_in_bytes < max(nbytes) // 2 + workspace

"""Paged KV cache: host-side page pool + device buffer creation.

The device layout is ``[num_layers, num_pages, page_size, kv_heads, head_dim]``
(see ``smg_tpu/ops/attention.py``).  Page 0 is reserved as the garbage page for
padded/inactive writes, so the allocator never hands it out.

Reference analogue: the external engines' KV allocators (SGLang's
token-to-kv-pool); in-tree here because the TPU engine owns its memory.
HBM sizing mirrors ``--mem-fraction-static``-style knobs forwarded by the
reference's worker launcher (``bindings/python/src/smg/serve.py``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from smg_tpu.engine.config import CacheConfig
from smg_tpu.models.config import ModelConfig


class OutOfPagesError(RuntimeError):
    pass


class PagePool:
    """Free-list page allocator.  Page 0 is the reserved garbage page."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self._free: list[int] = list(range(num_pages - 1, 0, -1))  # pop() yields 1,2,...

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise OutOfPagesError(f"requested {n} pages, {len(self._free)} free")
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: list[int]) -> None:
        for p in pages:
            if p == 0:
                raise ValueError("page 0 is reserved and never allocated")
            self._free.append(p)

    def reset(self) -> None:
        self._free = list(range(self.num_pages - 1, 0, -1))


class StateSlotPool:
    """Free-list allocator of per-sequence state slots, the recurrent model's
    companion of ``PagePool``: a sequence of a model with recurrent layers
    holds one slot from admission to release, beside its pages.  Slot 0 is
    the reserved garbage slot (padded rows of a batch name it)."""

    def __init__(self, num_slots: int):
        if num_slots < 2:
            raise ValueError("need at least 2 state slots (slot 0 is reserved)")
        self.num_slots = num_slots
        self._free: list[int] = list(range(num_slots - 1, 0, -1))

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_slots - 1 - len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise OutOfPagesError("no state slot free")
        return self._free.pop()

    def free(self, slot: int) -> None:
        if slot == 0:
            raise ValueError("state slot 0 is reserved and never allocated")
        self._free.append(slot)


@dataclass
class StateSpec:
    """The two state pools of a model with recurrent layers (shapes as the
    module's ``state_shapes`` gives them, the garbage slot included)."""

    num_slots: int  # allocatable slots + the garbage slot
    state_shape: tuple[int, ...]  # float32
    conv_shape: tuple[int, ...]
    conv_dtype: str

    @property
    def slot_bytes(self) -> int:
        """Bytes one sequence's slot holds over all recurrent layers: a tail of
        ``[R, W]`` (``tail_block``) as the whole tiles its rows take on the
        device, a tail of one flat row as the row."""
        from smg_tpu.ops.linear_attention import tail_padded_rows

        layers, _, *block = self.conv_shape
        if len(block) == 2:
            block[0] = tail_padded_rows(block[0], self.conv_dtype)
        return (math.prod(self.state_shape) // self.num_slots * 4
                + layers * math.prod(block) * jnp.dtype(self.conv_dtype).itemsize)

    @property
    def total_bytes(self) -> int:
        return self.slot_bytes * self.num_slots


@dataclass
class KvCacheSpec:
    num_layers: int
    num_pages: int
    page_size: int
    num_kv_heads: int
    head_dim: int
    dtype: str
    # a latent cache (``ops/latent_attention.py``): one buffer whose entries
    # are this many lanes wide, and no V buffer.  0: K and V of
    # ``num_kv_heads * head_dim`` lanes each.
    latent_lanes: int = 0
    # values narrower than keys: the V buffer's lanes.  0: as many as K's.
    v_lanes: int = 0
    # a latent cache whose tokens leave an index key besides, in
    # ``index_layers`` of the layers (``ops/sparse_attention.py``): the second
    # buffer holds those, ``index_lanes`` wide, on the same pages.  0: none.
    index_layers: int = 0
    index_lanes: int = 0

    @property
    def lanes(self) -> int:
        return self.latent_lanes or self.num_kv_heads * self.head_dim

    @property
    def shape(self) -> tuple[int, ...]:
        # fused lane layout (see smg_tpu/ops/attention.py)
        return (self.num_layers, self.num_pages, self.page_size, self.lanes)

    @property
    def v_shape(self) -> tuple[int, ...]:
        """The V buffer's shape: where the cache is latent, the index keys'
        (of zero size for a model that has none)."""
        if self.latent_lanes and self.index_layers:
            return (self.index_layers, self.num_pages, self.page_size, self.index_lanes)
        if self.latent_lanes:
            return (self.num_layers, 0, self.page_size, self.lanes)
        return (*self.shape[:3], self.v_lanes or self.lanes)

    @property
    def bytes_per_page(self) -> int:
        # k + v (or the one latent buffer and the index keys), all layers
        itemsize = jnp.dtype(self.dtype).itemsize
        lanes = self.lanes + (0 if self.latent_lanes else self.v_lanes or self.lanes)
        return (self.num_layers * lanes + self.index_layers * self.index_lanes) \
            * self.page_size * itemsize


@dataclass
class WindowSpec:
    """The window layers' store: for every sequence one slot, each slot a
    ring of ``ring_tokens`` entries a window layer (``ops/window_attention.py``),
    K and V of lanes of their own.  What a slot holds does not grow with the
    sequence's context."""

    num_slots: int  # allocatable slots + the garbage slot
    num_layers: int
    window: int
    ring_tokens: int
    k_lanes: int
    v_lanes: int
    dtype: str

    @property
    def k_shape(self) -> tuple[int, ...]:
        return (self.num_layers, self.num_slots, self.ring_tokens, self.k_lanes)

    @property
    def v_shape(self) -> tuple[int, ...]:
        return (self.num_layers, self.num_slots, self.ring_tokens, self.v_lanes)

    @property
    def slot_bytes(self) -> int:
        """Bytes one sequence's slot holds over all window layers."""
        return (self.num_layers * self.ring_tokens * (self.k_lanes + self.v_lanes)
                * jnp.dtype(self.dtype).itemsize)

    @property
    def total_bytes(self) -> int:
        return self.slot_bytes * self.num_slots


def plan_cache(
    model: ModelConfig,
    cache: CacheConfig,
    hbm_bytes_free: int | None = None,
    param_bytes: int = 0,
    tp: int = 1,
) -> KvCacheSpec:
    """Decide num_pages.  With ``auto_size`` and a known HBM budget, fill the
    headroom left after weights; otherwise use the configured num_pages.

    The spec always describes the GLOBAL buffer shape (the fused kv-lane dim
    carries all kv heads; GSPMD shards it over ``tp``).  Sizing inputs are
    PER-DEVICE: ``hbm_bytes_free`` and ``param_bytes`` are for the tightest
    single device, and ``tp`` is the kv-lane shard factor, so each device
    holds ``bytes_per_page / tp`` of every page."""
    spec = KvCacheSpec(
        num_layers=model.num_layers,
        num_pages=cache.num_pages,
        page_size=cache.page_size,
        num_kv_heads=model.num_kv_heads,
        head_dim=model.head_dim,
        dtype=cache.dtype,
    )
    if cache.auto_size and hbm_bytes_free is not None:
        kv_lanes = model.num_kv_heads * model.head_dim
        kv_shard = tp if tp > 1 and kv_lanes % tp == 0 else 1
        per_page_device = spec.bytes_per_page // kv_shard
        budget = int(hbm_bytes_free * cache.hbm_utilization) - param_bytes
        spec.num_pages = int(max(budget // per_page_device, 16))
    return spec


def plan_recurrent_cache(
    model: ModelConfig,
    cache: CacheConfig,
    state_slots: int,
    state_shapes,
    hbm_limit: int | None = None,
    hbm_in_use: int = 0,
    workspace: int = 0,
) -> tuple[KvCacheSpec, StateSpec]:
    """Split what the device has left between state slots and pages, for a
    model whose layers are not all attention.  Pages exist only for the
    layers that hold keys and values (``model.num_cache_layers``); the
    ``state_slots`` slots (and the garbage slot) are taken first, because a
    sequence cannot be admitted without one, and pages get the rest.

    ``hbm_limit`` and ``hbm_in_use`` are the tightest device's, read **after
    the weights are on it**: the budget is ``hbm_utilization`` of the whole
    device less what is in use, so the weights come off once
    (``plan_cache`` takes them off what is free after them, a second time,
    PERF.md 7.3a; that path is left as it is).  ``workspace`` is kept free of
    pages, as in ``plan_latent_cache``.  Where the model's cache is latent
    (``model.latent_cache``) the pages hold one entry of ``entry_lanes`` a
    token and no V, as ``plan_latent_cache``'s do."""
    from smg_tpu.ops.latent_attention import entry_lanes

    s_shape, c_shape = state_shapes(model, state_slots + 1)
    state = StateSpec(num_slots=state_slots + 1, state_shape=tuple(s_shape),
                      conv_shape=tuple(c_shape), conv_dtype=model.dtype)
    spec = KvCacheSpec(
        num_layers=model.num_cache_layers,
        num_pages=cache.num_pages,
        page_size=cache.page_size,
        num_kv_heads=model.num_kv_heads,
        head_dim=model.head_dim,
        dtype=cache.dtype,
        latent_lanes=(entry_lanes(model.kv_lora_rank, model.qk_rope_head_dim)
                      if model.latent_cache else 0),
    )
    if cache.auto_size and hbm_limit is not None:
        budget = (int(hbm_limit * cache.hbm_utilization) - hbm_in_use - state.total_bytes
                  - workspace)
        spec.num_pages = int(max(budget // spec.bytes_per_page, 16))
    return spec, state


def plan_latent_cache(
    model: ModelConfig,
    cache: CacheConfig,
    hbm_limit: int | None = None,
    hbm_in_use: int = 0,
    workspace: int = 0,
) -> KvCacheSpec:
    """Pages of a latent cache: one buffer of ``entry_lanes`` a token and
    cache layer (``model.num_cache_layers``: one for every attention
    sublayer, which is not every model's depth), and where some layers have
    an indexer (``model.num_index_layers``) a second buffer of their index
    keys on the same pages, sized from the same budget.  ``hbm_limit`` and
    ``hbm_in_use`` are the tightest device's, read
    **after the weights are on it**, so the weights come off once, as in
    ``plan_recurrent_cache``.  ``workspace``: bytes the largest program needs
    beside its arguments, kept free of pages (where the weights take most of
    the device, what ``hbm_utilization`` leaves does not hold a prefill)."""
    from smg_tpu.ops.latent_attention import entry_lanes

    spec = KvCacheSpec(
        num_layers=model.num_cache_layers,
        num_pages=cache.num_pages,
        page_size=cache.page_size,
        num_kv_heads=model.num_kv_heads,
        head_dim=model.head_dim,
        dtype=cache.dtype,
        latent_lanes=entry_lanes(model.kv_lora_rank, model.qk_rope_head_dim),
        index_layers=model.num_index_layers,
        index_lanes=model.index_head_dim if model.num_index_layers else 0,
    )
    if cache.auto_size and hbm_limit is not None:
        budget = int(hbm_limit * cache.hbm_utilization) - hbm_in_use - workspace
        spec.num_pages = int(max(budget // spec.bytes_per_page, 16))
    return spec


def plan_window_cache(
    model: ModelConfig,
    cache: CacheConfig,
    slots: int,
    unaccepted: int,
    hbm_limit: int | None = None,
    hbm_in_use: int = 0,
    workspace: int = 0,
) -> tuple[KvCacheSpec, WindowSpec]:
    """Split what the device has left between the window layers' slots and
    the full layers' pages, for a model with both kinds of layer.  Pages
    exist only for the full layers (``model.num_cache_layers``), K and V of
    their own widths; the ``slots`` slots (and the garbage slot) are taken
    first, because a sequence cannot be admitted without one, each a ring of
    the window and the ``unaccepted`` columns that may lie on the device past
    what the host has accepted.  ``hbm_limit`` and ``hbm_in_use`` are read
    **after the weights are on the device**, so the weights come off once,
    and ``workspace`` is kept free of pages, as in ``plan_latent_cache``."""
    from smg_tpu.ops.window_attention import ring_tokens

    wk, wv = model.kv_lanes(window=True)
    window = WindowSpec(
        num_slots=slots + 1, num_layers=model.num_window_layers, window=model.sliding_window,
        ring_tokens=ring_tokens(model.sliding_window, unaccepted), k_lanes=wk, v_lanes=wv,
        dtype=cache.dtype)
    spec = KvCacheSpec(
        num_layers=model.num_cache_layers,
        num_pages=cache.num_pages,
        page_size=cache.page_size,
        num_kv_heads=model.num_kv_heads,
        head_dim=model.head_dim,
        dtype=cache.dtype,
        v_lanes=model.kv_lanes()[1],
    )
    if cache.auto_size and hbm_limit is not None:
        budget = (int(hbm_limit * cache.hbm_utilization) - hbm_in_use - workspace
                  - window.total_bytes)
        spec.num_pages = int(max(budget // spec.bytes_per_page, 16))
    return spec, window


def create_kv_buffers(spec: KvCacheSpec, sharding=None) -> tuple[jax.Array, jax.Array]:
    """Allocate zeroed K and V buffers (optionally with a NamedSharding)."""
    dtype = jnp.dtype(spec.dtype)
    if sharding is not None:
        # smglint: disable-next=RETRACE runs at engine init / idle flush_cache only
        zeros = lambda shape: jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=(sharding))()
    else:
        zeros = lambda shape: jnp.zeros(shape, dtype)
    return zeros(spec.shape), zeros(spec.v_shape)

"""The convolution's tails in their pool (``ops/linear_attention.py``): how
``tail_block`` lays a slot's tail out, and the decode step over the slots
(``conv_decode_step``) against ``conv_token`` on tails kept outside any pool,
at the three state models' ``(K, C)`` and dtype at the benchmark's cuts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.engine.kv_cache import StateSpec
from smg_tpu.ops import linear_attention as LA

LAYERS, SLOTS, LAYER = 3, 11, 1
# lanes 0 and 1 on neighbouring slots, lanes 2 and 5 padded (the garbage
# slot, not running), lane 3 on a real slot and not running; slots 1, 3, 4, 7,
# 8 and 10 are named by no lane
LANE_SLOTS = [5, 6, 0, 9, 2, 0]
LANE_RUNS = [True, True, False, False, True, False]

# (model, K, C, dtype, the pool's blocks): ``conv_channels`` of the benchmark's
# configurations; ``nemotron_h.state_shapes`` keeps the flat row a slot
MODELS = [("kimi-linear-48b-a3b", 4, 12288, "bfloat16", "tiles"),
          ("nemotron-3-super-120b-a12b", 4, 10240, "bfloat16", "flat"),
          ("nemotron-3-super-120b-a12b-in-tiles", 4, 10240, "bfloat16", "tiles"),
          ("olmo-hybrid-7b", 4, 11520, "bfloat16", "tiles"),
          ("toy", 4, 256, "float32", "tiles"),
          ("toy-no-128-divides", 4, 100, "float32", "tiles")]

f32 = lambda a: np.asarray(jnp.asarray(a).astype(jnp.float32))


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("model,K,C,dtype,blocks", MODELS, ids=[m[0] for m in MODELS])
def test_the_decode_step_is_conv_token_over_the_slots_it_is_given(model, K, C, dtype, blocks,
                                                                  bias):
    """Two columns, jitted as a frame runs them: the outputs are
    ``conv_token``'s on each lane's own tail, every running lane's block is its
    tail shifted by one tap with the input as its last, neighbouring slots each
    their own, a lane that does not run and the padded lanes' garbage slot as
    they were bit for bit, and so every slot no lane names and every other
    layer."""
    rng = np.random.default_rng(C + bias)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)
    block = LA.tail_block(C, K, dtype) if blocks == "tiles" else ((K - 1) * C,)
    pool = draw(LAYERS, SLOTS, *block)
    xs, w, b = draw(2, len(LANE_SLOTS), C), draw(K, C), draw(C) if bias else None
    slots, runs = jnp.asarray(LANE_SLOTS, jnp.int32), jnp.asarray(LANE_RUNS)
    step = jax.jit(lambda p, x: LA.conv_decode_step(p, LAYER, slots, runs, x, w, b))
    tails = LA.read_tail(pool, LAYER, slots, K - 1)  # kept beside the pool, lane by lane
    assert tails.shape == (len(LANE_SLOTS), K - 1, C) and tails.dtype == pool.dtype
    got = pool
    for x in xs:
        want_y, new = LA.conv_token(x, tails, w, b)
        tails = jnp.where(runs[:, None, None], new, tails)
        y, got = step(got, x)
        assert y.dtype == jnp.float32 and got.dtype == pool.dtype and got.shape == pool.shape
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-5, atol=1e-5)
    for lane, (slot, ran) in enumerate(zip(LANE_SLOTS, LANE_RUNS)):
        if ran:  # two taps on, the inputs last
            block = f32(LA.read_tail(got, LAYER, slots[lane:lane + 1], K - 1))[0]
            assert np.array_equal(block, f32(tails)[lane])
            assert np.array_equal(block[-2:], f32(xs)[:, lane])
        else:
            assert np.array_equal(f32(got[LAYER, slot]), f32(pool[LAYER, slot]))
    for slot in set(range(SLOTS)) - set(LANE_SLOTS):
        assert np.array_equal(f32(got[LAYER, slot]), f32(pool[LAYER, slot]))
    assert np.array_equal(f32(got[0]), f32(pool[0])) and np.array_equal(f32(got[2]), f32(pool[2]))


@pytest.mark.parametrize("C,dtype,R,W,padded", [
    (12288, "bfloat16", 48, 768, 48),  # kimi: 16 rows a tap, not a byte more than the flat row
    (10240, "bfloat16", 48, 640, 48),  # nemotron
    (11520, "bfloat16", 15, 2304, 16),  # olmo: no width gives whole tiles; 15 rows in 16, 6.7 %
    (12288, "float32", 24, 1536, 24),
    (10240, "float32", 24, 1280, 24),
    (11520, "float32", 15, 2304, 16),
    (256, "float32", 6, 128, 8),
    (100, "float32", 3, 100, 8),  # no 128 divides it: taps by channels, and nothing raises
])
def test_a_slots_tail_is_whole_tiles_of_its_pool(C, dtype, R, W, padded):
    K = 4
    assert LA.tail_block(C, K, dtype) == (R, W)
    assert R * W == (K - 1) * C and LA.tail_padded_rows(R, dtype) == padded
    assert C % W == 0 and (W % 128 == 0 or W == C)
    # the plan counts the rows a slot's tail takes on the device, not the rows it has
    spec = StateSpec(num_slots=73, state_shape=(2, 73, 8, 128), conv_shape=(2, 73, R, W),
                     conv_dtype=dtype)
    assert spec.slot_bytes == 2 * (8 * 128 * 4 + padded * W * jnp.dtype(dtype).itemsize)
    flat = StateSpec(num_slots=73, state_shape=(2, 73, 8, 128), conv_shape=(2, 73, R * W),
                     conv_dtype=dtype)
    assert flat.slot_bytes == 2 * (8 * 128 * 4 + R * W * jnp.dtype(dtype).itemsize)
    # a block is a tail in ``read_tail``'s order: tap by tap, a tap's channels in order
    tail = jnp.arange((K - 1) * C, dtype=jnp.float32).reshape(1, K - 1, C)
    pool = LA.write_tail(jnp.zeros((1, 2, R, W), jnp.float32), 0, jnp.asarray([1]), tail)
    assert np.array_equal(np.asarray(pool[0, 1]).reshape(-1), np.arange((K - 1) * C))
    assert np.array_equal(np.asarray(LA.read_tail(pool, 0, jnp.asarray([1]), K - 1)), tail)

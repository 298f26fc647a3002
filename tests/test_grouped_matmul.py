"""The Pallas grouped matmul (`ops/grouped_matmul.py`) in interpret
mode against ``jax.lax.ragged_dot`` on the same operands: the rows a
group holds agree, and what the kernel visits is what the sizes say."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.ops import grouped_matmul as gm

# name -> (rows, K, N, the held groups' sizes, groups in the stack, the
# first held group, dtype, visits expected)
CASES = {
    # One chip's experts of Nemotron-3-Super (ungated, 1,024 -> 2,688)
    # and of Qwen3-Next (gated, 2,048 -> 512), two of them; the served
    # dtype at a smaller width (this suite's matmul precision makes
    # bfloat16 slow at these).
    "nemotron-block-1024x2688": (128, 1024, 2688, (40, 61), 2, 0,
                                 jnp.float32, 2),
    "qwen3-next-block-2048x512": (128, 2048, 512, (9, 100), 2, 0,
                                  jnp.float32, 2),
    "bfloat16-rows-and-stack": (256, 128, 256, (40, 7, 90, 21), 4, 0,
                                jnp.bfloat16, 5),
    "an-empty-group": (256, 64, 128, (50, 0, 70, 0), 4, 0, jnp.float32, 2),
    "a-group-straddles-two-row-tiles": (384, 64, 128, (100, 60, 10), 3, 0,
                                        jnp.float32, 4),
    "several-groups-inside-one-tile": (128, 64, 256, (3, 5, 1, 8, 2, 13), 6,
                                       0, jnp.float32, 6),
    "every-row-held-elsewhere": (256, 64, 128, (0, 0, 0), 3, 0, jnp.float32,
                                 0),
    "the-layer-offset-into-the-stack": (256, 64, 128, (30, 0, 99, 5), 12, 8,
                                        jnp.float32, 4),
    "exactly-one-tile-of-rows": (128, 64, 128, (128,), 1, 0, jnp.float32, 1),
    "a-group-over-three-tiles": (512, 64, 128, (20, 300, 40), 3, 0,
                                 jnp.float32, 5),
}


def _operands(rows, k, n, groups, dtype, seed=0):
    kx, kw = jax.random.split(jax.random.key(seed))
    stack = jax.random.normal(kw, (groups, k, n), jnp.float32) / np.sqrt(k)
    return (jax.random.normal(kx, (rows, k), jnp.float32).astype(dtype),
            stack.astype(dtype))


def _every(sizes, groups, first):
    every = np.zeros((groups,), np.int32)
    every[first:first + len(sizes)] = sizes
    return jnp.asarray(every)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_is_ragged_dot_on_the_rows_a_group_holds(name):
    rows, k, n, sizes, groups, first, dtype, n_visits = CASES[name]
    x, stack = _operands(rows, k, n, groups, dtype)
    held = sum(sizes)
    want = jax.lax.ragged_dot(x, stack, _every(sizes, groups, first))
    # `first` traced, as a layer walk in a scan would hand it.
    got = jax.jit(lambda *a: gm.grouped_matmul(*a, interpret=True))(
        x, stack, jnp.asarray(sizes, jnp.int32), jnp.int32(first))
    assert got.shape == (rows, n) and got.dtype == dtype
    tol = dict(atol=2e-5, rtol=2e-5) if dtype == jnp.float32 else dict(
        atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(got[:held], np.float32),
                               np.asarray(want[:held], np.float32), **tol)
    # A visited tile's rows past the groups read zero.
    edge = -(-held // gm.ROW_TILE) * gm.ROW_TILE
    assert not np.asarray(got[held:edge], np.float32).any()
    *_, counted = gm.visits(jnp.asarray(sizes, jnp.int32), rows)
    assert int(counted) == n_visits


def test_visits_walk_the_groups_in_order_and_each_tile_in_one_run():
    sizes = jnp.asarray((100, 0, 60, 10, 0, 130), jnp.int32)
    offsets, group, tile, n = gm.visits(sizes, 512)
    n = int(n)
    assert offsets.tolist() == [0, 100, 100, 160, 170, 170, 300]
    # (tile, group) pairs that share a row, by group then tile: a tile's
    # visits are consecutive, an empty group has none.
    assert list(zip(tile[:n].tolist(), group[:n].tolist())) == [
        (0, 0), (0, 2), (1, 2), (1, 3), (1, 5), (2, 5)]
    assert group.shape == tile.shape == (512 // gm.ROW_TILE + 6 - 1,)


@pytest.mark.parametrize("k, n, limit, tile", [
    # Under the kernel's own limit each of the benchmark's expert
    # blocks is taken whole: one contiguous copy an expert.
    (1024, 2688, gm.VMEM_LIMIT, 2688), (2688, 1024, gm.VMEM_LIMIT, 1024),
    (2048, 512, gm.VMEM_LIMIT, 512), (512, 2048, gm.VMEM_LIMIT, 2048),
    # Under the compiler's default scope 21 lane tiles split as 3 x 7.
    (1024, 2688, 16 * 2**20, 896), (2688, 1024, 16 * 2**20, 512),
    # A width that is no multiple of a lane tile is never split.
    (64, 96, 1, 96),
])
def test_the_output_tile_follows_the_shapes(monkeypatch, k, n, limit, tile):
    monkeypatch.setattr(gm, "VMEM_LIMIT", limit)
    assert gm._tile_n(k, n, 2) == tile


def test_differentiated_it_is_ragged_dot():
    rows, k, n, sizes, groups, first = 256, 64, 128, (30, 0, 99, 5), 12, 8
    x, stack = _operands(rows, k, n, groups, jnp.float32, seed=1)
    held = sum(sizes)
    mask = (jnp.arange(rows) < held)[:, None]
    every = _every(sizes, groups, first)

    def loss(fn):
        return lambda x, w: jnp.sum(jnp.where(mask, fn(x, w), 0.0) ** 2)

    want = jax.grad(loss(lambda x, w: jax.lax.ragged_dot(x, w, every)),
                    argnums=(0, 1))(x, stack)
    got = jax.grad(loss(lambda x, w: gm.grouped_matmul(
        x, w, jnp.asarray(sizes, jnp.int32), first, interpret=True)),
        argnums=(0, 1))(x, stack)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4,
                                   rtol=1e-4)


def test_rows_off_the_tile_and_a_stack_of_another_type_are_refused():
    x, stack = _operands(128, 64, 128, 2, jnp.float32)
    sizes = jnp.asarray((5, 5), jnp.int32)
    with pytest.raises(ValueError, match="multiple of 128"):
        gm.grouped_matmul(x[:100], stack, sizes, interpret=True)
    with pytest.raises(ValueError, match="stack of"):
        gm.grouped_matmul(x, stack.astype(jnp.bfloat16), sizes,
                          interpret=True)


def test_the_kernel_is_for_a_tpu_that_holds_the_stacks_whole(monkeypatch):
    """`sorted_dispatch` asks the backend, as the paged attention's
    "auto" does, and a mesh: the partitioner cannot split a kernel."""
    from jax.sharding import Mesh

    from polyaxon_tpu.models import moe

    assert not moe._grouped_kernel()                    # the CPU of this test
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe._grouped_kernel()
    with Mesh(np.asarray(jax.devices()[:1]), ("tp",)):
        assert moe._grouped_kernel()
    with Mesh(np.asarray(jax.devices()[:2]), ("tp",)):
        assert not moe._grouped_kernel()

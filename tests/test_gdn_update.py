"""The Pallas kernel for the gated delta rule's decode update
(`ops/gdn_update.py`) in interpret mode: against `gated_delta.step` and
``put_layer`` on the same operands, against the sequential recurrence of
``ops/gated_delta.py``'s docstring, and what it leaves alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models.common import put_layer
from polyaxon_tpu.ops import gated_delta
from polyaxon_tpu.ops import gdn_update as gu

# name -> (layers, rows in the leaf, rows that step, heads, dk, dv, layer)
CASES = {
    # `qwen3_next_tiny`'s heads, every row of the leaf stepping.
    "tiny-every-row": (3, 2, 2, 4, 8, 8, 0),
    # Fewer rows step than the leaf holds, in a middle layer.
    "tiny-a-middle-layer-some-rows": (3, 4, 2, 4, 8, 8, 1),
    # The published head (128 x 128), two of them.
    "published-head-128x128": (2, 2, 2, 2, 128, 128, 1),
    "one-row-one-head": (1, 1, 1, 1, 16, 32, 0),
    "key-and-value-sizes-differ": (2, 3, 3, 2, 32, 16, 1),
}


def _operands(layers, rows, batch, heads, dk, dv, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    k = normal(batch, heads, dk)
    return tuple(map(jnp.asarray, (
        normal(layers, rows, heads, dk, dv),
        normal(batch, heads, dk) * dk ** -0.5,
        k / np.linalg.norm(k, axis=-1, keepdims=True),
        normal(batch, heads, dv),
        -np.abs(normal(batch, heads)),                       # g <= 0
        1.0 / (1.0 + np.exp(-normal(batch, heads))))))       # beta


def _plain(stack, layer, q, k, v, g, beta, started):
    """What `gated_delta.step_rows` does off the chip."""
    batch = q.shape[0]
    state = jnp.where(started[:, None, None, None], stack[layer, :batch], 0.0)
    o, state = gated_delta.step(q, k, v, g, beta, state)
    return o, put_layer(stack, state, layer)


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_is_step_over_the_leaf(name):
    layers, rows, batch, heads, dk, dv, layer = CASES[name]
    stack, *vectors = _operands(layers, rows, batch, heads, dk, dv)
    started = jnp.arange(batch) % 3 != 1          # row 1 has not
    want_o, want = _plain(stack, layer, *vectors, started)
    o, new = gu.gdn_update(stack, layer, *vectors, started, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(new), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    # Every other layer, and every row past those that stepped, bit for
    # bit what it was.
    mask = np.ones(stack.shape[:2], bool)
    mask[layer, :batch] = False
    np.testing.assert_array_equal(np.asarray(new)[mask],
                                  np.asarray(stack)[mask])


def test_a_few_positions_are_the_sequential_recurrence():
    """``S_t = e^g S + k ⊗ β(v − (e^g S)ᵀk)``, ``o_t = S_tᵀq`` in
    float64, the state carried through the leaf; the traced layer."""
    layers, rows, heads, dk, dv, layer, steps = 2, 3, 2, 16, 8, 1, 4
    stack = jnp.zeros((layers, rows, heads, dk, dv)) + 7.0   # never read
    S = np.zeros((rows, heads, dk, dv))
    step = jax.jit(lambda stack, layer, *a: gu.gdn_update(
        stack, layer, *a, interpret=True))
    for t in range(steps):
        _, q, k, v, g, beta = _operands(layers, rows, rows, heads, dk, dv,
                                        seed=10 + t)
        o, stack = step(stack, jnp.int32(layer), q, k, v, g, beta,
                        jnp.full((rows,), t > 0))
        q, k, v, g, beta = (np.asarray(x, np.float64)
                            for x in (q, k, v, g, beta))
        S = np.exp(g)[..., None, None] * S
        S = S + k[..., :, None] * (beta[..., None] * (
            v - np.einsum("bhkv,bhk->bhv", S, k)))[..., None, :]
        np.testing.assert_allclose(
            np.asarray(o), np.einsum("bhkv,bhk->bhv", S, q), atol=1e-5)
    np.testing.assert_allclose(np.asarray(stack[layer]), S, atol=1e-5)
    assert np.all(np.asarray(stack[0]) == 7.0)


def test_a_row_that_has_not_started_starts_from_zeros():
    stack, q, k, v, g, beta = _operands(2, 3, 3, 2, 8, 8)
    dirty = stack.at[1, 1].set(jnp.nan)           # an earlier sequence's
    started = jnp.asarray([True, False, True])
    o, new = gu.gdn_update(dirty, 1, q, k, v, g, beta, started,
                           interpret=True)
    # From zeros: δ = βv, S = k ⊗ βv, o = βv (k·q).
    written = beta[1, :, None] * v[1]
    np.testing.assert_allclose(
        np.asarray(new[1, 1]),
        np.asarray(k[1, :, :, None] * written[:, None, :]), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(o[1]),
        np.asarray(written * jnp.sum(k[1] * q[1], -1, keepdims=True)),
        atol=1e-6)
    assert np.all(np.isfinite(np.asarray(o)))


def test_no_decay_and_no_write_leave_the_state_alone():
    stack, q, k, v, _, _ = _operands(2, 4, 4, 2, 8, 16)
    zeros = jnp.zeros(q.shape[:2])
    o, new = gu.gdn_update(stack, 0, q, k, v, zeros, zeros,
                           jnp.ones((4,), bool), interpret=True)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(stack))
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(jnp.einsum("bhkv,bhk->bhv", stack[0], q)),
        atol=1e-5)


@pytest.mark.parametrize("rows, heads, dk, dv, limit, block", [
    # The benchmark's 128 rows of 32 heads of 128 x 128 (64 KB a head):
    # two rows, 4 MB a block; under the compiler's default scope one.
    (128, 32, 128, 128, gu.VMEM_LIMIT, (2, 32)),
    (128, 32, 128, 128, 16 * 2**20, (1, 32)),
    # `qwen3_next_tiny` and the AOT case's smaller leaves go whole where
    # they fit; three rows of the AOT case's would, and 128 has no 3.
    (2, 4, 8, 8, gu.VMEM_LIMIT, (2, 4)),
    (6, 32, 128, 128, gu.VMEM_LIMIT, (3, 32)),
    # Nothing divides evenly: 5 rows step (a prime) and a row's 6 heads
    # do not fit (4 would): whole rows give way to 3 heads of one row.
    (5, 6, 128, 128, 1398102, (1, 3)),
    (5, 32, 128, 128, gu.VMEM_LIMIT, (1, 32)),
    # A head that does not fit is still taken alone.
    (4, 2, 128, 128, 1, (1, 1)),
])
def test_the_block_follows_the_shapes(monkeypatch, rows, heads, dk, dv, limit,
                                      block):
    monkeypatch.setattr(gu, "VMEM_LIMIT", limit)
    assert gu._block(rows, heads, dk, dv) == block


def test_heads_split_into_blocks_agree(monkeypatch):
    """A limit under a row's heads: a grid over head blocks too."""
    monkeypatch.setattr(gu, "VMEM_LIMIT", 3 * 4 * 8 * 8 * 4 * 4 // 3)
    stack, *vectors = _operands(2, 3, 3, 6, 8, 8, seed=3)
    assert gu._block(3, 6, 8, 8) == (1, 3)
    started = jnp.asarray([True, True, False])
    want_o, want = _plain(stack, 1, *vectors, started)
    # Not through the jitted entry: it has the default limit's lowering.
    o, new = gu._forward(stack, 1, *vectors, started, True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(new), np.asarray(want), atol=2e-5)


def test_operands_of_other_shapes_are_refused():
    stack, q, k, v, g, beta = _operands(2, 3, 3, 2, 8, 8)
    started = jnp.ones((3,), bool)
    with pytest.raises(ValueError, match="float32 leaf"):
        gu.gdn_update(stack.astype(jnp.bfloat16), 0, q, k, v, g, beta,
                      started, interpret=True)
    with pytest.raises(ValueError, match="B <= rows"):
        gu.gdn_update(stack[:, :2], 0, q, k, v, g, beta, started,
                      interpret=True)


def test_the_kernel_is_for_a_tpu_that_holds_the_leaf_whole(monkeypatch):
    """`step_rows` asks the backend and a mesh, as `sorted_dispatch`
    does: the partitioner can split `step` and cannot split a kernel."""
    from jax.sharding import Mesh

    assert not gated_delta.update_kernel()          # the CPU of this test
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gated_delta.update_kernel()
    with Mesh(np.asarray(jax.devices()[:1]), ("tp",)):
        assert gated_delta.update_kernel()
    with Mesh(np.asarray(jax.devices()[:2]), ("tp",)):
        assert not gated_delta.update_kernel()


def test_off_the_chip_step_rows_is_step_and_put_layer():
    stack, *vectors = _operands(3, 5, 4, 2, 8, 8, seed=5)
    started = jnp.asarray([True, False, True, True])
    want_o, want = _plain(stack, 2, *vectors, started)
    o, new = gated_delta.step_rows(stack, 2, *vectors, started)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want_o))
    np.testing.assert_array_equal(np.asarray(new), np.asarray(want))

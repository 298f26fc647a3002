"""The window space of the page pool (serving/paged.py
`WindowedPagePool`) and the windowed form of the paged decode kernel
(ops/paged_attention.py, ``window=``), each against the plain thing it
extends: the pool's full space against a `PagePool` under the same
schedule, the kernel against a masked softmax over gathered pages."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from polyaxon_tpu.models import llama
from polyaxon_tpu.ops.paged_attention import paged_decode_attention
from polyaxon_tpu.serving.paged import PagePool, WindowedPagePool

PAGE = 16


def _schedule(seed: int, slots: int, max_len: int, steps: int):
    """A random run of an engine's calls: ("admit", slot, prompt),
    ("step", slot) one position on, ("release", slot). A prompt is one
    of three shared documents cut somewhere and a tail of its own, so
    that the radix tree has matches to offer."""
    rng = np.random.default_rng(seed)
    documents = rng.integers(0, 50, (3, max_len // 2))
    pos = [-1] * slots
    for _ in range(steps):
        b = int(rng.integers(slots))
        if pos[b] < 0:
            n = int(rng.integers(2, max_len // 2))
            shared = int(rng.integers(0, n))
            prompt = np.concatenate([
                documents[int(rng.integers(3))][:shared],
                rng.integers(50, 100, n - shared)]).astype(np.int32)
            pos[b] = n - 1
            yield "admit", b, prompt
        elif pos[b] + 1 >= max_len or rng.random() < 0.02:
            pos[b] = -1
            yield "release", b, 0
        else:
            pos[b] += 1
            yield "step", b, pos[b]


@pytest.mark.parametrize("seed,window", [(0, 64), (1, 32), (2, 128)])
def test_random_schedule_keeps_both_spaces_sound(seed, window):
    """Over a random admit / step / release schedule: `check_invariants`
    stays clean, no row ever holds more than window/page + 1 window
    pages and every one of them lies within the window, a released page
    is on the free list at once (and is the next one taken), and the
    full space's tables, matches, evictions and free count are, call for
    call, those of a plain `PagePool` that matches whole pages: the
    radix tree shares the full space's pages under a window as it does
    without one, and the window space is shared with nobody."""
    slots, max_len, n_pages = 4, 512, 96
    pool = WindowedPagePool(slots, max_len, PAGE, n_pages, window=window,
                            window_layers=3)
    plain = PagePool(slots, max_len, PAGE, n_pages)
    plain.whole_page_matches = True
    assert pool.prefix_cache and pool.whole_page_matches
    most = window // PAGE + 1
    assert pool.window_pages_per_row == most
    assert pool.window_n_pages == slots * most + 1
    live = {}
    for what, b, arg in _schedule(seed, slots, max_len, 3000):
        if what == "admit":
            n = len(arg)
            fits = plain.can_admit(n, arg)
            assert pool.can_admit(n, arg) == fits
            # The engine's pick asks both questions in one call: the
            # same answer from either pool.
            offered = plain.admissible_match(n, arg)
            assert pool.admissible_match(n, arg) == offered
            assert (offered is not None) == fits
            mine, theirs = pool.admit(b, n, arg), plain.admit(b, n, arg)
            assert bool(mine) == bool(theirs) == fits
            if fits:
                assert mine == theirs and mine.cow is None
                assert mine.matched_tokens == offered
                assert mine.matched_tokens % PAGE == 0
                start = pool.suffix_start(mine.matched_tokens)
                assert start % PAGE == 0
                assert start == max(0, mine.matched_tokens - 3 * window)
                pool.commit_prefix(b)
                plain.commit_prefix(b)
                live[b] = n - 1
        elif what == "release":
            if b in live:
                del live[b]
                pool.release(b)
                plain.release(b)
        elif b in live:
            ok = plain.ensure(b, arg)
            assert pool.ensure(b, arg) == ok
            if not ok:
                del live[b]
                pool.release(b)
                plain.release(b)
                continue
            before = pool.window_pages_released
            free_before = list(pool._window_free)
            pool.roll(b, arg)
            if pool.window_pages_released > before:
                # The page handed back was free at once: it is the one
                # the next allocation (this roll's own) took.
                assert len(pool._window_free) == len(free_before)
            live[b] = arg
        np.testing.assert_array_equal(pool.tables, plain.tables)
        assert pool.free_pages == plain.free_pages
        held = pool.window_tables >= 0
        assert held.sum(axis=1).max() <= most
        for slot, p in live.items():
            at = np.flatnonzero(held[slot])
            # From the window's first page to the position's, no hole.
            first = max(0, p // PAGE + 1 - most)
            np.testing.assert_array_equal(
                at, np.arange(first, p // PAGE + 1))
            assert (first + 1) * PAGE > p - window, "a page behind the window"
    assert pool.check_invariants() == []
    assert pool.window_row_pages_max <= most
    for b in list(live):
        pool.release(b)
    stats = pool.window_stats()
    assert stats["live"] == 0 and stats["free"] == stats["total"]
    assert pool.check_invariants() == []
    assert pool.prefix_hits == plain.prefix_hits > 0
    assert pool.prefix_evictions == plain.prefix_evictions > 0


def test_a_released_window_page_is_reusable_at_once():
    pool = WindowedPagePool(2, 256, PAGE, 40, window=32)
    assert pool.admit(0, 40)              # pages 0..2 of both chains
    np.testing.assert_array_equal(
        np.flatnonzero(pool.window_tables[0] >= 0), [0, 1, 2])
    for pos in range(40, 48):
        assert pool.ensure(0, pos)
        pool.roll(0, pos)
    assert pool.window_pages_released == 0
    oldest = int(pool.window_tables[0, 0])
    assert pool.ensure(0, 48)
    pool.roll(0, 48)                      # page 3 in, page 0 out
    assert pool.window_pages_released == 1
    assert pool.window_tables[0, 0] == -1
    # On the free list at once: it is the page the same roll took for
    # the new position (the list is last in, first out).
    assert pool.window_tables[0, 3] == oldest
    assert pool.window_stats()["free"] == 3
    # A long prompt takes the last three pages of its chain only.
    assert pool.admit(1, 100)
    held = np.flatnonzero(pool.window_tables[1] >= 0)
    np.testing.assert_array_equal(held, [4, 5, 6])
    assert pool.window_stats()["free"] == 0
    assert pool.padded_row(1).shape == (2, 16)
    assert pool.check_invariants() == []
    # A row's two chains move together.
    pool.release(0)
    before = pool.padded_row(1)
    assert pool.handoff(1, 0) == 7
    np.testing.assert_array_equal(pool.padded_row(0), before)
    assert (pool.padded_row(1) < 0).all() and pool.check_invariants() == []
    pool.release(0)
    pool.release(1)
    assert pool.window_stats()["free"] == 6


def test_window_pool_counts_a_broken_window_space():
    pool = WindowedPagePool(2, 256, PAGE, 40, window=32)
    assert pool.admit(0, 40)
    page = int(pool.window_tables[0, 1])
    pool._window_free.append(page)
    assert any("held and on the free list" in line
               for line in pool.check_invariants())
    pool._window_free.pop()
    pool.window_tables[1, 0] = page
    assert any("several table entries" in line
               for line in pool.check_invariants())
    pool.window_tables[1, 0] = -1
    pool._window_free.pop()
    assert any("leaked" in line for line in pool.check_invariants())


def _engine(model, **args):
    from polyaxon_tpu.models import family_of
    from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

    family = family_of(model)
    cfg = family.CONFIGS[model]
    params = family.init(cfg, jax.random.key(0))["params"]
    return ContinuousBatchingEngine(model, cfg, params, slots=2, kv="paged",
                                    page_size=4, kv_pages=32, **args)


@pytest.mark.parametrize("model,whole_pages,matches", [
    ("llama_tiny", False, True), ("kimi_k2_tiny", False, True),
    ("moe_tiny", False, True), ("lfm2_tiny", True, True),
    ("qwen3_next_tiny", False, False), ("nemotron_h_tiny", False, False)])
def test_an_engine_without_window_layers_builds_the_plain_pool(
        model, whole_pages, matches):
    """Decided once, where the pool is built: a llama engine has a
    `PagePool`, no window tables, no `step.window` leaf and nothing of
    a suffix that starts below its match; so has a family whose pages
    hold a latent a token, one whose pages hold a state (whole pages
    only) and one whose rows do (no match at all): each is handed the
    pool it was before a window family's learned to share."""
    engine = _engine(model)
    try:
        assert type(engine._pool) is PagePool
        assert engine._pool.prefix_cache == matches
        assert engine._pool.whole_page_matches == whole_pages
        assert engine._window_tables is None
        assert not hasattr(engine._pool, "suffix_start")
        out = engine.generate([[1, 2, 3, 4, 5, 6, 7, 8, 9, 10]] * 2, 6)
        stats = engine.stats()
    finally:
        engine.stop()
    assert len(out[0]) == 6
    assert "step.window" not in stats["tick_phase_ns"]
    assert not any(key.startswith("kv_window") for key in stats)
    assert stats["kv_pages_free"] == 32
    assert stats["prefill_tokens_recomputed"] == 0
    assert stats["prefill_tokens_matched"] == stats["prefill_tokens_skipped"]


@pytest.mark.parametrize("model,layers", [("smallthinker_tiny", 3),
                                          ("exaone_moe_tiny", 3)])
def test_an_engine_with_window_layers_builds_the_pool_that_shares(model,
                                                                  layers):
    """A family with window layers is given both spaces, the radix tree
    over the full one (whole pages) and the count of window layers a
    suffix program walks, read off the cache; `prefix_cache=False`
    reaches this pool as it reaches the plain one."""
    engine = _engine(model)
    try:
        assert type(engine._pool) is WindowedPagePool
        assert engine._pool.prefix_cache and engine._pool.whole_page_matches
        assert engine._pool.window_layers == layers
        assert engine._suffix_prefill is not None
    finally:
        engine.stop()
    # `prefix_cache=False` reaches this pool; and rows no longer than
    # `window x layers` could skip nothing behind a match, so that pool
    # matches nothing, as it did before it learned to share.
    for args in (dict(prefix_cache=False),
                 dict(max_len=engine._pool.window * layers)):
        engine = _engine(model, **args)
        try:
            assert not engine._pool.prefix_cache
            assert engine._pool.radix_stats()["pages"] == 0
        finally:
            engine.stop()


def _gathered_reference(q, k_pool, v_pool, layer, tables, pos, window):
    """Masked softmax over every gathered page: float32, no kernel."""
    B, H, Hd = q.shape
    KV, page = k_pool.shape[2], k_pool.shape[3]
    keys = llama.paged_gather(k_pool[layer], jnp.maximum(tables, 0))
    vals = llama.paged_gather(v_pool[layer], jnp.maximum(tables, 0))
    _, _, _, valid = llama.paged_coords(pos, tables, page, window)
    keys = jnp.repeat(keys, H // KV, axis=2).astype(jnp.float32)
    vals = jnp.repeat(vals, H // KV, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), keys) * Hd ** -0.5
    s = jnp.where(valid[:, 0], s, -1e30)
    probs = jax.nn.softmax(s, axis=-1) * valid[:, 0].any(-1, keepdims=True)
    return jnp.einsum("bhk,bkhd->bhd", probs, vals)


@pytest.mark.parametrize("hd,window", [(128, 64), (128, 256), (16, 64)])
def test_windowed_kernel_matches_the_masked_gather(hd, window):
    """The streamed form (head size 128) and the pipelined one (16), in
    interpret mode, under a window: rows idle, shorter than the window,
    at its edges and many windows long, in a table whose entries before
    the window were released, with the scratch page poisoned so that a
    fetch of a released entry would show."""
    KV, rep, L, maxp = 2, 2, 3, 64
    positions = np.asarray([-1, 5, window - 1, window, 3 * window + 7,
                            maxp * PAGE - 1], np.int32)
    B = len(positions)
    rng = np.random.default_rng(0)
    most = window // PAGE + 1
    n_pages = B * most + 1
    tables = np.full((B, maxp), -1, np.int32)
    free = list(range(n_pages - 1, 0, -1))
    for b, p in enumerate(positions):
        if p >= 0:
            last = p // PAGE
            for idx in range(max(0, last + 1 - most), last + 1):
                tables[b, idx] = free.pop()
    shape = (L, n_pages, KV, PAGE, hd)
    k_pool = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=shape), jnp.float32)
    if hd % 128 == 0:
        # The streamed form copies only the window's pages; the
        # pipelined one brings a step's pages whatever they are.
        k_pool = k_pool.at[:, 0].set(jnp.nan)
        v_pool = v_pool.at[:, 0].set(jnp.nan)
    q = jnp.asarray(rng.normal(size=(B, KV * rep, hd)), jnp.float32)
    layer = 1
    got = paged_decode_attention(q, k_pool, v_pool, layer,
                                 jnp.asarray(tables), jnp.asarray(positions),
                                 window=window)
    clean = (k_pool.at[:, 0].set(0.0), v_pool.at[:, 0].set(0.0))
    want = _gathered_reference(q, *clean, layer, jnp.asarray(tables),
                               jnp.asarray(positions), window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[0]).any()          # the idle row
    # Without the window the same call reads the released entries as
    # holes: a different answer past the window, the same before it.
    full = paged_decode_attention(q, *clean, layer, jnp.asarray(tables),
                                  jnp.asarray(positions))
    np.testing.assert_allclose(np.asarray(full[1]), np.asarray(want[1]),
                               atol=2e-5, rtol=2e-5)

"""The decode step's sampling keys (ISSUE 25): derived inside the
compiled program from per-slot seeds that change at admission only, bit
for bit the keys the engine used to fold on the host for every token."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.models import llama
from polyaxon_tpu.models.common import sample_row
from polyaxon_tpu.serving.batching import ContinuousBatchingEngine, step_keys

SEEDS = [0, 42, -1, 2**31, 2**40 + 7]


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(llama.CONFIGS["llama_tiny"], dtype=jnp.float32)
    return cfg, llama.init(cfg, jax.random.key(0))["params"]


@pytest.mark.parametrize("seed", SEEDS)
def test_in_program_keys_are_the_host_fold(seed):
    counts = np.arange(301, dtype=np.int32)
    seeds = jnp.asarray(np.full(counts.size, seed, np.int64))
    got = np.asarray(jax.random.key_data(
        jax.jit(step_keys)(seeds, jnp.asarray(counts))))
    base = jax.random.key(seed)
    want = np.stack([
        np.asarray(jax.random.key_data(jax.random.fold_in(base, int(n))))
        for n in counts])
    np.testing.assert_array_equal(got, want)


def _reference(cfg, params, max_len, prompt, variants, temperature, seed,
               top_p, top_k):
    """One request alone, one row: the engine's documented draw,
    `fold_in(key(seed), tokens so far)` over the ragged decode step's
    logits, through the executable (`plain` or `filtered`) that the
    engine ran at each of the request's steps."""
    cache = llama.cb_init_cache(cfg, 1, max_len)
    pos, cur, pre = llama.cb_admission(prompt)
    row = llama.cb_prefill(cfg, params, jnp.asarray([pre], jnp.int32),
                           max_len)
    cache = llama.insert_cache_row(cache, row, jnp.int32(0))
    key, out = jax.random.key(seed), []
    for n, variant in enumerate(variants):
        logits, cache = llama.decode_step_ragged(
            cfg, params, cache, jnp.asarray([cur], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        k = jax.random.fold_in(key, n)
        if temperature <= 0:
            nxt = int(jnp.argmax(logits[0]))
        elif variant == "filtered":
            nxt = int(sample_row(logits[0], k, temperature, top_p, top_k))
        else:
            nxt = int(jax.random.categorical(k, logits[0] / temperature))
        out.append(nxt)
        cur, pos = nxt, pos + 1
    return out


@pytest.mark.parametrize("kv", ["dense", "paged"])
def test_mixed_batch_draws_each_request_as_if_alone(model, kv):
    cfg, params = model
    kwargs = {"page_size": 4} if kv == "paged" else {}
    engine = ContinuousBatchingEngine("llama_tiny", cfg, params, slots=4,
                                      max_len=64, kv=kv, **kwargs)
    # Which executable ran at each request's n-th token, and how many
    # kinds of row each step held: read on the engine's own thread.
    variants: dict[str, dict[int, str]] = {}
    kinds_seen = []

    def recorded(real, variant):
        def call(*args):
            live = [r for r in engine._slot_req if r is not None]
            # A step is launched before the one before it is read, so
            # which token this is, is the engine's count of launches,
            # not `len(r.out)`.
            for b, r in enumerate(engine._slot_req):
                if r is not None:
                    variants.setdefault(r.id, {})[
                        int(engine._counts[b])] = variant
            kinds_seen.append({(r.temperature > 0, r.top_p < 1.0)
                               for r in live})
            return real(*args)
        return call

    engine._step_plain = recorded(engine._step_plain, "plain")
    engine._step_filtered = recorded(engine._step_filtered, "filtered")
    asks = [  # prompt, tokens, temperature, seed, top_p
        ([5, 6, 7], 56, 0.0, 0, 1.0),
        ([1, 2, 3, 4], 44, 0.8, 42, 1.0),
        ([9, 8, 7, 6, 5], 12, 0.9, -1, 0.7),
        ([2, 4, 6], 16, 1.1, 2**40 + 7, 1.0),
        ([7, 1], 10, 0.7, 2**31, 0.9),   # waits for a slot, then reuses one
    ]
    try:
        reqs = []
        for prompt, n, temperature, seed, top_p in asks:
            reqs.append(engine.submit(prompt, n, temperature=temperature,
                                      seed=seed, top_p=top_p))
            deadline = time.monotonic() + 120
            while (len(reqs[0].out) < 3 * len(reqs)
                   and time.monotonic() < deadline):
                time.sleep(0.002)       # staggered: three steps apart
        got = [r.wait(timeout=300) for r in reqs]
    finally:
        engine.stop()
    for req, out, (prompt, n, temperature, seed, top_p) in zip(
            reqs, got, asks):
        steps = variants[req.id]
        assert sorted(steps) == list(range(n))
        want = _reference(cfg, params, 64, prompt,
                          [steps[i] for i in range(n)], temperature, seed,
                          top_p, 0)
        assert out == want, (prompt, seed)
    # greedy, plain-temperature and top-p rows decoded in one batch, and
    # a plain-temperature request saw both executables
    assert any(len(kinds) == 3 for kinds in kinds_seen)
    assert set(variants[reqs[1].id].values()) == {"plain", "filtered"}


def test_decode_steps_build_no_key_on_the_host(model, monkeypatch):
    cfg, params = model
    slots = 16
    engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                      slots=slots, max_len=96)
    calls = []

    def counted(name, real):
        def call(*args, **kwargs):
            calls.append(name)
            return real(*args, **kwargs)
        return call

    try:
        # warm-up: every program compiled (tracing calls jax.random.*)
        engine.generate([[5, 6, 7]], max_new_tokens=4, temperature=0.8,
                        timeout=300)
        monkeypatch.setattr(jax.random, "fold_in",
                            counted("fold_in", jax.random.fold_in))
        monkeypatch.setattr(jax.random, "key",
                            counted("key", jax.random.key))
        monkeypatch.setattr(jnp, "stack", counted("stack", jnp.stack))
        # ... nor, at the admission, a device program to copy an array
        monkeypatch.setattr(jnp, "array", counted("array", jnp.array))
        before = engine.stats()
        engine.generate([[5, 6, 7]], max_new_tokens=60, temperature=0.8,
                        seed=3, timeout=300)
        after = engine.stats()
    finally:
        engine.stop()
        monkeypatch.undo()
    steps = after["decode_steps"] - before["decode_steps"]
    assert steps >= 50
    assert calls == []
    keys_ns = (after["tick_phase_ns"]["step.keys"]
               - before["tick_phase_ns"]["step.keys"]) / steps
    assert keys_ns > 0
    # The parent's `step.keys`, on this machine: one fold_in per slot on
    # a key held in a Python list, and a stack.
    held = [jax.random.key(0)] * slots

    def parent_keys():
        return jnp.stack([jax.random.fold_in(held[b], 7)
                          for b in range(slots)])

    parent_keys().block_until_ready()
    t0 = time.perf_counter_ns()
    for _ in range(50):
        parent_keys()
    parent_ns = (time.perf_counter_ns() - t0) / 50
    assert keys_ns < parent_ns / 10, (keys_ns, parent_ns)


def test_sampling_state_is_uploaded_at_admission_only(model):
    cfg, params = model
    engine = ContinuousBatchingEngine("llama_tiny", cfg, params, slots=2,
                                      max_len=64)
    seen = []
    real = engine._step_plain

    def watch(*args):
        seen.append(args[4:9])    # seeds, counts, temps, top_ps, top_ks
        return real(*args)

    engine._step_plain = watch
    try:
        engine.generate([[5, 6, 7]], max_new_tokens=12, temperature=0.8,
                        seed=2**40 + 7, timeout=300)
        engine.generate([[5, 6, 7]], max_new_tokens=4, timeout=300)
    finally:
        engine.stop()
    first = seen[:12]
    assert len(seen) == 16
    for seeds, counts, temps, top_ps, top_ks in first[1:]:
        # the arrays of the step before, not copies of them
        assert seeds is first[0][0] and temps is first[0][2]
        assert top_ps is first[0][3] and top_ks is first[0][4]
    assert [int(args[1][0]) for args in first] == list(range(12))
    assert int(first[0][0][0]) == 7          # 2**40 + 7, narrowed as key()
    assert float(first[0][2][0]) == pytest.approx(0.8)
    # the slot's next tenant brought its own state
    assert seen[12][0] is not first[0][0]
    assert float(seen[12][2][0]) == 0.0


def test_a_preempted_sampled_request_draws_the_same_tokens(model):
    """Eviction discards a request's tokens and re-admission generates
    them again: the draw is keyed by the tokens it has so far, so it
    starts over at 0 and repeats."""
    cfg, params = model
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
    ask = dict(temperature=0.8, seed=2**40 + 7, klass="best-effort")
    outs = []
    for rival in (False, True):
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=64, kv="paged",
                                          page_size=4)
        try:
            req = engine.submit(prompt, 24, **ask)
            if rival:
                while len(req.out) < 3:
                    time.sleep(0.002)
                engine.submit([7, 7, 7], 2, klass="interactive").wait(
                    timeout=300)
            outs.append(req.wait(timeout=300))
        finally:
            engine.stop()
    assert req.preemptions >= 1
    assert outs[0] == outs[1] and len(outs[1]) == 24
    assert len(set(outs[0])) > 4    # sampled: not a greedy loop

"""Serving runtime tests: HTTP generate endpoint, exact-length grouping
correctness, checkpoint loading, error surfaces."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from polyaxon_tpu.serving import ServingServer, load_params


def _post(url, payload, timeout=120):
    req = urllib.request.Request(
        url + "/v1/generate", method="POST",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.load(resp)


@pytest.fixture(scope="module")
def server():
    with ServingServer("llama_tiny", seed=0) as s:
        yield s


class TestServing:
    def test_health_and_models(self, server):
        with urllib.request.urlopen(server.url + "/healthz", timeout=10) as r:
            assert json.load(r) == {"status": "ok", "model": "llama_tiny"}
        with urllib.request.urlopen(server.url + "/v1/models", timeout=10) as r:
            assert json.load(r) == {"models": ["llama_tiny"]}

    def test_generate_shapes_and_determinism(self, server):
        out = _post(server.url, {"tokens": [[5, 6, 7]], "max_new_tokens": 9})
        assert len(out["tokens"]) == 1 and len(out["tokens"][0]) == 9
        again = _post(server.url, {"tokens": [[5, 6, 7]], "max_new_tokens": 9})
        assert again["tokens"] == out["tokens"]  # greedy is deterministic

    def test_ragged_batch_matches_single_rows(self, server):
        """Grouping by exact length must give each row the same result it
        would get alone (no padding contamination)."""
        rows = [[5, 6, 7], [9, 8, 7, 6, 5], [1, 2, 3]]
        batch = _post(server.url, {"tokens": rows, "max_new_tokens": 6})
        for row, expect in zip(rows, batch["tokens"]):
            solo = _post(server.url, {"tokens": [row], "max_new_tokens": 6})
            assert solo["tokens"][0] == expect

    def test_sampling_uses_seed(self, server):
        a = _post(server.url, {"tokens": [[3, 4]], "max_new_tokens": 8,
                               "temperature": 1.0, "seed": 1})
        b = _post(server.url, {"tokens": [[3, 4]], "max_new_tokens": 8,
                               "temperature": 1.0, "seed": 1})
        c = _post(server.url, {"tokens": [[3, 4]], "max_new_tokens": 8,
                               "temperature": 1.0, "seed": 2})
        assert a["tokens"] == b["tokens"]
        assert a["tokens"] != c["tokens"]  # overwhelmingly likely

    def test_errors_are_typed(self, server):
        for payload in (
            {"tokens": []},                       # empty batch → []
            {"tokens": [[]]},                     # empty prompt
            {"tokens": [[1]], "max_new_tokens": 10**6},  # budget too big
            {"tokens": "nope"},                   # wrong type
        ):
            try:
                out = _post(server.url, payload)
                assert payload == {"tokens": []} and out == {"tokens": []}
            except urllib.error.HTTPError as exc:
                assert exc.code == 400
                assert "error" in json.load(exc)

    def test_negative_budget_rejected(self, server):
        for bad in (-1, 0):
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(server.url, {"tokens": [[1, 2]], "max_new_tokens": bad})
            assert err.value.code == 400

    def test_temperature_sweep_reuses_executable(self, server):
        """Temperature is a traced argument — distinct values must not
        recompile (only greedy vs sampling switches programs)."""
        before = server.engine._compiled.cache_info()
        for t in (0.7, 0.8, 0.95):
            _post(server.url, {"tokens": [[4, 5, 6, 7]], "max_new_tokens": 5,
                               "temperature": t, "seed": 0})
        after = server.engine._compiled.cache_info()
        assert after.misses - before.misses <= 1  # one sampling program

    def test_serve_from_trained_jaxjob_checkpoint(self, tmp_path):
        """The advertised flow: train with checkpointing, then serve the
        artifacts/<uuid>/checkpoints dir (full train-state layout)."""
        from polyaxon_tpu.polyflow import V1JAXJob
        from polyaxon_tpu.runtime import run_jaxjob

        art = str(tmp_path / "run")
        job = V1JAXJob.from_dict({
            "kind": "jaxjob", "mesh": {"axes": {"dp": -1}},
            "checkpointing": {"enabled": True, "intervalSteps": 2,
                              "asyncSave": False},
            "runtime": {"model": "llama_tiny", "steps": 3, "batch_size": 1,
                        "seq_len": 16},
        })
        run_jaxjob(job, artifacts_dir=art)
        with ServingServer("llama_tiny", art + "/checkpoints") as s:
            out = _post(s.url, {"tokens": [[5, 6, 7]], "max_new_tokens": 4})
            assert len(out["tokens"][0]) == 4

    def test_serves_t5_seq2seq(self):
        with ServingServer("t5_tiny", seed=0) as s:
            out = _post(s.url, {"tokens": [[5, 6, 7, 8]], "max_new_tokens": 6})
            assert len(out["tokens"][0]) == 6
            again = _post(s.url, {"tokens": [[5, 6, 7, 8]],
                                  "max_new_tokens": 6})
            assert again["tokens"] == out["tokens"]
            with urllib.request.urlopen(s.url + "/v1/models", timeout=10) as r:
                assert json.load(r) == {"models": ["t5_tiny"]}

    def test_unknown_model_rejected(self):
        with pytest.raises(ValueError, match="not servable"):
            ServingServer("resnet50")

    def test_load_params_restores_checkpoint(self, tmp_path):
        import jax

        from polyaxon_tpu.runtime.checkpoint import CheckpointManager
        from polyaxon_tpu.polyflow.runs import V1JaxCheckpointing

        from polyaxon_tpu.models import llama

        # A checkpoint is what training saves: float32, every projection
        # `[D, N]` under its own name (a served tree, `[N, D]` under
        # `<name>_t`, is not one: tests/test_served_weights.py).
        cfg, _ = load_params("llama_tiny", seed=3)
        params = llama.init(cfg, jax.random.key(3))["params"]
        mutated = jax.tree.map(lambda x: x + 1.0, params)
        ckpt = CheckpointManager(
            str(tmp_path / "ck"),
            V1JaxCheckpointing(enabled=True, interval_steps=1, async_save=False))
        ckpt.save(5, {"params": mutated}, force=True)
        ckpt.close()

        _, restored = load_params("llama_tiny", str(tmp_path / "ck"), seed=3)
        # What was saved comes back, rounded to the dtype a server holds
        # it in (`embed`: cfg.dtype).
        leaf = jax.tree.leaves(restored)[0]
        saved = jax.tree.leaves(mutated)[0]
        assert leaf.dtype == cfg.dtype
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(saved.astype(cfg.dtype)))


class TestContinuousBatching:
    """Slot-pool engine (serving/batching.py): per-request correctness
    must be independent of what else occupies the pool."""

    def test_decode_step_ragged_matches_scalar(self):
        """Rows at different depths in one ragged step == each row run
        alone with the scalar-position decode_step; idle rows stay
        finite."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.models import llama

        cfg = dataclasses.replace(llama.CONFIGS["llama_tiny"],
                                  dtype=jnp.float32)
        params = llama.init(cfg, jax.random.key(0))["params"]
        max_len = 32
        rows = [jax.random.randint(jax.random.key(i + 1), (1, 5 + 3 * i),
                                   0, cfg.vocab_size) for i in range(3)]
        ref = []
        for r in rows:
            _, cache = llama.prefill(cfg, params, r[:, :-1], max_len)
            lg, _ = llama.decode_step(cfg, params, cache, r[0, -1:],
                                      jnp.int32(r.shape[1] - 1))
            ref.append(np.asarray(lg[0]))

        cache = llama.init_cache(cfg, len(rows) + 1, max_len)
        for i, r in enumerate(rows):
            _, c1 = llama.prefill(cfg, params, r[:, :-1], max_len)
            cache = {
                "k": cache["k"].at[:, i].set(c1["k"][:, 0]),
                "v": cache["v"].at[:, i].set(c1["v"][:, 0]),
            }
        tokens = jnp.asarray([r[0, -1] for r in rows] + [0], jnp.int32)
        pos = jnp.asarray([r.shape[1] - 1 for r in rows] + [-1], jnp.int32)
        out, _ = llama.decode_step_ragged(cfg, params, cache, tokens, pos)
        for i in range(len(rows)):
            np.testing.assert_allclose(np.asarray(out[i]), ref[i],
                                       atol=2e-4, rtol=2e-4)
        assert np.isfinite(np.asarray(out[len(rows)])).all()

    def test_matches_static_engine_greedy(self):
        """Continuous batching with mixed prompt lengths and budgets,
        more requests than slots (exercises retire→admit), must equal
        the whole-budget reference generation per request."""
        from polyaxon_tpu.models import llama
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
        from polyaxon_tpu.serving.server import load_params

        cfg, params = load_params("llama_tiny", seed=0)
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=2, max_len=64)
        try:
            prompts = [[5, 6, 7], [9, 8, 7, 6, 5], [1, 2], [3, 4, 5, 6]]
            budgets = [6, 9, 4, 7]
            reqs = [engine.submit(p, b) for p, b in zip(prompts, budgets)]
            outs = [r.wait(timeout=600) for r in reqs]
            import jax.numpy as jnp

            for p, b, got in zip(prompts, budgets, outs):
                expect = np.asarray(llama.generate(
                    cfg, params, jnp.asarray([p], jnp.int32),
                    max_new_tokens=b))[0].tolist()
                assert got == expect, (p, b)
        finally:
            engine.stop()

    def test_http_concurrent_requests(self):
        """Concurrent HTTP clients against --batching continuous each
        get the same tokens the static server produces."""
        import threading

        with ServingServer("llama_tiny", seed=0) as static_s, \
                ServingServer("llama_tiny", seed=0, batching="continuous",
                              slots=3) as cont_s:
            rows = [[5, 6, 7], [9, 8, 7, 6, 5], [1, 2, 3, 4]]
            expect = [
                _post(static_s.url,
                      {"tokens": [r], "max_new_tokens": 5})["tokens"][0]
                for r in rows]
            got: dict[int, list] = {}
            errs: list[Exception] = []

            def worker(i):
                try:
                    got[i] = _post(
                        cont_s.url,
                        {"tokens": [rows[i]],
                         "max_new_tokens": 5})["tokens"][0]
                except Exception as exc:  # noqa: BLE001
                    errs.append(exc)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(rows))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            assert not errs, errs
            assert [got[i] for i in range(len(rows))] == expect

    def test_persistent_step_failure_fails_fast(self):
        """A device that throws on every decode step (e.g. persistent
        OOM) must NOT burn one rebuilt-cache step per queued request:
        after max_step_failures consecutive failures the engine drains
        the queue and stops (ADVICE r2, batching.py fail loop)."""
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
        from polyaxon_tpu.serving.server import load_params

        cfg, params = load_params("llama_tiny", seed=0)
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32)
        calls = {"n": 0}

        def broken_step(*args, **kwargs):
            calls["n"] += 1
            raise RuntimeError("RESOURCE_EXHAUSTED: persistent OOM")

        engine._step_plain = engine._step_filtered = broken_step
        try:
            reqs = [engine.submit([1, 2, 3], 4) for _ in range(6)]
            errs = []
            for r in reqs:
                with pytest.raises(RuntimeError) as exc_info:
                    r.wait(timeout=120)
                errs.append(str(exc_info.value))
            # Fail-fast: exactly max_step_failures device steps, not
            # one per request; the rest drained with a typed error.
            assert calls["n"] == engine.max_step_failures
            assert sum("engine failed" in e for e in errs) == 3
            assert engine.stats()["stopped"] is True
            assert engine.stats()["step_failures"] == 3
            with pytest.raises(RuntimeError, match="engine stopped"):
                engine.submit([1, 2, 3], 4)
        finally:
            engine.stop()

    def test_persistent_admission_failure_fails_fast(self):
        """Device breakage can surface in the admission prefill instead
        of the decode step (each request compiles/runs its own prefill)
        — it must hit the same fail-fast budget, not burn one prefill
        per queued request."""
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
        from polyaxon_tpu.serving.server import load_params

        cfg, params = load_params("llama_tiny", seed=0)
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32)
        calls = {"n": 0}

        def broken_prefill(plen):
            def run(params, prompt):
                calls["n"] += 1
                raise RuntimeError("RESOURCE_EXHAUSTED: prefill OOM")

            return run

        engine._compiled_prefill = broken_prefill
        try:
            with engine._cv:    # all queued before the third one fails
                reqs = [engine.submit([1, 2, 3], 4) for _ in range(6)]
            for r in reqs:
                with pytest.raises(RuntimeError):
                    r.wait(timeout=120)
            assert calls["n"] == engine.max_step_failures
            assert engine.stats()["stopped"] is True
        finally:
            engine.stop()

    def test_fail_fast_releases_live_slots(self):
        """Fail-fast triggered from the admission path must error-and-
        retire requests still LIVE in slots — the loop thread exits, so
        an unretired slot's waiter would block forever."""
        import time as _time

        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
        from polyaxon_tpu.serving.server import load_params

        cfg, params = load_params("llama_tiny", seed=0)
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=4, max_len=256)
        try:
            live = engine.submit([1, 2, 3], 200)  # long-running
            deadline = _time.time() + 60
            while engine.stats()["active"] == 0:
                assert _time.time() < deadline, "request never went live"
                _time.sleep(0.05)

            def broken_prefill(plen):
                def run(params, prompt):
                    raise RuntimeError("RESOURCE_EXHAUSTED")

                return run

            engine._compiled_prefill = broken_prefill
            # One _admit pass hits 3 free slots → 3 consecutive
            # failures before any step can reset the counter.
            bad = [engine.submit([4, 5], 50) for _ in range(3)]
            for r in bad:
                with pytest.raises(RuntimeError):
                    r.wait(timeout=120)
            with pytest.raises(RuntimeError, match="engine failed"):
                live.wait(timeout=120)  # released, not hung
            assert engine.stats()["stopped"] is True
        finally:
            engine.stop()

    def test_bad_request_admission_errors_do_not_stop_engine(self):
        """Request-scoped admission errors (ValueError — not an XLA
        RuntimeError) must not trip the device fail-fast: three bad
        requests in a row would otherwise deny service to everyone."""
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
        from polyaxon_tpu.serving.server import load_params

        cfg, params = load_params("llama_tiny", seed=0)
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32)
        real_admission = engine._family_mod.cb_admission
        state = {"bad": True}

        def sometimes_bad(tokens):
            if state["bad"]:
                raise ValueError("family rejected this prompt")
            return real_admission(tokens)

        import types

        engine._family_mod = types.SimpleNamespace(
            **{n: getattr(engine._family_mod, n)
               for n in dir(engine._family_mod) if not n.startswith("__")})
        engine._family_mod.cb_admission = sometimes_bad
        try:
            bad = [engine.submit([1, 2, 3], 4) for _ in range(4)]
            for r in bad:
                with pytest.raises(RuntimeError, match="rejected"):
                    r.wait(timeout=120)
            assert engine.stats()["stopped"] is False
            state["bad"] = False
            good = engine.submit([1, 2, 3], 4)
            assert len(good.wait(timeout=120)) == 4  # still serving
        finally:
            engine.stop()

    def test_transient_step_failure_recovers(self):
        """One failed step fails only the live requests; the engine
        rebuilds the cache and keeps serving the queue."""
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
        from polyaxon_tpu.serving.server import load_params

        cfg, params = load_params("llama_tiny", seed=0)
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32)
        real_step = engine._step_plain
        calls = {"n": 0}

        def flaky_step(*args, **kwargs):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("transient")
            return real_step(*args, **kwargs)

        engine._step_plain = flaky_step
        try:
            r1 = engine.submit([1, 2, 3], 4)
            with pytest.raises(RuntimeError, match="transient"):
                r1.wait(timeout=120)
            r2 = engine.submit([1, 2, 3], 4)
            out = r2.wait(timeout=120)
            assert len(out) == 4
            assert engine.stats()["stopped"] is False
            assert engine.stats()["step_failures"] == 1
        finally:
            engine.stop()

    def test_over_budget_rejected(self):
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
        from polyaxon_tpu.serving.server import load_params

        cfg, params = load_params("llama_tiny", seed=0)
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=16)
        try:
            with pytest.raises(ValueError, match="exceeds max_len"):
                engine.submit([1] * 10, 10)
        finally:
            engine.stop()

    def test_t5_continuous_matches_static(self, monkeypatch):
        """Seq2seq continuous batching: per-slot encoder state (padded
        cross-KV + length mask) lets requests with different encoder
        lengths share one ragged decoder step — outputs equal the
        static engine. fp32: bf16 reduction-order noise can flip
        argmax between the two decode paths."""
        import dataclasses

        import jax.numpy as jnp

        from polyaxon_tpu.models import t5

        monkeypatch.setitem(
            t5.CONFIGS, "t5_tiny",
            dataclasses.replace(t5.CONFIGS["t5_tiny"], dtype=jnp.float32))
        rows = [[5, 6, 7], [9, 8, 7, 6, 5, 4]]
        with ServingServer("t5_tiny", seed=0) as static_s:
            expect = _post(static_s.url, {"tokens": rows,
                                          "max_new_tokens": 5})["tokens"]
        with ServingServer("t5_tiny", seed=0, batching="continuous",
                           slots=2) as cont_s:
            got = _post(cont_s.url, {"tokens": rows,
                                     "max_new_tokens": 5})["tokens"]
        assert got == expect

    def test_t5_ragged_decode_matches_scalar(self):
        """T5 decode_step_ragged at mixed per-row depths == per-row
        scalar decode_step with its own cross-KV."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.models import t5

        cfg = dataclasses.replace(t5.CONFIGS["t5_tiny"], dtype=jnp.float32)
        params = t5.init(cfg, jax.random.key(0))["params"]
        max_new = 8
        prompts = [jnp.asarray([[5, 6, 7]], jnp.int32),
                   jnp.asarray([[9, 8, 7, 6, 5]], jnp.int32)]
        # Reference: run each request alone, stepping to depth d_i.
        depths = [0, 2]
        refs, pool = [], t5.cb_init_cache(cfg, 3, max_new)
        toks, poss = [], []
        for i, (prompt, depth) in enumerate(zip(prompts, depths)):
            enc = t5.encode(cfg, params, prompt)
            cross = t5.precompute_cross_kv(cfg, params, enc)
            cache = t5.init_decoder_cache(cfg, 1, max_new)
            tok = jnp.asarray([0], jnp.int32)
            for d in range(depth + 1):
                lg, cache = t5.decode_step(cfg, params, cross, cache,
                                           tok, d)
                tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            refs.append((lg, cache, tok))
            # Seed the pool slot: encoder row + replayed decoder KV.
            row = t5.cb_prefill(cfg, params, prompt, max_new)
            pool = t5.insert_cache_row(pool, row, jnp.int32(i))
            pool = {
                **pool,
                "k": pool["k"].at[:, i].set(cache["k"][:, 0]),
                "v": pool["v"].at[:, i].set(cache["v"][:, 0]),
            }
        # One ragged step at each row's NEXT depth (+ an idle row).
        import numpy as np

        tokens = jnp.asarray([int(refs[0][2][0]), int(refs[1][2][0]), 0],
                             jnp.int32)
        pos = jnp.asarray([depths[0] + 1, depths[1] + 1, -1], jnp.int32)
        rag_lg, _ = t5.decode_step_ragged(cfg, params, pool, tokens, pos)
        for i, (prompt, depth) in enumerate(zip(prompts, depths)):
            enc = t5.encode(cfg, params, prompt)
            cross = t5.precompute_cross_kv(cfg, params, enc)
            lg, cache, tok = refs[i]
            want, _ = t5.decode_step(cfg, params, cross, cache, tok,
                                     depth + 1)
            np.testing.assert_allclose(np.asarray(rag_lg[i]),
                                       np.asarray(want[0]),
                                       atol=2e-4, rtol=2e-4)
        assert np.isfinite(np.asarray(rag_lg[2])).all()  # idle row


class TestShardedServing:
    """Mesh-sharded weights: serving an 8B-class model tensor-parallel
    (SURVEY §2b TP row) must be output-identical to single-device."""

    def test_tp_sharded_matches_unsharded(self):
        rows = [[5, 6, 7], [9, 8, 7, 6, 5]]
        with ServingServer("llama_tiny", seed=0) as ref_s:
            expect = _post(ref_s.url,
                           {"tokens": rows, "max_new_tokens": 6})["tokens"]
        with ServingServer("llama_tiny", seed=0,
                           mesh_axes={"tp": 4}) as tp_s:
            assert tp_s.mesh is not None
            got = _post(tp_s.url,
                        {"tokens": rows, "max_new_tokens": 6})["tokens"]
        assert got == expect

    def test_fsdp_all_devices_continuous(self):
        """fsdp=-1 absorbs the whole 8-device mesh; the continuous
        batcher runs on sharded weights too."""
        rows = [[5, 6, 7], [1, 2, 3, 4]]
        with ServingServer("llama_tiny", seed=0) as ref_s:
            expect = _post(ref_s.url,
                           {"tokens": rows, "max_new_tokens": 5})["tokens"]
        with ServingServer("llama_tiny", seed=0, batching="continuous",
                           slots=2, mesh_axes={"fsdp": -1}) as s:
            got = _post(s.url,
                        {"tokens": rows, "max_new_tokens": 5})["tokens"]
        assert got == expect


class TestStreaming:
    @staticmethod
    def _stream(url, payload, timeout=300):
        import urllib.request

        req = urllib.request.Request(
            url + "/v1/generate", method="POST",
            data=json.dumps(dict(payload, stream=True)).encode(),
            headers={"Content-Type": "application/json"})
        events = []
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            assert resp.headers["Content-Type"].startswith(
                "text/event-stream")
            event_name = None
            for raw in resp:
                line = raw.decode().rstrip("\n")
                if line.startswith("event: "):
                    event_name = line[len("event: "):]
                elif line.startswith("data: "):
                    events.append((event_name or "token",
                                   json.loads(line[len("data: "):])))
                    event_name = None
        return events

    def test_streaming_matches_nonstreaming_continuous(self):
        rows = [[5, 6, 7], [9, 8, 7, 6, 5]]
        with ServingServer("llama_tiny", seed=0, batching="continuous",
                           slots=2) as s:
            expect = _post(s.url, {"tokens": rows,
                                   "max_new_tokens": 6})["tokens"]
            events = self._stream(s.url, {"tokens": rows,
                                          "max_new_tokens": 6})
        done = [p for name, p in events if name == "done"]
        assert len(done) == 1 and done[0]["tokens"] == expect
        # Per-token events reassemble into the same rows, in order.
        streamed = [[], []]
        for name, p in events:
            if name == "token":
                streamed[p["index"]].append(p["token"])
        assert streamed == expect

    def test_streaming_static_engine_bursts(self):
        rows = [[5, 6, 7]]
        with ServingServer("llama_tiny", seed=0) as s:
            expect = _post(s.url, {"tokens": rows,
                                   "max_new_tokens": 5})["tokens"]
            events = self._stream(s.url, {"tokens": rows,
                                          "max_new_tokens": 5})
        done = [p for name, p in events if name == "done"]
        assert done and done[0]["tokens"] == expect
        assert [p["token"] for n, p in events if n == "token"] == expect[0]

    @pytest.mark.parametrize("batching", ["continuous", "static"])
    def test_streaming_bad_request_is_http_400(self, batching):
        """Over-budget streaming requests are proper HTTP 400s on BOTH
        engines — never a 200 stream carrying an error event."""
        import urllib.error
        import urllib.request

        with ServingServer("llama_tiny", seed=0, batching=batching,
                           slots=1) as s:
            req = urllib.request.Request(
                s.url + "/v1/generate", method="POST",
                data=json.dumps({"tokens": [[1] * 100],
                                 "max_new_tokens": 10_000,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(req, timeout=60)
            assert err.value.code == 400


class TestStats:
    def test_stats_counters_both_engines(self):
        for batching, engine_name in (("static", "static"),
                                      ("continuous", "continuous")):
            with ServingServer("llama_tiny", seed=0,
                               batching=batching, slots=2) as s:
                _post(s.url, {"tokens": [[5, 6, 7], [1, 2, 3]],
                              "max_new_tokens": 4})
                with urllib.request.urlopen(s.url + "/v1/stats",
                                            timeout=10) as r:
                    stats = json.load(r)
            assert stats["engine"] == engine_name
            assert stats["requests_served"] == 2
            assert stats["tokens_generated"] == 8
            if batching == "continuous":
                assert stats["active"] == 0 and stats["queued"] == 0

    def test_occupancy_gauges_during_burst(self):
        """A burst of more requests than slots must surface in the
        occupancy gauges: queue_depth_peak >= 1 and avg_occupancy in
        (0, 1] — the number that says continuous batching is winning
        (VERDICT r2 item 5)."""
        with ServingServer("llama_tiny", seed=0, batching="continuous",
                           slots=2) as s:
            rows = [[5, 6, 7], [9, 8, 7], [1, 2, 3], [4, 5, 6]]
            _post(s.url, {"tokens": rows, "max_new_tokens": 6},
                  timeout=300)
            with urllib.request.urlopen(s.url + "/v1/stats",
                                        timeout=10) as r:
                stats = json.load(r)
        assert stats["decode_steps"] > 0
        assert stats["queue_depth_peak"] >= 1  # 4 requests, 2 slots
        assert stats["avg_occupancy"] is not None
        assert 0.0 < stats["avg_occupancy"] <= 1.0


class TestSampling:
    """top-p/top-k fused into the compiled step (VERDICT r2 item 5):
    distribution checks at fixed seed, greedy-equivalence over HTTP
    for all families, and request validation."""

    def test_top_k_one_is_argmax(self):
        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.models.common import sample_row

        logits = jnp.asarray([0.3, 2.0, -1.0, 1.4, 0.0])
        for seed in range(8):
            tok = sample_row(logits, jax.random.key(seed),
                             jnp.float32(3.0), jnp.float32(1.0),
                             jnp.int32(1))
            assert int(tok) == 1

    def test_top_k_distribution_matches_renormalized_softmax(self):
        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.models.common import sample_row

        logits = jnp.asarray([2.0, 1.5, 0.5, -0.5, -3.0, 1.0])
        n = 4000
        keys = jax.random.split(jax.random.key(0), n)
        draws = np.asarray(jax.vmap(
            lambda k: sample_row(logits, k, jnp.float32(1.0),
                                 jnp.float32(1.0), jnp.int32(2)))(keys))
        assert set(np.unique(draws)) <= {0, 1}  # only the top-2 ids
        p = jax.nn.softmax(jnp.asarray([2.0, 1.5]))  # renormalized pair
        freq0 = float(np.mean(draws == 0))
        assert abs(freq0 - float(p[0])) < 0.03

    def test_top_p_keeps_minimal_nucleus(self):
        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.models.common import sample_row

        # softmax ≈ [0.63, 0.23, 0.09, 0.03, 0.01]: p=0.5 → nucleus is
        # exactly the argmax; p=0.8 → the top-2.
        logits = jnp.asarray([3.0, 2.0, 1.0, 0.0, -1.0])
        keys = jax.random.split(jax.random.key(1), 500)

        def draw(p):
            return np.asarray(jax.vmap(
                lambda k: sample_row(logits, k, jnp.float32(1.0),
                                     jnp.float32(p), jnp.int32(0)))(keys))

        assert set(np.unique(draw(0.5))) == {0}
        assert set(np.unique(draw(0.8))) <= {0, 1}

    def test_plain_sampling_bit_stable_with_historical_draw(self):
        """sample_logits with filters disabled must reproduce the exact
        jax.random.categorical draw older clients' seeds produced."""
        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.models.common import sample_logits

        logits = jax.random.normal(jax.random.key(3), (4, 16))
        key = jax.random.key(7)
        want = jax.random.categorical(key, logits / 0.7, axis=-1)
        got = sample_logits(logits, key, jnp.float32(0.7))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("model,batching", [
        ("llama_tiny", "static"), ("llama_tiny", "continuous"),
        ("t5_tiny", "static"), ("t5_tiny", "continuous"),
    ])
    def test_top_k_one_equals_greedy_over_http(self, model, batching):
        """temperature high + top_k=1 must equal greedy output for
        every family on both engines — the end-to-end proof the filter
        runs inside the step."""
        kw = {"batching": batching, "slots": 2} if batching == "continuous" \
            else {}
        with ServingServer(model, seed=0, **kw) as s:
            greedy = _post(s.url, {"tokens": [[5, 6, 7]],
                                   "max_new_tokens": 6}, timeout=300)
            topk1 = _post(s.url, {"tokens": [[5, 6, 7]],
                                  "max_new_tokens": 6,
                                  "temperature": 4.0, "top_k": 1,
                                  "seed": 9}, timeout=300)
        assert topk1["tokens"] == greedy["tokens"]

    def test_invalid_sampling_params_rejected(self, server):
        for payload in ({"top_p": 0.0}, {"top_p": 1.5}, {"top_k": -1}):
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(server.url, {"tokens": [[1, 2]],
                                   "max_new_tokens": 2, **payload})
            assert err.value.code == 400

    def test_direct_engine_callers_validated_too(self):
        """Range checks live in the engines, not just the HTTP layer:
        a Python caller passing top_p=0 must get a ValueError, not a
        silent argmax degeneration."""
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
        from polyaxon_tpu.serving.server import load_params

        cfg, params = load_params("llama_tiny", seed=0)
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32)
        try:
            with pytest.raises(ValueError, match="top_p"):
                engine.submit([1, 2], 2, temperature=1.0, top_p=0.0)
            with pytest.raises(ValueError, match="top_k"):
                engine.submit([1, 2], 2, temperature=1.0, top_k=-1)
        finally:
            engine.stop()

    def test_plain_temperature_continuous_seed_stable(self):
        """The continuous engine keeps the historical per-row
        categorical draw when no filter is active — the filtered step
        variant (full-vocab sort) only engages for rows that use
        top_p/top_k, so pre-existing (seed → tokens) mappings hold."""
        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.models import llama
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
        from polyaxon_tpu.serving.server import load_params

        cfg, params = load_params("llama_tiny", seed=0)
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32)
        try:
            got = engine.submit([5, 6, 7], 3, temperature=0.8,
                                seed=42).wait(timeout=300)
            # Reference: the engine's documented draw — fold_in(step)
            # per emitted token over the ragged decode step's logits.
            cache = engine._family_mod.cb_init_cache(cfg, 1, 32)
            pos0, tok0, pre = engine._family_mod.cb_admission([5, 6, 7])
            row_cache = engine._family_mod.cb_prefill(
                cfg, params, jnp.asarray([pre], jnp.int32), 32)
            cache = engine._family_mod.insert_cache_row(
                cache, row_cache, jnp.int32(0))
            key, cur, pos, want = jax.random.key(42), tok0, pos0, []
            for step_i in range(3):
                logits, cache = llama.decode_step_ragged(
                    cfg, params, cache, jnp.asarray([cur], jnp.int32),
                    jnp.asarray([pos], jnp.int32))
                k = jax.random.fold_in(key, step_i)
                nxt = int(jax.random.categorical(k, logits[0] / 0.8))
                want.append(nxt)
                cur, pos = nxt, pos + 1
            assert got == want
        finally:
            engine.stop()


class TestQuantize:
    """Int8 weight-only serving (VERDICT r2 item 10): per-channel
    symmetric quantization over the contraction axis, dequantized
    inside the jitted programs."""

    def test_roundtrip_error_bounded_per_channel(self):
        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.serving.quantize import quantize_leaf

        w = jax.random.normal(jax.random.key(0), (3, 64, 32), jnp.float32)
        qt = quantize_leaf(w)
        assert qt.q.dtype == jnp.int8
        assert qt.scale.shape == (3, 1, 32)  # per-layer per-out-channel
        # Symmetric rounding: |w - deq| <= scale/2 elementwise.
        err = jnp.abs(w - qt.dequantize())
        assert bool(jnp.all(err <= qt.scale / 2 + 1e-7))

    def test_dequantize_tree_identity_on_plain_trees(self):
        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.serving.quantize import dequantize_tree

        tree = {"w": jnp.ones((4, 4)), "b": jnp.zeros(4), "n": 3}
        out = dequantize_tree(tree)
        assert out["w"] is tree["w"] and out["b"] is tree["b"]
        assert out["n"] == 3

    def test_tree_bytes_roughly_halved(self):
        import jax

        from polyaxon_tpu.models import llama
        from polyaxon_tpu.serving.quantize import quantize_tree, tree_bytes

        params = llama.init(llama.CONFIGS["llama_tiny"],
                            jax.random.key(0))["params"]
        full = tree_bytes(params)
        q = quantize_tree(params)
        # bf16 matmul weights -> int8 + f32 scales; 1-D norm gains stay.
        assert tree_bytes(q) < 0.62 * full

    def test_logit_parity_bounded(self):
        """Quantization noise must stay small relative to the logit
        scale: the int8 forward tracks the bf16 forward closely on a
        randomly-initialized llama_tiny."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from polyaxon_tpu.models import llama
        from polyaxon_tpu.serving.quantize import (dequantize_tree,
                                                   quantize_tree)

        cfg = llama.CONFIGS["llama_tiny"]
        params = llama.init(cfg, jax.random.key(0))["params"]
        tokens = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                    cfg.vocab_size)
        ref = np.asarray(llama.forward(cfg, params, tokens))
        deq = dequantize_tree(quantize_tree(params))
        got = np.asarray(llama.forward(cfg, deq, tokens))
        denom = np.maximum(np.abs(ref).max(), 1e-6)
        rel = np.abs(got - ref).max() / denom
        assert rel < 0.05, f"int8 logits off by {rel:.3f} of logit scale"
        # And the distributions stay essentially identical.
        cos = float(np.sum(ref * got)
                    / (np.linalg.norm(ref) * np.linalg.norm(got)))
        assert cos > 0.999

    def test_static_serving_end_to_end_int8(self):
        with ServingServer("llama_tiny", seed=0, quantize="int8") as s:
            out = _post(s.url, {"tokens": [[5, 6, 7]], "max_new_tokens": 8})
            assert len(out["tokens"][0]) == 8
            again = _post(s.url, {"tokens": [[5, 6, 7]],
                                  "max_new_tokens": 8})
            assert again["tokens"] == out["tokens"]  # greedy deterministic

    def test_continuous_matches_static_int8(self):
        """Both engines dequantize the same tree, so int8 greedy decode
        must agree token-for-token between them."""
        import jax

        from polyaxon_tpu.models import llama
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine
        from polyaxon_tpu.serving.quantize import quantize_tree
        from polyaxon_tpu.serving.server import _Engine

        cfg = llama.CONFIGS["llama_tiny"]
        params = quantize_tree(
            llama.init(cfg, jax.random.key(0))["params"])
        static = _Engine("llama_tiny", cfg, params)
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=2)
        try:
            rows = [[5, 6, 7], [1, 2, 3, 4]]
            want = static.generate(rows, max_new_tokens=6)
            got = engine.generate(rows, max_new_tokens=6, timeout=120)
            assert got == want
        finally:
            engine.stop()


class TestStatsPage:
    def test_serving_dashboard_served(self, server):
        import urllib.request

        for path in ("/", "/ui"):
            with urllib.request.urlopen(server.url + path, timeout=10) as r:
                page = r.read().decode()
                assert r.headers["Content-Type"].startswith("text/html")
        assert "/v1/stats" in page and "tokens generated" in page


def _full_tables_on_while_carries(hlo: str, V: int, D: int) -> list:
    """Full-precision [V,D]/[D,V] buffers riding any while-loop carry —
    the hoisted-dequant regression signature both orientation tests
    scan for. Assumes the carry-tuple type prints on the `while(` line
    (XLA text format); the single shared copy is the one to fix when
    that changes."""
    import re

    carried = []
    for m in re.finditer(r"while\(", hlo):
        line = hlo[hlo.rfind("\n", 0, m.start()) + 1:m.start()]
        carried += re.findall(r"(?:bf16|f32)\[(\d+),(\d+)\]", line)
    return [s for s in carried if {int(s[0]), int(s[1])} == {V, D}]


class TestQuantizeInLoop:
    """VERDICT r3 #3: int8 must stay the HBM-resident format through
    the decode scan — the model unwraps each weight at its consumption
    site, so the compiled loop body consumes s8 operands instead of a
    hoisted bf16 copy of the tree."""

    def test_norm_gains_never_quantized(self):
        import jax

        from polyaxon_tpu.models import llama
        from polyaxon_tpu.serving.quantize import QuantizedTensor, quantize_tree

        cfg = llama.CONFIGS["llama_tiny"]
        q = quantize_tree(llama.init(cfg, jax.random.key(0))["params"])
        assert not isinstance(q["layers"]["attn_norm"], QuantizedTensor)
        assert not isinstance(q["final_norm"], QuantizedTensor)
        assert isinstance(q["layers"]["wq"], QuantizedTensor)
        assert isinstance(q["embed"], QuantizedTensor)

    def test_quantized_tree_flows_through_decode_scan(self):
        """Greedy parity with the plain tree, AND the compiled program
        keeps int8 live: s8 buffers present, and no full-table bf16
        embed ([V, D]) is materialized (rows are gathered int8-first)."""
        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.models import llama
        from polyaxon_tpu.serving.quantize import quantize_tree

        cfg = llama.CONFIGS["llama_tiny"]
        plain = llama.init(cfg, jax.random.key(0))["params"]
        quant = quantize_tree(plain)
        prompt = jnp.zeros((2, 8), jnp.int32)

        def run(params, prompt):
            return llama.generate(cfg, params, prompt, max_new_tokens=12)

        out_q = jax.jit(run)(quant, prompt)
        out_p = jax.jit(run)(plain, prompt)
        assert (out_q == out_p).all(), "int8 greedy decode diverged"

        hlo = jax.jit(run).lower(quant, prompt).compile().as_text()
        assert "s8[" in hlo, "quantized weights vanished from the program"
        V, D = cfg.vocab_size, cfg.dim
        assert f"bf16[{V},{D}]" not in hlo, (
            "full embed table dequantized to bf16 — the int8-first "
            "row gather regressed")
        # ADVICE r4 #1/#2: the UNTIED lm_head is [D, V], so a hoisted
        # dequant materializes the TRANSPOSED table — which the [V, D]
        # assert above cannot see. The regression signature is a full-
        # precision full-table buffer riding a while-loop carry (the
        # hoisted table is re-read every decode step).
        full_tables = _full_tables_on_while_carries(hlo, V, D)
        assert not full_tables, (
            f"full-precision lm_head/embed table {full_tables} rides "
            "the decode loop carry — the dequant was hoisted out of "
            "the loop (pin_in_loop regressed)")

    def test_tied_embeddings_quantized_decode(self):
        """The TIED head ([V, D] embed consumed transposed) through the
        full decode scan: greedy parity with the plain tree, and the
        same while-carry guarantee — no full-precision table in either
        orientation rides the loop (the tied table is the embed, so a
        hoist here would double-count the biggest weight)."""
        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.models import llama
        from polyaxon_tpu.serving.quantize import quantize_tree

        cfg = llama.CONFIGS["llama_tiny_tied"]
        assert cfg.tie_embeddings
        plain = llama.init(cfg, jax.random.key(0))["params"]
        assert "lm_head" not in plain  # tied: embed IS the head
        quant = quantize_tree(plain)
        prompt = jnp.zeros((2, 8), jnp.int32)

        def run(params, prompt):
            return llama.generate(cfg, params, prompt, max_new_tokens=10)

        out_q = jax.jit(run)(quant, prompt)
        out_p = jax.jit(run)(plain, prompt)
        assert (out_q == out_p).all(), "tied int8 greedy decode diverged"

        hlo = jax.jit(run).lower(quant, prompt).compile().as_text()
        full = _full_tables_on_while_carries(
            hlo, cfg.vocab_size, cfg.dim)
        assert not full, (
            f"full-precision tied table {full} rides the decode loop "
            "carry — the transposed lm_logits branch regressed")

    def test_families_serve_int8(self):
        """int8 must work for EVERY servable family end-to-end (review
        regression: the t5 encoder stack missed the unwrap-at-
        consumption conversion and only llama was tested). t5 holds
        exact greedy parity; moe does NOT get a parity assert — int8
        error through the top-k router is a discrete re-route, so
        tiny random-init models legitimately diverge mid-sequence —
        but must serve, deterministically."""
        # llama_tiny_tied: no parity assert either — a tied head is the
        # [V, D] embed consumed transposed, so its per-D quant scales sit
        # on the logits CONTRACTION axis and int8 noise flips argmax on
        # tiny random models (prompt-dependent; observed [5,6,7,8]).
        # The load-bearing tied guarantees are serve + determinism here
        # and the while-carry scan in test_tied_embeddings_quantized_decode.
        for model, parity in (("t5_tiny", True), ("moe_tiny", False),
                              ("llama_tiny_tied", False)):
            with ServingServer(model, seed=0) as plain:
                ref = _post(plain.url,
                            {"tokens": [[5, 6, 7, 8]], "max_new_tokens": 5})
            with ServingServer(model, seed=0, quantize="int8") as q:
                out = _post(q.url,
                            {"tokens": [[5, 6, 7, 8]], "max_new_tokens": 5})
                again = _post(q.url,
                              {"tokens": [[5, 6, 7, 8]], "max_new_tokens": 5})
            assert len(out["tokens"][0]) == 5, f"{model} int8 failed"
            assert out["tokens"] == again["tokens"], (
                f"{model} int8 nondeterministic")
            if parity:
                assert out["tokens"] == ref["tokens"], (
                    f"{model} int8 diverged")


class TestChunkedPrefill:
    """vLLM-style chunked prefill on the continuous engine: long
    prompts stream into a standalone row cache N tokens per loop
    iteration instead of blocking the pool on one monolithic prefill;
    the finished row inserts like any admission."""

    def _params(self):
        import jax

        from polyaxon_tpu.models import llama

        cfg = llama.CONFIGS["llama_tiny"]
        return cfg, llama.init(cfg, jax.random.key(0))["params"]

    def test_outputs_identical_to_monolithic_prefill(self):
        """Every prompt-length shape (shorter than the chunk, exact
        multiples, padded tails, single-token) produces the same
        greedy AND sampled output as the unchunked engine."""
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

        cfg, params = self._params()
        prompts = [[7], [1, 2, 3], [5, 6, 7, 8, 9],
                   [4] * 8, [2, 9] * 6 + [1]]  # 1, 3, 5, 8, 13
        want, got = [], []
        for chunk in (None, 4):
            engine = ContinuousBatchingEngine(
                "llama_tiny", cfg, params, slots=2, prefill_chunk=chunk)
            try:
                reqs = [engine.submit(p, 6, temperature=t, seed=11)
                        for p in prompts for t in (0.0, 0.7)]
                outs = [r.wait(timeout=300) for r in reqs]
            finally:
                engine.stop()
            (want if chunk is None else got).append(outs)
        assert got[0] == want[0]

    def test_live_rows_keep_decoding_during_long_admission(self):
        """A short request admitted first must FINISH while the long
        prompt is still observably prefilling — the property chunking
        exists for. (A blocking monolithic prefill can never show
        requests_served >= 1 and prefilling == 1 at the same instant.)"""
        import time as _time

        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

        cfg, params = self._params()
        engine = ContinuousBatchingEngine(
            "llama_tiny", cfg, params, slots=2, prefill_chunk=2)
        try:
            short = engine.submit([5, 6], 4)
            long = engine.submit(list(range(1, 60)), 4)  # ~29 chunks
            interleaved = False
            deadline = _time.monotonic() + 300
            while _time.monotonic() < deadline:
                s = engine.stats()
                if s["requests_served"] >= 1 and s["prefilling"] >= 1:
                    interleaved = True  # short done, long still streaming
                    break
                if s["requests_served"] >= 2:
                    break  # both finished without the window being seen
                _time.sleep(0.005)
            short_out = short.wait(timeout=300)
            long_out = long.wait(timeout=300)
        finally:
            engine.stop()
        assert len(short_out) == 4 and len(long_out) == 4
        assert interleaved, (
            "short request never observed finished while the long "
            "prompt was still prefilling — admission blocked the pool")

    def test_spec_and_chunked_compose(self):
        """Speculative rounds + chunked admission together still equal
        the plain continuous engine's greedy output."""
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

        cfg, params = self._params()
        prompts = [[5, 6, 7, 8, 9, 10, 11], [1, 2, 3]]
        plain = ContinuousBatchingEngine("llama_tiny", cfg, params, slots=2)
        try:
            want = [plain.submit(p, 8).wait(timeout=300) for p in prompts]
        finally:
            plain.stop()
        engine = ContinuousBatchingEngine(
            "llama_tiny", cfg, params, slots=2, prefill_chunk=3,
            draft=("llama_tiny", cfg, params, 3))
        try:
            got = [r.wait(timeout=300)
                   for r in [engine.submit(p, 8) for p in prompts]]
        finally:
            engine.stop()
        assert got == want

    def test_paged_and_static_refused(self):
        from polyaxon_tpu.serving import ServingServer
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

        cfg, params = self._params()
        with pytest.raises(ValueError, match="dense"):
            ContinuousBatchingEngine("llama_tiny", cfg, params,
                                     kv="paged", prefill_chunk=8)
        with pytest.raises(ValueError, match="continuous"):
            ServingServer("llama_tiny", prefill_chunk=8)


class TestEosStop:
    """Per-request early stop: generation retires at the first of the
    request's eos_tokens (inclusive), on every engine."""

    def _expect(self, full, eos_set):
        hit = next((i for i, t in enumerate(full) if t in eos_set), None)
        return full if hit is None else full[:hit + 1]

    def test_static_engine_truncates_at_eos(self, server):
        full = _post(server.url,
                     {"tokens": [[5, 6, 7]], "max_new_tokens": 9}
                     )["tokens"][0]
        eos = full[3]  # guaranteed to occur
        got = _post(server.url, {"tokens": [[5, 6, 7]],
                                 "max_new_tokens": 9,
                                 "eos_token": eos})["tokens"][0]
        assert got == self._expect(full, {eos})
        assert len(got) < 9

    def test_continuous_engines_truncate_at_eos(self):
        import jax

        from polyaxon_tpu.models import llama
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

        cfg = llama.CONFIGS["llama_tiny"]
        params = llama.init(cfg, jax.random.key(0))["params"]
        prompts = [[5, 6, 7], [1, 2, 3, 4]]
        plain = ContinuousBatchingEngine("llama_tiny", cfg, params, slots=2)
        try:
            full = [plain.submit(p, 10).wait(timeout=300) for p in prompts]
        finally:
            plain.stop()
        eos = full[0][2]
        for draft in (None, ("llama_tiny", cfg, params, 3)):
            engine = ContinuousBatchingEngine(
                "llama_tiny", cfg, params, slots=2, draft=draft)
            try:
                got = [engine.submit(p, 10, eos_tokens=[eos])
                       .wait(timeout=300) for p in prompts]
            finally:
                engine.stop()
            label = "spec" if draft else "plain"
            for g, f in zip(got, full):
                assert g == self._expect(f, {eos}), (label, g, f)

    def test_bad_eos_rejected(self, server):
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as err:
            _post(server.url, {"tokens": [[1, 2]], "max_new_tokens": 4,
                               "eos_tokens": ["nope"]})
        assert err.value.code == 400

class TestLmLogitsChunked:
    """common.lm_logits — the chunked quantized head consumption that
    keeps int8 on decode-loop carries (ADVICE r4 #1). The llama_tiny
    e2e tests only exercise the exact-divide path; these cover padding
    (V not a multiple of the chunk) and the tied/transposed layout."""

    def _check(self, D, V, transpose, chunk):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from polyaxon_tpu.models.common import lm_logits
        from polyaxon_tpu.serving.quantize import quantize_leaf

        shape = (V, D) if transpose else (D, V)
        w = jax.random.normal(jax.random.key(0), shape, jnp.float32) * 0.1
        q = quantize_leaf(w)
        x = jax.random.normal(jax.random.key(1), (3, D), jnp.bfloat16)
        got = lm_logits(x, q, jnp.bfloat16, transpose=transpose,
                        chunk=chunk)
        tab = q.dequantize().astype(jnp.bfloat16)
        want = (x @ (tab.T if transpose else tab)).astype(jnp.float32)
        assert got.shape == (3, V)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-2, rtol=1e-2)

    def test_pad_path(self):
        # V=300: chunk 128 → 3 chunks with 84 pad columns sliced off.
        self._check(D=32, V=300, transpose=False, chunk=128)

    def test_tied_transpose_path(self):
        self._check(D=32, V=300, transpose=True, chunk=128)

    def test_tiny_vocab_falls_back(self):
        # V too small to split: the one-dot fallback path.
        self._check(D=16, V=3, transpose=False, chunk=128)
        self._check(D=16, V=3, transpose=True, chunk=128)

    def test_3d_hidden_states(self):
        """decode_chunk passes [B, c, D] hidden states — the chunked
        path must broadcast like the plain matmul does."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from polyaxon_tpu.models.common import lm_logits
        from polyaxon_tpu.serving.quantize import quantize_leaf

        D, V = 16, 256
        w = jax.random.normal(jax.random.key(0), (D, V), jnp.float32) * 0.1
        q = quantize_leaf(w)
        x = jax.random.normal(jax.random.key(1), (2, 5, D), jnp.bfloat16)
        got = lm_logits(x, q, jnp.bfloat16, chunk=64)
        want = (x @ q.dequantize().astype(jnp.bfloat16)).astype(jnp.float32)
        assert got.shape == (2, 5, V)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-2, rtol=1e-2)


# ===================================================== request obs (ISSUE 10)
class TestRequestObservability:
    """ISSUE 10 e2e: concurrent streams against a real continuous
    server leave queue_wait→prefill→decode span timelines behind
    `/requests/{id}/timeline`, per-class SLO series on a line-parsed
    `/metrics` scrape, and shed-load accounting when admission says
    no."""

    _SAMPLE_RE = None  # compiled lazily in _parse_metrics

    @pytest.fixture(scope="class")
    def obs_server(self):
        with ServingServer("llama_tiny", seed=0, batching="continuous",
                           slots=2, prefill_chunk=4) as s:
            yield s

    @staticmethod
    def _timeline(url, request_id):
        with urllib.request.urlopen(
                f"{url}/requests/{request_id}/timeline", timeout=30) as r:
            return json.load(r)

    @staticmethod
    def _parse_metrics(url):
        """Strict 0.0.4 line parse: ({name: type}, {sample: value});
        an unparseable exposition line fails the test, not just the
        missing-series assertion."""
        import re

        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            text = r.read().decode()
        sample_re = re.compile(
            r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
            r'(\{(?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*\})?'
            r' ([-+0-9.eE]+|\+Inf|-Inf|NaN)$')
        types, samples = {}, {}
        for line in text.splitlines():
            if not line.strip():
                continue
            if line.startswith("# TYPE "):
                _, _, name, mtype = line.split(" ")
                types[name] = mtype
            elif not line.startswith("#"):
                match = sample_re.match(line)
                assert match, f"unparseable exposition line: {line!r}"
                samples[match.group(1) + (match.group(2) or "")] = float(
                    match.group(3))
        return types, samples

    def test_concurrent_streams_leave_phase_timelines(self, obs_server):
        import threading

        rows = [[5, 6, 7, 8, 9, 10], [9, 8, 7, 6, 5, 4], [1, 2, 3, 4, 5, 6]]
        results: dict[int, list] = {}
        errs: list[Exception] = []

        def worker(i):
            try:
                results[i] = TestStreaming._stream(
                    obs_server.url,
                    {"tokens": [rows[i]], "max_new_tokens": 6,
                     "class": "interactive"})
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(rows))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not errs, errs

        for i in range(len(rows)):
            done = [p for name, p in results[i] if name == "done"]
            assert len(done) == 1
            assert len(done[0]["tokens"][0]) == 6
            (rid,) = done[0]["request_ids"]
            payload = self._timeline(obs_server.url, rid)
            assert payload["trace_id"] == rid
            (root,) = payload["spans"]
            assert root["name"] == "request"
            phases = [c["name"] for c in root["children"]]
            assert phases[0] == "queue_wait" and phases[-1] == "decode"
            assert "prefill" in phases

            summary = payload["summary"]
            assert summary["request_id"] == rid
            assert summary["class"] == "interactive"
            assert summary["status"] == "ok"
            assert summary["tokens_out"] == 6
            assert summary["events"].get("first_token") == 1
            # 6-token prompt through a 4-token chunked prefill streams
            # at least one chunk.
            assert summary["events"].get("chunk", 0) >= 1
            assert summary["ttft_ms"] is not None and summary["ttft_ms"] > 0
            assert set(summary["phases_ms"]) >= {"queue_wait", "prefill",
                                                 "decode"}

    def test_metrics_scrape_has_per_class_slo_series(self, obs_server):
        _post(obs_server.url, {"tokens": [[5, 6, 7], [7, 6, 5]],
                               "max_new_tokens": 5, "class": "scrape"})
        types, samples = self._parse_metrics(obs_server.url)
        for name in ("polyaxon_serving_ttft_seconds",
                     "polyaxon_serving_tpot_seconds",
                     "polyaxon_serving_queue_wait_seconds",
                     "polyaxon_serving_engine_tick_seconds"):
            assert types[name] == "histogram", name
        assert types["polyaxon_serving_rejected_total"] == "counter"
        assert types["polyaxon_serving_batch_slots"] == "gauge"
        # Both rows of the labeled request landed in every SLO family.
        for stem in ("ttft", "tpot", "queue_wait"):
            key = (f'polyaxon_serving_{stem}_seconds_count'
                   '{class="scrape"}')
            assert samples.get(key, 0) >= 2, key
        assert samples['polyaxon_serving_engine_tick_seconds_count'] > 0
        assert ('polyaxon_serving_admissions_total{outcome="admitted"}'
                in samples)
        # Tick telemetry gauges expose the batch composition states.
        for state in ("decode", "prefill", "free"):
            assert (f'polyaxon_serving_batch_slots{{state="{state}"}}'
                    in samples), state

    def test_requests_listing_and_unknown_id_404(self, obs_server):
        out = _post(obs_server.url, {"tokens": [[4, 5, 6]],
                                     "max_new_tokens": 3})
        (rid,) = out["request_ids"]
        with urllib.request.urlopen(obs_server.url + "/requests",
                                    timeout=30) as r:
            listing = json.load(r)["requests"]
        mine = [row for row in listing if row["request_id"] == rid]
        assert mine and mine[0]["class"] == "batch"
        assert mine[0]["done"] is True and mine[0]["status"] == "ok"
        with pytest.raises(urllib.error.HTTPError) as err:
            self._timeline(obs_server.url, "deadbeef" * 2)
        assert err.value.code == 404
        assert "unknown or evicted" in json.load(err.value)["error"]

    def test_static_engine_has_no_timelines(self, server):
        for path in ("/requests", "/requests/deadbeef/timeline"):
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(server.url + path, timeout=30)
            assert err.value.code == 404
            assert "continuous" in json.load(err.value)["error"]

    def test_shed_load_is_accounted(self):
        """queue_full and shutdown rejections land in the labeled
        rejected counter AND stats()["rejected"]; a rejected request
        never occupies timeline-ring capacity."""
        import time

        from polyaxon_tpu.obs import metrics as obs_metrics
        from polyaxon_tpu.serving.batching import (ContinuousBatchingEngine,
                                                   QueueFull)

        cfg, params = load_params("llama_tiny", seed=0)
        engine = ContinuousBatchingEngine("llama_tiny", cfg, params,
                                          slots=1, max_len=32,
                                          max_pending=1)
        rejected = obs_metrics.serving_rejected_total()
        base_full = rejected.value(reason="queue_full")
        base_stop = rejected.value(reason="shutdown")
        try:
            real_plain = engine._step_plain

            def slow_step(*args, **kwargs):
                time.sleep(0.05)
                return real_plain(*args, **kwargs)

            engine._step_plain = slow_step
            accepted = [engine.submit([1, 2, 3], 8)]
            with pytest.raises(QueueFull) as err:
                for _ in range(4):  # 1-deep queue: full within a few
                    accepted.append(engine.submit([1, 2, 3], 8))
            assert err.value.retry_after >= 1
            for req in accepted:
                req.wait(timeout=600)
            stats = engine.stats()
            assert stats["rejected"]["queue_full"] >= 1
            assert rejected.value(reason="queue_full") > base_full
            # Ring holds exactly the accepted requests.
            assert stats["traced_requests"] == len(accepted)
        finally:
            engine.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            engine.submit([1, 2, 3], 4)
        assert engine.stats()["rejected"]["shutdown"] >= 1
        assert rejected.value(reason="shutdown") > base_stop


@pytest.mark.slow
class TestTracingOverhead:
    """ISSUE 10 acceptance: request tracing ON vs OFF must cost <= 5%
    throughput on the same workload (min-of-3 wall clock; a small
    absolute allowance absorbs scheduler jitter on the CPU-tiny
    model)."""

    def test_tracing_overhead_within_five_percent(self):
        import time

        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

        cfg, params = load_params("llama_tiny", seed=0)

        def best_wall(tracing):
            engine = ContinuousBatchingEngine(
                "llama_tiny", cfg, params, slots=4, max_len=64,
                request_tracing=tracing)
            try:
                engine.submit([7] * 8, 4).wait(timeout=600)  # warm
                best = None
                for _ in range(3):
                    t0 = time.perf_counter()
                    reqs = [engine.submit([7] * 8, 24) for _ in range(16)]
                    for req in reqs:
                        req.wait(timeout=600)
                    wall = time.perf_counter() - t0
                    best = wall if best is None else min(best, wall)
                assert engine.stats()["traced_requests"] == (
                    49 if tracing else 0)
                return best
            finally:
                engine.stop()

        untraced = best_wall(False)
        traced = best_wall(True)
        assert traced <= untraced * 1.05 + 0.025, (
            f"tracing overhead: {traced:.3f}s traced vs "
            f"{untraced:.3f}s untraced")


class TestRunAhead:
    """ISSUE 34: the decode loop runs one step ahead of the host. A
    step is launched before the one before it is read back, the tokens
    go from step to step on the device, and whatever needs the host's
    tokens whole reads the step in flight first."""

    MAX_LEN = 64

    @pytest.fixture(scope="class")
    def model(self):
        import dataclasses

        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.models import llama

        # float32: a row decoded alone and in a batch pick one argmax
        cfg = dataclasses.replace(llama.CONFIGS["llama_tiny"],
                                  dtype=jnp.float32)
        return cfg, llama.init(cfg, jax.random.key(0))["params"]

    def _alone(self, model, prompt, n, temperature=0.0, seed=0):
        """One request, one row, no engine: the ragged decode step's
        logits, the draw keyed by `step_keys(seed, tokens so far)`."""
        import functools

        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.models import llama
        from polyaxon_tpu.serving.batching import step_keys

        cfg, params = model
        cache = llama.cb_init_cache(cfg, 1, self.MAX_LEN)
        pos, cur, pre = llama.cb_admission(prompt)
        row = llama.cb_prefill(cfg, params, jnp.asarray([pre], jnp.int32),
                               self.MAX_LEN)
        cache = llama.insert_cache_row(cache, row, jnp.int32(0))
        keys = step_keys(jnp.asarray(np.full(n, seed, np.int64)),
                         jnp.arange(n, dtype=jnp.int32))
        step = jax.jit(functools.partial(llama.decode_step_ragged, cfg))
        out = []
        for i in range(n):
            logits, cache = step(params, cache,
                                 jnp.asarray([cur], jnp.int32),
                                 jnp.asarray([pos], jnp.int32))
            if temperature > 0:
                cur = int(jax.random.categorical(
                    keys[i], logits[0] / temperature))
            else:
                cur = int(jnp.argmax(logits[0]))
            out.append(cur)
            pos += 1
        return out

    def _engine(self, model, **kwargs):
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

        cfg, params = model
        kwargs.setdefault("slots", 2)
        engine = ContinuousBatchingEngine(
            "llama_tiny", cfg, params, max_len=self.MAX_LEN, kv="paged",
            page_size=4, **kwargs)
        # Which requests each launch computed a token for, in order.
        engine.launched = []
        for name in ("_step_plain", "_step_filtered"):
            def call(*args, real=getattr(engine, name)):
                engine.launched.append(
                    [r.id for r in engine._slot_req if r is not None])
                return real(*args)
            setattr(engine, name, call)
        return engine

    @staticmethod
    def _launches(engine, req):
        return [i for i, ids in enumerate(engine.launched) if req.id in ids]

    @staticmethod
    def _wait_for_tokens(req, n):
        import time

        deadline = time.monotonic() + 120
        while len(req.out) < n and time.monotonic() < deadline:
            time.sleep(0.001)
        assert len(req.out) >= n

    @staticmethod
    def _sound(stats, dropped=0):
        assert stats["decode_tokens_dropped"] == dropped
        assert stats["kv_invariant_violations"] == 0
        assert stats["kv_pages_free"] == stats["kv_pages_total"]
        assert stats["step_failures"] == 0

    @pytest.mark.parametrize("budgets", [(9, 9, 9), (7, 8, 9)],
                             ids=["same_step", "consecutive_steps"])
    def test_rows_ending_by_budget_decode_as_if_alone(self, model, budgets):
        prompts = [[5, 6, 7], [1, 2, 3, 4], [9, 8, 7, 6, 5],
                   [2, 4, 6], [7, 1], [3, 3, 3, 3]]
        engine = self._engine(model, slots=3)
        try:
            with engine._cv:    # all queued before the loop picks one
                reqs = [engine.submit(p, budgets[i % 3])
                        for i, p in enumerate(prompts)]
            outs = [r.wait(timeout=300) for r in reqs]
            stats = engine.stats()
        finally:
            engine.stop()
        for req, prompt, out in zip(reqs, prompts, outs):
            assert out == self._alone(model, prompt, req.max_new), prompt
        # the first three went live together and ended as the case says
        ends = [self._launches(engine, r)[-1] for r in reqs[:3]]
        assert [e - ends[0] for e in ends] == [b - budgets[0]
                                               for b in budgets]
        # a slot freed at the launch that reached its row's budget is
        # filled for the launch after: no step ran with a row missing
        assert all(len(ids) == 3 for ids in engine.launched[:ends[0] + 1])
        self._sound(stats)
        assert stats["requests_served"] == len(prompts)
        assert stats["tokens_generated"] == sum(len(o) for o in outs)
        # every step but the first after an idle engine ran ahead
        assert stats["decode_steps_ahead"] >= stats["decode_steps"] - 2

    def test_seeded_sampling_draws_by_the_hosts_count(self, model):
        asks = [([5, 6, 7], 20, 0.8, 42), ([1, 2, 3, 4], 14, 1.1, 2**40 + 7),
                ([9, 8, 7], 9, 0.0, 0), ([2, 4, 6], 11, 0.7, 2**31)]
        engine = self._engine(model, slots=2)
        try:
            with engine._cv:
                reqs = [engine.submit(p, n, temperature=t, seed=s)
                        for p, n, t, s in asks]
            outs = [r.wait(timeout=300) for r in reqs]
            stats = engine.stats()
        finally:
            engine.stop()
        for (prompt, n, temperature, seed), out in zip(asks, outs):
            assert out == self._alone(model, prompt, n, temperature, seed)
        self._sound(stats)

    def test_a_stop_token_is_seen_one_step_late_and_nothing_leaks(
            self, model):
        """The stopped row's one extra step is computed and dropped;
        its slot is filled between that launch and its readback, and
        the new tenant gets its own tokens only."""
        stopper, runner, tenant = [5, 6, 7], [1, 2, 3, 4], [9, 8, 7, 6, 5]
        full = self._alone(model, stopper, 12)
        at = next(i for i in range(2, 12) if full[i] not in full[:i])
        engine = self._engine(model, slots=2)
        try:
            with engine._cv:
                a = engine.submit(stopper, 12, eos_tokens=[full[at]])
                c = engine.submit(runner, 40)
                b = engine.submit(tenant, 10)
            got = [r.wait(timeout=300) for r in (a, c, b)]
            stats = engine.stats()
        finally:
            engine.stop()
        assert got[0] == full[:at + 1]
        assert got[1] == self._alone(model, runner, 40)
        assert got[2] == self._alone(model, tenant, 10)
        # one more launch than tokens: the step behind the stop token
        assert len(self._launches(engine, a)) == at + 2
        # ... and the tenant's first launch is the one right after it
        assert (self._launches(engine, b)[0]
                == self._launches(engine, a)[-1] + 1)
        self._sound(stats, dropped=1)
        assert stats["tokens_generated"] == sum(len(g) for g in got)

    def test_a_cancelled_row_keeps_the_tokens_of_the_step_in_flight(
            self, model):
        engine = self._engine(model, slots=2)
        try:
            with engine._cv:
                a = engine.submit([5, 6, 7], 50)
                c = engine.submit([1, 2, 3, 4], 30)
            self._wait_for_tokens(a, 5)
            engine.cancel(a)
            with pytest.raises(RuntimeError, match="cancelled"):
                a.wait(timeout=300)
            out_c = c.wait(timeout=300)
            stats = engine.stats()
        finally:
            engine.stop()
        # every token computed for the cancelled row was read: none lost
        assert 5 <= len(a.out) == len(self._launches(engine, a)) < 50
        assert a.out == self._alone(model, [5, 6, 7], len(a.out))
        assert out_c == self._alone(model, [1, 2, 3, 4], 30)
        self._sound(stats)

    def test_a_preempted_row_is_requeued_with_its_tokens_whole(self, model):
        engine = self._engine(model, slots=1)
        seen = []
        real = engine._evict_slot

        def evict(b, reason):
            req = engine._slot_req[b]
            seen.append((len(engine._unread), len(req.out),
                         len(self._launches(engine, req))))
            return real(b, reason)

        engine._evict_slot = evict
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
        try:
            be = engine.submit(prompt, 24, klass="best-effort")
            self._wait_for_tokens(be, 4)
            ia = engine.submit([7, 7, 7], 3, klass="interactive")
            out_ia = ia.wait(timeout=300)
            out_be = be.wait(timeout=300)
            stats = engine.stats()
        finally:
            engine.stop()
        assert be.preemptions >= 1 and len(seen) == be.preemptions
        for unread, have, launched in seen[:1]:
            assert unread == 0 and have == launched >= 4
        assert out_ia == self._alone(model, [7, 7, 7], 3)
        assert out_be == self._alone(model, prompt, 24)
        self._sound(stats)

    def test_stop_reads_the_step_in_flight(self, model):
        engine = self._engine(model, slots=1)
        try:
            a = engine.submit([5, 6, 7], 50)
            self._wait_for_tokens(a, 3)
        finally:
            engine.stop()
        assert a.done.is_set() and not engine._unread
        assert 3 <= len(a.out) == len(self._launches(engine, a))
        assert a.out == self._alone(model, [5, 6, 7], 50)[:len(a.out)]
        if len(a.out) < 50:
            assert a.error == "engine stopped"

    def test_an_error_at_the_readback_is_one_failure(self, model):
        """A device error surfaces where the tokens are read, with a
        step already queued behind the failed one: one failure, every
        request in either step fails with it (the one that had left
        its slot for its last token too), the next is served."""
        engine = self._engine(model, slots=2)
        real = engine._read_oldest
        armed = [True]

        def read_oldest():
            if armed[0] and any(last for _, _, last in
                                engine._unread[0].rows):
                armed[0] = False
                assert len(engine._unread) == 2
                raise RuntimeError("device lost")
            return real()

        engine._read_oldest = read_oldest
        try:
            with engine._cv:
                a = engine.submit([5, 6, 7], 4)     # leaves its slot first
                b = engine.submit([1, 2, 3, 4], 30)
                c = engine.submit([9, 8, 7], 6)     # takes a's slot
            for req in (a, b, c):
                with pytest.raises(RuntimeError, match="device lost"):
                    req.wait(timeout=300)
            assert len(a.out) == 3
            d = engine.submit([2, 4, 6], 8)
            out_d = d.wait(timeout=300)
            stats = engine.stats()
        finally:
            engine.stop()
        assert out_d == self._alone(model, [2, 4, 6], 8)
        assert stats["step_failures"] == 1 and stats["stopped"] is False
        assert stats["kv_invariant_violations"] == 0
        assert stats["kv_pages_free"] == stats["kv_pages_total"]

    def test_a_pool_run_dry_fails_that_row_with_its_token_read(self, model):
        """4 usable pages of 4: both rows hold two and want a third at
        position 8. The first to ask fails loudly, with every token
        computed for it read; its pages let the other finish."""
        engine = self._engine(model, slots=2, kv_pages=4)
        try:
            with engine._cv:
                a = engine.submit([5, 6, 7], 8)
                b = engine.submit([9, 8, 7], 8)
            with pytest.raises(RuntimeError, match="pool exhausted"):
                a.wait(timeout=300)
            out_b = b.wait(timeout=300)
            stats = engine.stats()
        finally:
            engine.stop()
        assert out_b == self._alone(model, [9, 8, 7], 8)
        assert len(a.out) == len(self._launches(engine, a)) == 6
        assert a.out == self._alone(model, [5, 6, 7], 6)
        assert stats["decode_tokens_dropped"] == 0
        assert stats["kv_invariant_violations"] == 0

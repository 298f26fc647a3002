"""Attention ops: flash (Pallas), ring (cp), ulysses (all-to-all) vs the
einsum reference. Runs on the 8-device virtual CPU mesh (conftest), the
same way the driver's dryrun validates sharding."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from polyaxon_tpu.ops.attention import dot_product_attention, xla_attention
from polyaxon_tpu.ops.flash import flash_attention
from polyaxon_tpu.ops.ring import ring_attention
from polyaxon_tpu.ops.ulysses import ulysses_attention


def _qkv(b=2, s=256, h=4, kv=2, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    return q, k, v


class TestFlash:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        ref = xla_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gqa_grouping(self):
        q, k, v = _qkv(h=8, kv=2)
        ref = xla_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gradients_match(self):
        q, k, v = _qkv()

        def loss(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v) ** 2
            )

        gf = jax.grad(loss(lambda *a: flash_attention(*a, block_q=128, block_k=128)),
                      argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda *a: xla_attention(*a)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    @pytest.mark.parametrize("axes", [
        {"fsdp": 4}, {"dp": 2, "tp": 2}, {"dp": 2, "fsdp": 2, "tp": 2}])
    def test_partitioned_under_mesh_matches_reference(self, cpu_devices,
                                                      axes):
        """Under a multi-device ambient mesh the kernel runs per shard
        of (batch, kv heads) inside shard_map (a Mosaic call cannot be
        partitioned by GSPMD — tests/test_aot_tpu_compile.py holds the
        compiler to that). Values and gradients must not notice, with
        packed segments riding the batch sharding."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        n = int(np.prod(list(axes.values())))
        mesh = Mesh(np.array(cpu_devices[:n]).reshape(tuple(axes.values())),
                    tuple(axes))
        q, k, v = _qkv(b=4, h=4, kv=2)
        seg = jnp.repeat(jnp.arange(4, dtype=jnp.int32), 64)[None].repeat(
            4, axis=0)
        batch = tuple(a for a in ("dp", "fsdp") if a in axes)
        place = lambda x: jax.device_put(x, NamedSharding(  # noqa: E731
            mesh, P(batch, *([None] * (x.ndim - 1)))))

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v, segment_ids=seg) ** 2)

        want, gw = jax.value_and_grad(loss(xla_attention), (0, 1, 2))(q, k, v)
        with mesh:
            got, gg = jax.jit(jax.value_and_grad(
                loss(functools.partial(flash_attention, block_q=128,
                                       block_k=128, bwd_impl="pallas")),
                (0, 1, 2)))(place(q), place(k), place(v))
        np.testing.assert_allclose(got, want, rtol=2e-5)
        for a, b in zip(gg, gw):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_partitioned_inside_an_enclosing_manual_region(self,
                                                           cpu_devices):
        """Called from a body that already bound some axes (the pipeline
        binds `pp`), the kernel's shard_map nests over the rest: it must
        take the context's mesh (a nested shard_map refuses the concrete
        one) and bind only the unbound axes."""
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(cpu_devices[:4]).reshape(2, 2), ("pp", "fsdp"))
        q, k, v = _qkv(b=4, h=4, kv=2)
        want = xla_attention(q, k, v)

        def stage(q, k, v):  # pp is manual here, fsdp still automatic
            assert jax.sharding.get_abstract_mesh().manual_axes == ("pp",)
            return flash_attention(q, k, v, block_q=128, block_k=128)

        with mesh:
            got = jax.jit(jax.shard_map(
                stage, mesh=mesh, in_specs=P(), out_specs=P(),
                axis_names={"pp"}, check_vma=False))(q, k, v)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_interpret_mode_is_refused_off_cpu(self, monkeypatch):
        """No chip path may land on an interpreted kernel: interpret
        mode resolves from the CPU backend only and an explicit request
        anywhere else raises."""
        from polyaxon_tpu.ops import flash

        assert flash.resolve_interpret(None) is True  # the CPU test mesh
        assert flash.resolve_interpret(False) is False  # AOT for a topology
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert flash.resolve_interpret(None) is False
        with pytest.raises(ValueError, match="CPU backend only"):
            flash.resolve_interpret(True)

    def test_give_way_is_loud_and_named(self):
        """A shape that does not tile runs the einsum reference — with
        a warning naming the shape, and `implementation_for` telling
        callers the same decision in advance."""
        from polyaxon_tpu.ops import flash

        assert flash.implementation_for(2048, 2048, 64) == "pallas"
        assert flash.implementation_for(96, 96, 64) == "einsum"
        assert flash.implementation_for(256, 256, 80) == "einsum"
        q, k, v = _qkv(s=96)
        with pytest.warns(flash.KernelFallbackWarning, match="Sq=96"):
            out = flash_attention(q, k, v)
        np.testing.assert_allclose(out, xla_attention(q, k, v),
                                   atol=2e-5, rtol=2e-5)

    def test_sliding_window_matches_band_mask(self):
        """xla window path equals an explicit band-mask softmax, and the
        Pallas kernel (block skipping + in-block band) matches it."""
        q, k, v = _qkv(s=256)
        W = 64

        # Explicit reference: full logits with a band mask.
        from polyaxon_tpu.ops.attention import repeat_kv

        kf, vf = repeat_kv(k, 2), repeat_kv(v, 2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kf) * (64 ** -0.5)
        rows = jnp.arange(256)[:, None]
        cols = jnp.arange(256)[None, :]
        band = (rows >= cols) & (rows - cols < W)
        logits = jnp.where(band[None, None], logits, -1e30)
        ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), vf)

        out_xla = xla_attention(q, k, v, causal=True, window=W)
        np.testing.assert_allclose(out_xla, ref, atol=2e-5, rtol=2e-5)
        out_flash = flash_attention(q, k, v, causal=True, window=W,
                                    block_q=128, block_k=128)
        np.testing.assert_allclose(out_flash, ref, atol=2e-5, rtol=2e-5)

    def test_sliding_window_gradients_match(self):
        q, k, v = _qkv(s=256)

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

        gf = jax.grad(loss(lambda *a: flash_attention(
            *a, window=64, block_q=128, block_k=128)), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda *a: xla_attention(*a, window=64)),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_sliding_window_decode_matches_forward(self):
        """Cache decode with a window reproduces windowed teacher-forced
        logits at the last position."""
        import dataclasses

        from polyaxon_tpu.models import llama

        cfg = dataclasses.replace(llama.CONFIGS["llama_tiny"],
                                  dtype=jnp.float32, sliding_window=8)
        variables = llama.init(cfg, jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (2, 24), 0, cfg.vocab_size)
        full = llama.forward(cfg, variables["params"], toks)
        logits, cache = llama.prefill(cfg, variables["params"], toks[:, :-1], 24)
        step_logits, _ = llama.decode_step(
            cfg, variables["params"], cache, toks[:, -1], jnp.int32(23))
        np.testing.assert_allclose(step_logits, full[:, -1], atol=2e-4,
                                   rtol=2e-4)

    def test_packed_segments_match_reference(self):
        """Flash with segment_ids equals the einsum reference's packed
        mask, forward and gradients — including combined with causal."""
        q, k, v = _qkv(s=256)
        seg = jnp.asarray(
            [[0] * 100 + [1] * 156, [0] * 200 + [1] * 56], jnp.int32)

        ref = xla_attention(q, k, v, causal=True, segment_ids=seg)
        out = flash_attention(q, k, v, causal=True, segment_ids=seg,
                              block_q=128, block_k=128)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

        gf = jax.grad(loss(lambda *a: flash_attention(
            *a, segment_ids=seg, block_q=128, block_k=128)),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda *a: xla_attention(*a, segment_ids=seg)),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_packed_plus_window_matches_reference(self):
        q, k, v = _qkv(s=256)
        seg = jnp.asarray([[0] * 128 + [1] * 128] * 2, jnp.int32)
        ref = xla_attention(q, k, v, causal=True, segment_ids=seg, window=32)
        out = flash_attention(q, k, v, causal=True, segment_ids=seg,
                              window=32, block_q=128, block_k=128)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_rolling_cache_matches_full_forward_across_wraps(self):
        """Sliding-window decode uses an O(window) ring-buffer cache;
        greedy generation must match feeding the growing sequence through
        the full windowed forward pass — across several ring wraps."""
        import dataclasses

        from polyaxon_tpu.models import llama

        cfg = dataclasses.replace(llama.CONFIGS["llama_tiny"],
                                  dtype=jnp.float32, sliding_window=8)
        variables = llama.init(cfg, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(1), (1, 4), 0, cfg.vocab_size)
        n_new = 20  # >> window: the ring wraps multiple times

        out = llama.generate(cfg, variables["params"], prompt,
                             max_new_tokens=n_new)
        # Cache really is window-sized (pure shape arithmetic).
        assert llama.cache_len(cfg, 4 + n_new) == 8

        seq = prompt
        for _ in range(n_new):
            logits = llama.forward(cfg, variables["params"], seq)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(seq[:, 4:]))

    def test_window_zero_rejected_everywhere(self):
        q, k, v = _qkv(s=256)
        for fn in (lambda: xla_attention(q, k, v, causal=True, window=0),
                   lambda: flash_attention(q, k, v, causal=True, window=0),
                   lambda: xla_attention(q, k, v, causal=False, window=8)):
            with pytest.raises(ValueError):
                fn()

    def test_small_seq_falls_back(self):
        q, k, v = _qkv(s=64)  # < 128: cannot tile → xla fallback path
        ref = xla_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=1e-6)

    def test_dispatch(self):
        q, k, v = _qkv()
        out = dot_product_attention(q, k, v, impl="flash")
        ref = dot_product_attention(q, k, v, impl="xla")
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_auto_blocks_pick(self):
        """The one tile rule: per kernel the rows a grid step keeps, the
        positions it copies and the sub-block of a turn; tiles divide
        the seq, stay >= 128 where the seq allows, and a tight budget
        forces smaller tiles than a loose one."""
        from polyaxon_tpu.ops.flash import (COPIED, KERNELS, RESIDENT, SUB,
                                            _tile_bytes, auto_blocks)

        tiles = auto_blocks(2048, 2048, 64)
        assert tuple(tiles) == KERNELS
        for kernel, (bq, bk, sub) in tiles.items():
            assert 2048 % bq == 0 and 2048 % bk == 0
            assert bq >= 128 and bk >= 128
            # The looped axis: keys in fwd / dq, query rows in dk/dv.
            assert (bq if kernel == "dkdv" else bk) % sub == 0
        bq, bk, _ = tiles["fwd"]
        assert _tile_bytes(bq, bk, 64) <= 14 * 2**20
        # Tight budget → strictly smaller tiles than the default.
        tq, tk, _ = auto_blocks(2048, 2048, 64, vmem_budget=2**20)["fwd"]
        assert tq * tk < bq * bk
        # Non-power-of-two seq still yields a dividing tile.
        oq, ok_, osub = auto_blocks(1536, 1536, 128)["fwd"]
        assert 1536 % oq == 0 and 1536 % ok_ == 0 and ok_ % osub == 0
        # A copied block no longer than the window: a longer one copies
        # keys its rows cannot see.
        assert auto_blocks(8192, 8192, 128, window=1024)["fwd"][1] <= 1024
        # Long rows at the train cell's head size: the constants as they
        # were counted on the chip, dk/dv keeping keys.
        assert auto_blocks(16384, 16384, 128) == {
            "fwd": (RESIDENT, COPIED, SUB), "dkdv": (COPIED, RESIDENT, SUB),
            "dq": (RESIDENT, COPIED, SUB)}
        # Explicit sizes are the forward's tile and the backward's limit.
        assert auto_blocks(2048, 2048, 64, block_q=128, block_k=256) == {
            "fwd": (128, 256, 256), "dkdv": (128, 256, 128),
            "dq": (128, 256, 256)}

    def test_auto_blocks_committed_pick_table(self):
        """The tile table is folded into the rule (``perf/flash_tiles.json``
        is gone: it was probed at head size 64 and read only under
        ``"auto"``): the constants pass the rule's own VMEM screen at
        every head size a benchmark cell sends, a wide head halves what
        is copied before what is kept, and ``"auto"`` is the default."""
        import os

        from polyaxon_tpu.ops import flash

        assert not os.path.exists(os.path.join(
            os.path.dirname(flash.__file__), "..", "perf",
            "flash_tiles.json"))
        for d in (64, 128, 256):
            for kernel, (bq, bk, sub) in flash.auto_blocks(
                    16384, 16384, d).items():
                resident, copied = (bk, bq) if kernel == "dkdv" else (bq, bk)
                assert flash._tile_bytes(resident, copied, d) \
                    <= flash.VMEM_BUDGET
                assert resident == flash.RESIDENT and sub == flash.SUB
        wide = flash.auto_blocks(16384, 16384, 512)["fwd"]
        assert wide[0] == flash.RESIDENT and wide[1] < flash.COPIED
        # A seq the constants don't tile gets dividing blocks.
        for bq, bk, sub in flash.auto_blocks(1536, 1536, 64).values():
            assert 1536 % bq == 0 and 1536 % bk == 0

    def test_auto_blocks_matches_reference(self):
        q, k, v = _qkv()
        ref = xla_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True,
                              block_q="auto", block_k="auto")
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
        # And through the model-config path a training step compiles:
        # "auto" rides cfg.flash_block_q like an int does.
        out2 = dot_product_attention(q, k, v, impl="flash",
                                     block_q="auto", block_k="auto")
        np.testing.assert_allclose(out2, ref, atol=2e-5, rtol=2e-5)


class TestFlashPallasBackward:
    """Grad parity of the Pallas bwd kernels (the real-TPU default,
    exercised here in interpret mode) against the einsum reference —
    the gate before the kernels run on hardware."""

    @staticmethod
    def _grads(fn, q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                        argnums=(0, 1, 2))(q, k, v)

    def _check(self, flash_kwargs, ref_kwargs, qkv_kwargs=None,
               atol=5e-4, rtol=5e-4):
        q, k, v = _qkv(**(qkv_kwargs or {}))
        gf = self._grads(
            lambda *a: flash_attention(*a, block_q=128, block_k=128,
                                       bwd_impl="pallas", **flash_kwargs),
            q, k, v)
        gr = self._grads(lambda *a: xla_attention(*a, **ref_kwargs), q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=atol, rtol=rtol)

    @pytest.mark.parametrize("causal", [True, False])
    def test_gradients_match(self, causal):
        self._check({"causal": causal}, {"causal": causal})

    def test_gqa_folds_group_onto_kv_head(self):
        self._check({"causal": True}, {"causal": True},
                    qkv_kwargs={"h": 8, "kv": 2})

    def test_multiple_kv_blocks_per_q_block(self):
        # block 128 over seq 512 → 4×4 blocks: exercises accumulation
        # across inner grid steps in both kernels.
        self._check({"causal": True}, {"causal": True},
                    qkv_kwargs={"s": 512})

    def test_sliding_window(self):
        self._check({"causal": True, "window": 64},
                    {"causal": True, "window": 64})

    def test_packed_segments(self):
        seg = jnp.asarray(
            [[0] * 100 + [1] * 156, [0] * 200 + [1] * 56], jnp.int32)
        self._check({"causal": True, "segment_ids": seg},
                    {"causal": True, "segment_ids": seg})

    def test_bf16_matches_fp32_reference(self):
        """bf16 inputs through the Pallas bwd vs the fp32 einsum
        reference: agreement at bf16-resolution tolerances."""
        q, k, v = _qkv(dtype=jnp.bfloat16)
        qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
        gf = self._grads(
            lambda *a: flash_attention(*a, causal=True, block_q=128,
                                       block_k=128, bwd_impl="pallas"),
            q, k, v)
        gr = self._grads(lambda *a: xla_attention(*a, causal=True),
                         qf, kf, vf)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=0.1, rtol=0.1)


class TestFlashSchedule:
    """What the inner loops add (a copied block several sub-blocks long,
    the plain turn beside the masked one, dk/dv's transposed tiles):
    outputs, all three gradients and the lse cotangent against the
    einsum reference, the Pallas backward in interpret mode. A grid
    step keeps 128 rows, copies 512 positions and a turn takes 128, so
    S = 512 walks every run a configuration can have."""

    @pytest.fixture(autouse=True)
    def small_tiles(self, monkeypatch):
        from polyaxon_tpu.ops import flash

        monkeypatch.setattr(flash, "RESIDENT", 128)
        monkeypatch.setattr(flash, "COPIED", 512)
        monkeypatch.setattr(flash, "SUB", 128)

    @staticmethod
    def _loss(fn):
        def loss(q, k, v):
            o, lse = fn(q, k, v)
            # Both outputs carry a cotangent: the lse one enters ds.
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))
        return loss

    def _check(self, qkv=None, seg=None, **kw):
        from polyaxon_tpu.ops.attention import xla_attention_with_lse
        from polyaxon_tpu.ops.flash import flash_attention_with_lse

        q, k, v = _qkv(**{"s": 512, **(qkv or {})})
        kw["segment_ids"] = seg
        flash_fn = lambda *a: flash_attention_with_lse(  # noqa: E731
            *a, bwd_impl="pallas", **kw)
        ref_fn = lambda *a: xla_attention_with_lse(*a, **kw)  # noqa: E731
        # The outputs as a differentiated call computes them.
        for got, want in zip(jax.vjp(flash_fn, q, k, v)[0], ref_fn(q, k, v)):
            np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        gf = jax.grad(self._loss(flash_fn), (0, 1, 2))(q, k, v)
        gr = jax.grad(self._loss(ref_fn), (0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_the_tiles_are_the_small_ones(self):
        from polyaxon_tpu.ops import flash

        assert flash.auto_blocks(512, 512, 64) == {
            "fwd": (128, 512, 128), "dkdv": (512, 128, 128),
            "dq": (128, 512, 128)}

    @pytest.mark.parametrize("case", [
        # Only the last visible sub-block of a row block meets the
        # diagonal; the ones under it take the plain turn.
        dict(causal=True),
        dict(causal=False),
        # A window narrower than a sub-block: no plain turn at all.
        dict(causal=True, window=64),
        # A window whose trailing edge cuts a sub-block: masked turns on
        # both sides of the plain ones.
        dict(causal=True, window=200),
        dict(causal=True, window=256),
    ], ids=["causal", "full", "window64", "window200", "window256"])
    def test_matches_reference(self, case):
        self._check(**case)

    @pytest.mark.parametrize("d", [64, 128, 256])
    def test_head_sizes(self, d):
        self._check(qkv={"d": d, "b": 1}, causal=True)

    @pytest.mark.parametrize("h,kv", [(8, 2), (7, 1)], ids=["4to1", "7to1"])
    def test_gqa_groups(self, h, kv):
        self._check(qkv={"h": h, "kv": kv, "b": 1}, causal=True, window=200)

    @pytest.mark.parametrize("window", [None, 200])
    def test_packed_segments(self, window):
        seg = jnp.asarray(
            [[0] * 100 + [1] * 300 + [2] * 112, [0] * 384 + [1] * 128],
            jnp.int32)
        self._check(seg=seg, causal=True, window=window)

    @pytest.mark.parametrize("sq,sk", [(256, 512), (512, 256)])
    def test_forward_with_unequal_lengths(self, sq, sk):
        """Sq != Sk (a ring step's block against another's keys): the
        einsum reference for the full square, and for the causal one
        the kernel's own alignment, row i over keys 0..i."""
        from polyaxon_tpu.ops.attention import (repeat_kv,
                                                xla_attention_with_lse)
        from polyaxon_tpu.ops.flash import flash_attention_with_lse

        q, _, _ = _qkv(s=sq)
        _, k, v = _qkv(s=sk, seed=1)
        def flash(causal):
            return jax.vjp(functools.partial(
                flash_attention_with_lse, causal=causal), q, k, v)[0]

        for a, b in zip(flash(False),
                        xla_attention_with_lse(q, k, v, causal=False)):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, repeat_kv(k, 2)) * 64 ** -0.5
        seen = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        logits = jnp.where(seen[None, None], logits, -1e30)
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1),
                          repeat_kv(v, 2))
        o, lse = flash(True)
        np.testing.assert_allclose(o, want, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(lse, jax.nn.logsumexp(logits, -1),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("over_cols", [True, False])
    @pytest.mark.parametrize("window", [0, 1, 100, 128, 300, 1000])
    def test_turn_ranges_against_the_dense_mask(self, over_cols, window):
        """``(lo, a, b, hi)`` by brute force: a sub-block is visited iff
        it holds a visible position and takes the plain turn iff it
        holds no masked one, for every block pair of a 1,024 square."""
        from polyaxon_tpu.ops.flash import _turn_ranges

        n, fixed_n, sub, n_sub = 1024, 256, 128, 3
        rows, cols = np.arange(n)[:, None], np.arange(n)[None, :]
        dense = rows >= cols
        if window:
            dense &= rows - cols < window
        for fixed0 in range(0, n, fixed_n):
            for base in range(0, n - sub * n_sub + 1, sub):
                lo, a, b, hi = _turn_ranges(
                    fixed0, fixed_n, base, sub, n_sub, over_cols=over_cols,
                    causal=True, window=window)
                assert 0 <= lo <= a <= b <= hi <= n_sub
                for j in range(n_sub):
                    looped = slice(base + j * sub, base + (j + 1) * sub)
                    fixed = slice(fixed0, fixed0 + fixed_n)
                    tile = (dense[fixed, looped] if over_cols
                            else dense[looped, fixed])
                    assert (lo <= j < hi) == tile.any(), (fixed0, base, j)
                    if tile.all():
                        assert a <= j < b, (fixed0, base, j)
                    elif tile.any():
                        assert not a <= j < b, (fixed0, base, j)


class TestFlashText:
    """What a start of a server pays for the kernels (PERF.md §6, PRs
    37, 49-51): a ``pallas_call`` is traced and lowered at every call
    site of every program, so the kernels' text is held: as long at
    12,288 tokens as at 2,048 (every loop a ``fori_loop`` traced once),
    at most twice what the seed's kernels were (210 / 219 / 533 lines
    of jaxpr at 28 / 4 heads of 128), and shared by a program's sites
    of one shape and window (the entry is jitted)."""

    SEED_LINES = {"plain": 210, "window": 219, "grad": 533}

    @staticmethod
    def _lines(kind, s):
        q = jax.ShapeDtypeStruct((1, s, 28, 128), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, s, 4, 128), jnp.bfloat16)

        def fwd(q, k, v):
            return flash_attention(
                q, k, v, causal=True, interpret=False, bwd_impl="pallas",
                window=4096 if kind == "window" else None)

        fn = fwd
        if kind == "grad":
            fn = jax.grad(lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)),
                          (0, 1, 2))
        return len(str(jax.make_jaxpr(fn)(q, k, k)).splitlines())

    @pytest.mark.parametrize("kind", ["plain", "window", "grad"])
    def test_text_does_not_grow_with_the_sequence(self, kind):
        assert self._lines(kind, 2048) == self._lines(kind, 12288)

    @pytest.mark.parametrize("kind", ["plain", "window", "grad"])
    def test_text_is_at_most_twice_the_seeds(self, kind):
        assert self._lines(kind, 12288) <= 2 * self.SEED_LINES[kind]

    @pytest.mark.parametrize("windows", [(None,) * 8,
                                         (None, 256, 256, 256) * 2],
                             ids=["one-kind", "full-and-window"])
    def test_sites_of_one_shape_share_a_lowering(self, windows):
        """Eight calls inside one jit (a static plan's prefill: a site a
        layer) lower one body a distinct window, not one a site."""
        import re

        def program(q, k, v):
            for window in windows:
                q = q + flash_attention(q, k, v, causal=True, window=window)
            return q

        q = jax.ShapeDtypeStruct((1, 512, 4, 128), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16)
        text = jax.jit(program).lower(q, k, k).as_text()
        bodies = re.findall(r"func\.func private @_flash\w*\(", text)
        assert len(bodies) == len(set(windows))
        assert len(re.findall(r"call @_flash\w*\(", text)) == len(windows)


@pytest.fixture()
def cp_mesh(cpu_devices):
    return Mesh(np.array(cpu_devices).reshape(2, 4), ("dp", "cp"))


class TestRing:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, cp_mesh, causal):
        q, k, v = _qkv(b=4, s=256, h=8, kv=4)
        ref = xla_attention(q, k, v, causal=causal)
        with cp_mesh:
            out = jax.jit(lambda q, k, v: ring_attention(q, k, v, causal=causal))(
                q, k, v
            )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gradients_match(self, cp_mesh):
        q, k, v = _qkv(b=4, s=256, h=8, kv=4)
        gr = jax.grad(lambda q: jnp.sum(xla_attention(q, k, v) ** 2))(q)
        with cp_mesh:
            gg = jax.jit(
                jax.grad(lambda q: jnp.sum(ring_attention(q, k, v) ** 2))
            )(q)
        np.testing.assert_allclose(gg, gr, atol=5e-4, rtol=5e-4)

    def test_requires_axis(self):
        q, k, v = _qkv()
        with pytest.raises(ValueError, match="mesh axis"):
            ring_attention(q, k, v, axis_name="nonexistent")

    def test_odd_local_seq_pads_to_zigzag_and_matches(self, cp_mesh):
        """s_loc = 63 cannot split into zigzag halves; the global entry
        pads the tail by cp rows (causality keeps the pads unattended),
        runs the FAST zigzag path — no warning, no ~2x einsum fallback
        — and still matches the reference exactly. Gradients flow
        through the pad/slice unchanged."""
        import warnings

        from polyaxon_tpu.ops import ring

        q, k, v = _qkv(b=2, s=252, h=4, kv=2)
        ref = xla_attention(q, k, v, causal=True)
        ring._warned_einsum_fallback = False
        with cp_mesh:
            with warnings.catch_warnings():
                # Only the guarded fallback warning fails the test —
                # unrelated Deprecation/FutureWarnings must not.
                warnings.simplefilter("error", RuntimeWarning)
                out = jax.jit(
                    lambda q, k, v: ring_attention(q, k, v))(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

        gr = jax.grad(lambda q: jnp.sum(xla_attention(q, k, v) ** 2))(q)
        with cp_mesh:
            gg = jax.jit(
                jax.grad(lambda q: jnp.sum(ring_attention(q, k, v) ** 2))
            )(q)
        np.testing.assert_allclose(gg, gr, atol=5e-4, rtol=5e-4)

    def test_odd_local_seq_inside_shard_map_still_warns(self, cp_mesh):
        """Direct in-shard_map callers can't be re-padded from outside:
        the loud masked-einsum fallback remains (no silent slow mode)."""
        import functools

        from jax.sharding import PartitionSpec as P

        from polyaxon_tpu.ops import ring

        q, k, v = _qkv(b=2, s=252, h=4, kv=2)
        ref = xla_attention(q, k, v, causal=True)
        ring._warned_einsum_fallback = False
        spec = P(None, "cp", None, None)
        fn = jax.shard_map(
            functools.partial(ring._ring_attention_sharded, causal=True,
                              scale=q.shape[-1] ** -0.5, axis_name="cp"),
            mesh=cp_mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        with pytest.warns(RuntimeWarning, match="masked-einsum ring"):
            out = jax.jit(fn)(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.perf
    def test_zigzag_halves_causal_work(self, cpu_devices):
        """The v2 zigzag layout skips fully-post-diagonal blocks, so
        causal CP must be decisively faster than the masked contiguous
        fallback (theoretical attention-FLOP ratio 9/16; generous 0.8
        margin for CPU timing noise). Compiled-HLO cost_analysis can't
        assert this — it counts a lax.scan body once regardless of trip
        count — so this is the step-time check VERDICT r1 item 4 asks
        for. Retried: background load on a shared 1-core host can
        squeeze the margin on any single sample set."""
        import functools
        import time

        from polyaxon_tpu.ops import ring

        mesh = Mesh(np.array(cpu_devices[:4]).reshape(4), ("cp",))
        q, k, v = _qkv(b=1, s=4096, h=4, kv=2)
        spec = jax.sharding.PartitionSpec(None, "cp", None, None)

        def build(fn):
            f = jax.shard_map(
                functools.partial(fn, scale=64 ** -0.5, axis_name="cp"),
                mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                check_vma=False)
            return jax.jit(f)

        f2 = build(ring._ring_causal_zigzag)
        f1 = build(lambda q, k, v, scale, axis_name:
                   ring._ring_einsum_causal(q, k, v, scale=scale,
                                            axis_name=axis_name))
        np.testing.assert_allclose(np.asarray(f1(q, k, v)),
                                   np.asarray(f2(q, k, v)),
                                   atol=2e-5, rtol=2e-5)

        # Interleave samples so background-load drift hits both
        # variants equally; compare best-of-5. Measured ratio is ~0.27
        # on an idle host vs the 0.8 assertion bound. Up to 3 attempts:
        # a load spike that distorts one sample set shouldn't fail CI.
        jax.block_until_ready(f2(q, k, v))
        jax.block_until_ready(f1(q, k, v))
        for attempt in range(3):
            t2s, t1s = [], []
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(f2(q, k, v))
                t2s.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                jax.block_until_ready(f1(q, k, v))
                t1s.append(time.perf_counter() - t0)
            t2, t1 = min(t2s), min(t1s)
            if t2 < 0.8 * t1:
                return
        assert t2 < 0.8 * t1, (
            f"zigzag {t2 * 1e3:.0f}ms not clearly faster than "
            f"masked {t1 * 1e3:.0f}ms (3 attempts)")


class TestUlysses:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, cp_mesh, causal):
        q, k, v = _qkv(b=4, s=256, h=8, kv=4)
        ref = xla_attention(q, k, v, causal=causal)
        with cp_mesh:
            out = jax.jit(
                lambda q, k, v: ulysses_attention(q, k, v, causal=causal)
            )(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gqa_repeats_to_axis(self, cp_mesh):
        # 2 kv heads < 4-way cp axis: kv heads are repeated to fit.
        q, k, v = _qkv(b=4, s=256, h=8, kv=2)
        ref = xla_attention(q, k, v, causal=True)
        with cp_mesh:
            out = jax.jit(lambda q, k, v: ulysses_attention(q, k, v))(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gradients_match(self, cp_mesh):
        q, k, v = _qkv(b=4, s=256, h=8, kv=4)
        gr = jax.grad(lambda q: jnp.sum(xla_attention(q, k, v) ** 2))(q)
        with cp_mesh:
            gg = jax.jit(
                jax.grad(lambda q: jnp.sum(ulysses_attention(q, k, v) ** 2))
            )(q)
        np.testing.assert_allclose(gg, gr, atol=5e-4, rtol=5e-4)


class TestModelIntegration:
    def test_llama_ring_attention_forward(self, cp_mesh):
        """Llama forward with impl=ring under a dp×cp mesh matches xla."""
        from polyaxon_tpu.models import llama

        cfg_x = llama.CONFIGS["llama_tiny"]
        import dataclasses

        cfg_x = dataclasses.replace(cfg_x, max_seq_len=256, dtype=jnp.float32)
        cfg_r = dataclasses.replace(cfg_x, attention_impl="ring")
        variables = llama.init(cfg_x, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (4, 256), 0, cfg_x.vocab_size)
        ref = llama.forward(cfg_x, variables["params"], tokens)
        with cp_mesh:
            out = jax.jit(
                lambda p, t: llama.forward(cfg_r, p, t)
            )(variables["params"], tokens)
        np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)

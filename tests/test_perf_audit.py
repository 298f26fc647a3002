"""Communication-audit subsystem (polyaxon_tpu/perf).

Fast tiers: HLO parsing against hand-written instruction lines,
wire-byte formulas vs hand-computed shapes (including a compiled
single-collective program on the 8-device mesh), overlap-window
measurement against hand-computed FLOP/byte ratios in all three async
encodings, overlap-budget-gate logic, the double-buffered pipeline
parity drill, and AOT-probe timeout containment.

``slow``-marked: the full train-step audits per schedule (golden
collective counts == the committed budgets, the reshard-injection
drill) — each compiles the real train step on the 8-device mesh, so
they run in the ci.sh audit stage rather than tier-1.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from polyaxon_tpu.perf import audit, budgets
from polyaxon_tpu.perf.hlo import (
    ICI_BYTES_PER_S,
    PEAK_FLOPS_PER_S,
    parse_collectives,
    summarize_collectives,
    summarize_overlap,
)


class TestHloParse:
    def test_pallas_kernels_named_through_every_transform(self):
        """`pallas_kernels` is what tells a program that runs a Mosaic
        kernel from one that took a reference path: it reads the
        pallas_call's `name=` out of the custom call's op_name whatever
        transform wrapped it, and ignores custom calls that are not
        Mosaic's."""
        from polyaxon_tpu.perf.hlo import pallas_kernels

        hlo = """
  %flash_fwd.1 = (bf16[2,32,2048,64]{3,2,1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", backend_config={"x":{}}, metadata={op_name="jit(step)/shard_map/flash_fwd/pallas_call" stack_frame_id=2}
  %jvp.2 = (bf16[2,32,2048,64]{3,2,1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(flash_fwd)/pallas_call" stack_frame_id=3}
  %t.3 = bf16[2,8,2048,64]{3,2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(flash_bwd_dkdv))/pallas_call"}
  %p.4 = bf16[4,32,64]{2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/paged_decode/pallas_call"}
  %s.5 = f32[8]{0} custom-call(%z), custom_call_target="Sharding", metadata={op_name="jit(step)/not_a_kernel/pallas_call"}
  %d.6 = f32[8,8]{1,0} dot(%x, %y), metadata={op_name="jit(step)/dot_general"}
"""
        assert pallas_kernels(hlo) == {
            "flash_fwd": 2, "flash_bwd_dkdv": 1, "paged_decode": 1}
        assert pallas_kernels("") == {}

    def test_time_model_refuses_a_tpu_it_does_not_describe(self):
        """The overlap model's constants are v5e's: CPU-mesh HLO is
        ranked with them by design, another TPU kind is an error, and a
        TPU kind nobody recorded a peak for is an error everywhere MFU
        is computed — never a default."""
        import types

        from polyaxon_tpu.perf.hlo import require_modelled_device
        from polyaxon_tpu.runtime.flops import peak_flops

        def device(platform, kind):
            return types.SimpleNamespace(platform=platform, device_kind=kind)

        assert peak_flops(device("cpu", "cpu")) is None
        assert peak_flops(device("tpu", "TPU v5 lite")) == 197e12
        with pytest.raises(ValueError, match="no bf16 peak recorded"):
            peak_flops(device("tpu", "TPU v9 hypothetical"))
        require_modelled_device(device("cpu", "cpu"))
        require_modelled_device(device("tpu", "TPU v5 lite"))
        with pytest.raises(ValueError, match="v5e constants"):
            require_modelled_device(device("tpu", "TPU v4"))

    def test_counts_shapes_and_groups(self):
        hlo = """
  %all-reduce.1 = f32[256,64]{1,0} all-reduce(f32[256,64]{1,0} %add.5), channel_id=1, replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%sum
  %ag = bf16[8,128]{1,0} all-gather(bf16[1,128]{1,0} %p0), channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %a2a = f32[2,512,1,16]{3,2,1,0} all-to-all(f32[2,512,1,16]{3,2,1,0} %x), channel_id=3, replica_groups=[2,4]<=[8], dimensions={1}
  %cp = f32[2,64]{1,0} collective-permute(f32[2,64]{1,0} %y), channel_id=4, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
"""
        ops = parse_collectives(hlo, n_devices=8)
        assert [o.kind for o in ops] == [
            "all-reduce", "all-gather", "all-to-all", "collective-permute"]
        ar, ag, a2a, cp = ops
        # explicit replica_groups: first group has 4 members
        assert ar.group_size == 4
        assert ar.result_bytes == 256 * 64 * 4
        # iota-format groups [2,4]<=[8]: 2 groups of 4
        assert a2a.group_size == 4
        # bf16 = 2 bytes
        assert ag.result_bytes == 8 * 128 * 2

    def test_async_start_done_counted_once(self):
        hlo = """
  %ar0 = f32[64]{0} all-reduce-start(f32[64]{0} %x), replica_groups={{0,1}}, to_apply=%sum
  %ar1 = f32[64]{0} all-reduce-done(f32[64]{0} %ar0)
"""
        ops = parse_collectives(hlo, n_devices=2)
        assert len(ops) == 1
        assert ops[0].kind == "all-reduce"

    def test_tuple_result_shapes_sum(self):
        hlo = ("  %ar = (f32[16]{0}, bf16[8]{0}) all-reduce"
               "(f32[16]{0} %a, bf16[8]{0} %b), replica_groups={{0,1}}, "
               "to_apply=%sum\n")
        (op,) = parse_collectives(hlo, n_devices=2)
        assert op.result_bytes == 16 * 4 + 8 * 2

    def test_wire_byte_formulas_hand_computed(self):
        b = 1024  # one f32[256] tensor
        hlo = (
            "  %ar = f32[256]{0} all-reduce(f32[256]{0} %x), "
            "replica_groups={{0,1,2,3}}, to_apply=%s\n"
            "  %ag = f32[256]{0} all-gather(f32[64]{0} %x), "
            "replica_groups={{0,1,2,3}}, dimensions={0}\n"
            "  %rs = f32[256]{0} reduce-scatter(f32[1024]{0} %x), "
            "replica_groups={{0,1,2,3}}, to_apply=%s, dimensions={0}\n"
            "  %aa = f32[256]{0} all-to-all(f32[256]{0} %x), "
            "replica_groups={{0,1,2,3}}, dimensions={0}\n"
            "  %cp = f32[256]{0} collective-permute(f32[256]{0} %x), "
            "source_target_pairs={{0,1},{1,0}}\n")
        ops = {o.kind: o for o in parse_collectives(hlo, n_devices=4)}
        assert ops["all-reduce"].wire_bytes == pytest.approx(2 * b * 3 / 4)
        assert ops["all-gather"].wire_bytes == pytest.approx(b * 3 / 4)
        # reduce-scatter: result is the 1/g shard; receives (g-1) shards
        assert ops["reduce-scatter"].wire_bytes == pytest.approx(b * 3)
        assert ops["all-to-all"].wire_bytes == pytest.approx(b * 3 / 4)
        assert ops["collective-permute"].wire_bytes == pytest.approx(b)

    def test_summary_aggregates(self):
        hlo = (
            "  %a = f32[64]{0} all-reduce(f32[64]{0} %x), "
            "replica_groups={{0,1}}, to_apply=%s\n"
            "  %b = f32[64]{0} all-reduce(f32[64]{0} %y), "
            "replica_groups={{0,1}}, to_apply=%s\n")
        summary = summarize_collectives(parse_collectives(hlo, n_devices=2))
        assert summary["counts"] == {"all-reduce": 2}
        assert summary["n_collectives"] == 2
        assert summary["est_wire_bytes_per_step"] == 2 * int(2 * 256 * 0.5)


def _hidden_ratio(flops: float, wire_bytes: float) -> float:
    """The module's documented time model, restated independently:
    hidden fraction = min(coll_time, window_compute) / coll_time."""
    coll_s = wire_bytes / ICI_BYTES_PER_S
    return min(coll_s, flops / PEAK_FLOPS_PER_S) / coll_s


class TestOverlapParse:
    """Overlap-window measurement against hand-written HLO in all three
    async encodings, with hand-computed FLOP counts and wire bytes fed
    through the documented time model."""

    def test_start_done_window_and_ratio(self):
        # Classic pair: the dot between -start and -done is the window.
        hlo = """
  %ar0 = (f32[1024]{0}, f32[1024]{0}) all-reduce-start(f32[1024]{0} %x), replica_groups={{0,1,2,3}}, to_apply=%sum
  %mm = f32[128,128]{1,0} dot(f32[128,64]{1,0} %a, f32[64,128]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar1 = f32[1024]{0} all-reduce-done((f32[1024]{0}, f32[1024]{0}) %ar0)
"""
        (op,) = parse_collectives(hlo, n_devices=4)
        assert op.is_async and op.kind == "all-reduce"
        assert op.window_ops == 1
        # dot: 2 * result(128*128) * K(lhs contracting dim = 64)
        assert op.window_flops == 2 * 128 * 128 * 64
        wire = 2 * 1024 * 4 * 3 / 4  # ring all-reduce, g=4
        assert op.wire_bytes == pytest.approx(wire)
        assert op.overlap_ratio == pytest.approx(
            _hidden_ratio(op.window_flops, wire), rel=1e-3)

    def test_sync_collective_has_zero_overlap(self):
        hlo = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={{0,1,2,3}}, to_apply=%sum
  %mm = f32[128,128]{1,0} dot(f32[128,64]{1,0} %a, f32[64,128]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
"""
        (op,) = parse_collectives(hlo, n_devices=4)
        assert not op.is_async
        assert op.window_ops == 0 and op.overlap_ratio == 0.0

    def test_annotated_sync_form_window_to_first_consumer(self):
        # Encoding 2: sync-form op with async_collective_name frontend
        # attribute — in flight until its first consumer, so only %e
        # (not %r, the consumer) is window compute.
        hlo = """
  %ag = bf16[8,128]{1,0} all-gather(bf16[1,128]{1,0} %p0), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, frontend_attributes={async_collective_name="ag.1"}
  %e = f32[4096]{0} exponential(f32[4096]{0} %z)
  %r = bf16[8,128]{1,0} negate(bf16[8,128]{1,0} %ag)
"""
        (op,) = parse_collectives(hlo, n_devices=8)
        assert op.is_async and op.window_ops == 1
        assert op.window_flops == 4096  # elementwise = result elements
        wire = (8 * 128 * 2) * 7 / 8
        assert op.overlap_ratio == pytest.approx(
            _hidden_ratio(4096, wire), abs=1e-6)

    def test_continuation_fusion_pairing_and_census_dedup(self):
        # Encoding 3 (scheduled TPU modules): the transfer lives in a
        # start fusion, retires at the NAME-SUFFIX-matched done fusion,
        # and repeats inside an async_collective_fusion* computation —
        # censused exactly once, window = the %mm fusion between the
        # start/done pair.
        hlo = """
HloModule m, is_scheduled=true

%fc.start (p: f32[256]) -> (f32[1024]) {
  %p = f32[256]{0} parameter(0)
  ROOT %ag.inner = f32[1024]{0} all-gather(f32[256]{0} %p), replica_groups={{0,1,2,3}}, dimensions={0}
}

%fc.done (t: (f32[1024])) -> f32[1024] {
  %t = (f32[1024]{0}) parameter(0)
  ROOT %gte = f32[1024]{0} get-tuple-element((f32[1024]{0}) %t), index=0
}

%fc.mm (a: f32[64,64], b: f32[64,64]) -> f32[64,64] {
  %a = f32[64,64]{1,0} parameter(0)
  %b = f32[64,64]{1,0} parameter(1)
  ROOT %d = f32[64,64]{1,0} dot(f32[64,64]{1,0} %a, f32[64,64]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

%async_collective_fusion.1 (p2: f32[256]) -> f32[1024] {
  %p2 = f32[256]{0} parameter(0)
  ROOT %ag.repeat = f32[1024]{0} all-gather(f32[256]{0} %p2), replica_groups={{0,1,2,3}}, dimensions={0}
}

ENTRY %main (x: f32[256], a: f32[64,64], b: f32[64,64]) -> f32[1024] {
  %x = f32[256]{0} parameter(0)
  %a0 = f32[64,64]{1,0} parameter(1)
  %b0 = f32[64,64]{1,0} parameter(2)
  %async-collective-start.1 = (f32[1024]{0}) fusion(f32[256]{0} %x), kind=kLoop, calls=%fc.start
  %mm = f32[64,64]{1,0} fusion(f32[64,64]{1,0} %a0, f32[64,64]{1,0} %b0), kind=kOutput, calls=%fc.mm
  %async-collective-done.1 = f32[1024]{0} fusion((f32[1024]{0}) %async-collective-start.1), kind=kLoop, calls=%fc.done
  %cont = f32[1024]{0} fusion(f32[256]{0} %x), kind=kLoop, calls=%async_collective_fusion.1
  ROOT %out = f32[1024]{0} add(f32[1024]{0} %async-collective-done.1, f32[1024]{0} %cont)
}
"""
        (op,) = parse_collectives(hlo, n_devices=4)
        assert op.kind == "all-gather" and op.is_async
        assert op.window_ops == 1  # exactly the %mm fusion
        assert op.window_flops == 2 * 64 * 64 * 64  # fc.mm's dot
        wire = 1024 * 4 * 3 / 4
        assert op.wire_bytes == pytest.approx(wire)
        assert op.overlap_ratio == pytest.approx(
            _hidden_ratio(op.window_flops, wire), rel=1e-3)

    def test_fused_collective_overlaps_its_own_fusion(self):
        # A plain fusion whose callee issues a collective: the window
        # is the fusion itself, so its own compute hides the transfer.
        hlo = """
%fused (p: f32[1024], a: f32[64,64], b: f32[64,64]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  %a = f32[64,64]{1,0} parameter(1)
  %b = f32[64,64]{1,0} parameter(2)
  %d = f32[64,64]{1,0} dot(f32[64,64]{1,0} %a, f32[64,64]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %ar = f32[1024]{0} all-reduce(f32[1024]{0} %p), replica_groups={{0,1,2,3}}, to_apply=%sum
}

ENTRY %main (x: f32[1024], a: f32[64,64], b: f32[64,64]) -> f32[1024] {
  %x = f32[1024]{0} parameter(0)
  %a0 = f32[64,64]{1,0} parameter(1)
  %b0 = f32[64,64]{1,0} parameter(2)
  ROOT %f = f32[1024]{0} fusion(f32[1024]{0} %x, f32[64,64]{1,0} %a0, f32[64,64]{1,0} %b0), kind=kLoop, calls=%fused
}
"""
        (op,) = parse_collectives(hlo, n_devices=4)
        assert op.is_async and op.kind == "all-reduce"
        # Window = [the fusion]; its flops recurse into the callee
        # (the dot; the inner all-reduce itself counts zero).
        assert op.window_flops == 2 * 64 * 64 * 64

    def test_ratio_clamps_at_one(self):
        # A tiny transfer under a huge dot: hidden time is capped at
        # the collective time itself.
        hlo = """
  %ar0 = (f32[16]{0}, f32[16]{0}) all-reduce-start(f32[16]{0} %x), replica_groups={{0,1}}, to_apply=%sum
  %mm = f32[1024,1024]{1,0} dot(f32[1024,1024]{1,0} %a, f32[1024,1024]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar1 = f32[16]{0} all-reduce-done((f32[16]{0}, f32[16]{0}) %ar0)
"""
        (op,) = parse_collectives(hlo, n_devices=2)
        assert op.overlap_ratio == 1.0

    def test_convolution_flop_model(self):
        # Scheduled TPU modules lower matmuls to convolution; K is the
        # product of rhs dims whose dim_labels char is not 'o'.
        hlo = """
  %cp0 = (f32[65536]{0}, f32[65536]{0}) collective-permute-start(f32[65536]{0} %x), source_target_pairs={{0,1},{1,0}}
  %conv = f32[8,128,64]{2,1,0} convolution(f32[8,128,32]{2,1,0} %lhs, f32[1,64,32]{2,1,0} %rhs), window={size=1}, dim_labels=b0f_0oi->b0f
  %cp1 = f32[65536]{0} collective-permute-done((f32[65536]{0}, f32[65536]{0}) %cp0)
"""
        (op,) = parse_collectives(hlo, n_devices=2)
        assert op.kind == "collective-permute" and op.is_async
        # rhs [1, 64, 32] labeled "0oi": K = 1 * 32 (o=64 excluded);
        # result has 8*128*64 elements.
        assert op.window_flops == 2 * (8 * 128 * 64) * 32
        wire = 65536 * 4  # permute: one hop of the payload
        assert op.overlap_ratio == pytest.approx(
            _hidden_ratio(op.window_flops, wire), rel=1e-3)

    def test_summarize_overlap_mixes_async_and_sync(self):
        hlo = """
  %ar0 = (f32[1024]{0}, f32[1024]{0}) all-reduce-start(f32[1024]{0} %x), replica_groups={{0,1,2,3}}, to_apply=%sum
  %mm = f32[128,128]{1,0} dot(f32[128,64]{1,0} %a, f32[64,128]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar1 = f32[1024]{0} all-reduce-done((f32[1024]{0}, f32[1024]{0}) %ar0)
  %sync = f32[1024]{0} all-reduce(f32[1024]{0} %y), replica_groups={{0,1,2,3}}, to_apply=%sum
"""
        summary = summarize_overlap(parse_collectives(hlo, n_devices=4))
        assert summary["n_async_collectives"] == 1
        assert summary["n_sync_collectives"] == 1
        assert summary["async_by_kind"] == {"all-reduce": 1}
        # Schedule ratio = hidden seconds over TOTAL collective seconds:
        # the sync op doubles the denominator and hides nothing.
        wire = 2 * 1024 * 4 * 3 / 4
        flops = 2 * 128 * 128 * 64
        expected = (min(wire / ICI_BYTES_PER_S, flops / PEAK_FLOPS_PER_S)
                    / (2 * wire / ICI_BYTES_PER_S))
        assert summary["overlap_ratio"] == pytest.approx(expected, abs=1e-4)

    def test_no_wire_traffic_is_ratio_one(self):
        # Nothing to hide: by convention the gate never fails a
        # communication-free schedule.
        assert summarize_overlap([])["overlap_ratio"] == 1.0
        hlo = ("  %ar = f32[64]{0} all-reduce(f32[64]{0} %x), "
               "replica_groups={{0}}, to_apply=%s\n")
        assert summarize_overlap(
            parse_collectives(hlo, n_devices=1))["overlap_ratio"] == 1.0


class TestCompiledBytesSanity:
    """The estimator against a REAL compiled program whose traffic is
    hand-computable: psum of a known tensor over the 8-device mesh."""

    def test_psum_all_reduce_bytes(self, cpu_devices):
        mesh = Mesh(np.array(cpu_devices).reshape(8), ("dp",))
        n = 1024
        x = jax.device_put(
            jnp.arange(8 * n, dtype=jnp.float32).reshape(8, n),
            NamedSharding(mesh, P("dp")))

        @jax.jit
        def f(x):
            return jax.lax.with_sharding_constraint(
                x.sum(axis=0, keepdims=True) + 0.0,
                NamedSharding(mesh, P()))

        compiled = f.lower(x).compile()
        ops = parse_collectives(compiled.as_text(), n_devices=8)
        reduces = [o for o in ops
                   if o.kind in ("all-reduce", "reduce-scatter")]
        assert reduces, "expected a cross-device reduction in the HLO"
        # The reduced payload is the f32[1, n] row = 4n bytes; the ring
        # estimate for an 8-way all-reduce of it is 2 * 4n * 7/8.
        payload = 4 * n
        assert any(o.result_bytes == payload for o in reduces)
        ar = next(o for o in reduces if o.result_bytes == payload)
        assert ar.group_size == 8
        assert ar.wire_bytes == pytest.approx(2 * payload * 7 / 8)


class TestBudgetGate:
    def _report(self, **over):
        rep = {
            "name": "dp", "model": "llama_tiny", "axes": {"dp": 8},
            "attention": "xla", "seq_len": 256, "global_batch": 8,
            "counts": {"all-reduce": 15},
            "est_wire_bytes_per_step": 500_000,
        }
        rep.update(over)
        return rep

    def _budgets(self):
        return {
            "_meta": {"bytes_tolerance": 0.25},
            "dp": {
                "counts": {"all-reduce": 15},
                "est_wire_bytes_per_step": 500_000,
                "axes": {"dp": 8}, "model": "llama_tiny",
                "attention": "xla", "seq_len": 256, "global_batch": 8,
            },
        }

    def test_within_budget_passes(self):
        assert budgets.check_report(self._report(), self._budgets()) == []

    def test_extra_op_kind_fails(self):
        rep = self._report(counts={"all-reduce": 15, "all-gather": 1})
        violations = budgets.check_report(rep, self._budgets())
        assert violations and "all-gather" in violations[0]

    def test_count_regression_fails(self):
        rep = self._report(counts={"all-reduce": 16})
        assert budgets.check_report(rep, self._budgets())

    def test_bytes_regression_fails_past_tolerance(self):
        ok = self._report(est_wire_bytes_per_step=600_000)  # +20% < 25%
        assert budgets.check_report(ok, self._budgets()) == []
        bad = self._report(est_wire_bytes_per_step=700_000)  # +40%
        assert budgets.check_report(bad, self._budgets())

    def test_missing_entry_is_a_violation(self):
        rep = self._report(name="brand-new-schedule")
        violations = budgets.check_report(rep, self._budgets())
        assert violations and "no budget entry" in violations[0]

    def test_config_drift_demands_regeneration(self):
        rep = self._report(seq_len=512)
        violations = budgets.check_report(rep, self._budgets())
        assert violations and "regenerate" in violations[0]

    def test_committed_budget_file_loads_and_covers_standard_points(self):
        table = budgets.load_budgets()
        for point in audit.STANDARD_POINTS:
            assert point.name in table, (
                f"budgets.json is missing {point.name}; run "
                f"python -m polyaxon_tpu.perf --update-budgets")
            assert table[point.name]["counts"], point.name


class TestOverlapBudgetGate:
    def _floors(self):
        return {"_overlap": {"topology": "v5e:2x4", "floor_margin": 0.8,
                             "min_overlap_ratio": {"dp": 0.0,
                                                   "fsdp": 0.0355}}}

    def _rep(self, name, ratio):
        return {"name": name, "overlap_ratio": ratio}

    def test_above_floor_passes(self):
        reps = [self._rep("dp", 0.0), self._rep("fsdp", 0.05)]
        assert budgets.check_overlap(reps, budgets=self._floors()) == []

    def test_below_floor_fails(self):
        reps = [self._rep("dp", 0.0), self._rep("fsdp", 0.0)]
        violations = budgets.check_overlap(reps, budgets=self._floors())
        assert violations and "below floor" in violations[0]
        assert "fsdp" in violations[0]

    def test_missing_section_is_a_violation(self):
        violations = budgets.check_overlap(
            [self._rep("fsdp", 0.9)], budgets={"_meta": {}})
        assert violations and "_overlap" in violations[0]

    def test_floored_schedule_without_report_is_a_violation(self):
        violations = budgets.check_overlap(
            [self._rep("fsdp", 0.05)], budgets=self._floors())
        assert any("no report" in v for v in violations)

    def test_only_subset_suppresses_coverage_noise(self):
        # --schedules fsdp must not read as dp having vanished.
        assert budgets.check_overlap(
            [self._rep("fsdp", 0.05)], budgets=self._floors(),
            only=["fsdp"]) == []

    def test_unfloored_report_is_a_violation(self):
        reps = [self._rep("dp", 0.0), self._rep("fsdp", 0.05),
                self._rep("brand-new", 0.9)]
        violations = budgets.check_overlap(reps, budgets=self._floors())
        assert any("no overlap floor" in v for v in violations)

    def test_committed_floors_cover_standard_points(self):
        section = budgets.load_budgets().get("_overlap")
        assert section, ("budgets.json has no _overlap section; run "
                         "python -m polyaxon_tpu.perf --audit "
                         "--update-budgets")
        floors = section["min_overlap_ratio"]
        for point in audit.STANDARD_POINTS:
            assert point.name in floors, point.name
        # The floors carry their provenance and margin.
        assert section["topology"]
        assert 0 < section["floor_margin"] <= 1

    def test_cpu_census_regeneration_preserves_floors(self, tmp_path):
        # write_budgets (the CPU census path) must carry the _overlap
        # section over — the floors are AOT TPU evidence living in the
        # same file.
        path = str(tmp_path / "budgets.json")
        budgets.write_overlap_floors(
            [self._rep("fsdp", 0.05)], "v5e:2x4", path=path)
        budgets.write_budgets(
            [{"name": "dp", "counts": {}, "est_wire_bytes_per_step": 0,
              "axes": {}, "model": "m", "attention": "xla",
              "seq_len": 1, "global_batch": 1}], path=path)
        data = budgets.load_budgets(path)
        assert data["_overlap"]["min_overlap_ratio"] == {"fsdp": 0.04}
        assert "dp" in data


class TestPipelineDoubleBuffer:
    """ISSUE 12: the (arrived, to_send) double-buffered GPipe schedule
    shifts ticks, not values — per-microbatch outputs (and grads) are
    identical to the single-buffered schedule and the unpipelined
    reference. The TPU-side evidence that the decoupled ppermute
    actually hides under stage compute is the slow TestOverlapAot
    drill; THIS is the loss-parity half of the acceptance bar."""

    def _setup(self, cpu_devices):
        from polyaxon_tpu.parallel.pipeline import stack_stages

        mesh = Mesh(np.array(cpu_devices).reshape(8), ("pp",))
        L, d, batch = 8, 16, 16
        w = jax.random.normal(jax.random.key(0), (L, d, d),
                              jnp.float32) / np.sqrt(d)
        x = jax.random.normal(jax.random.key(1), (batch, d), jnp.float32)
        return mesh, stack_stages({"w": w}, 8), w, x

    @staticmethod
    def _stage_fn(local, h):
        out, _ = jax.lax.scan(
            lambda h, w: (jnp.tanh(h @ w), None), h, local["w"])
        return out

    def test_output_and_loss_parity(self, cpu_devices):
        from polyaxon_tpu.parallel.pipeline import pipeline_forward

        mesh, stacked, w, x = self._setup(cpu_devices)

        def run(db):
            return pipeline_forward(mesh, self._stage_fn, stacked, x,
                                    n_microbatches=4, double_buffer=db)

        single, double = run(False), run(True)
        ref = x
        for i in range(w.shape[0]):
            ref = jnp.tanh(ref @ w[i])
        np.testing.assert_allclose(np.asarray(double), np.asarray(single),
                                   atol=1e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(double), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        loss_s = float(jnp.mean(single ** 2))
        loss_d = float(jnp.mean(double ** 2))
        assert abs(loss_s - loss_d) <= 1e-5

    def test_gradients_match(self, cpu_devices):
        # The schedule is differentiable either way (scan + ppermute);
        # the backward pipeline must agree too.
        from polyaxon_tpu.parallel.pipeline import pipeline_forward

        mesh, stacked, _, x = self._setup(cpu_devices)

        def loss(db):
            return lambda p: jnp.mean(pipeline_forward(
                mesh, self._stage_fn, p, x,
                n_microbatches=4, double_buffer=db) ** 2)

        g_single = jax.grad(loss(False))(stacked)
        g_double = jax.grad(loss(True))(stacked)
        for a, b in zip(jax.tree.leaves(g_single),
                        jax.tree.leaves(g_double)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=1e-5, rtol=1e-5)

    def test_double_buffer_schedule_emits_permutes(self, cpu_devices):
        # Structural check on the compiled schedule: the stage hops are
        # real collective-permutes (sync on XLA:CPU; the TPU overlap
        # measurement is the slow AOT drill).
        from polyaxon_tpu.parallel.pipeline import pipeline_forward

        mesh, stacked, _, x = self._setup(cpu_devices)
        compiled = jax.jit(
            lambda p, t: pipeline_forward(mesh, self._stage_fn, p, t,
                                          n_microbatches=4,
                                          double_buffer=True)
        ).lower(stacked, x).compile()
        counts = summarize_collectives(parse_collectives(
            compiled.as_text(), n_devices=8))["counts"]
        assert counts.get("collective-permute", 0) >= 1, counts


@pytest.mark.slow
class TestOverlapAot:
    """AOT TPU overlap evidence (each test pays a strictly-timeouted
    topology-compile subprocess, so they live in the ci.sh audit stage
    / --full tier). Hosts whose toolchain cannot compile for any TPU
    topology SKIP — that is the CLI's exit-3 posture, infra rather
    than regression."""

    def test_fsdp_meets_floor_and_serialize_flips_the_gate(self):
        from polyaxon_tpu.perf import aot

        pinned = aot.run_overlap_audit(points=["fsdp"])
        if not pinned.get("ok"):
            pytest.skip(f"no workable TPU topology: {pinned}")
        (rep,) = pinned["reports"]
        floors = budgets.load_budgets()["_overlap"]["min_overlap_ratio"]
        assert rep["overlap_ratio"] >= floors["fsdp"]
        assert budgets.check_overlap(
            pinned["reports"], only=["fsdp"]) == []

        serial = aot.run_overlap_audit(points=["fsdp"], serialize=True)
        if not serial.get("ok"):
            pytest.skip(f"serialized compile unavailable: {serial}")
        (srep,) = serial["reports"]
        assert srep["overlap_ratio"] < rep["overlap_ratio"]
        violations = budgets.check_overlap(
            serial["reports"], only=["fsdp"])
        assert any("below floor" in v for v in violations), violations

    def test_double_buffered_pipeline_permutes_overlap(self):
        from polyaxon_tpu.perf import aot

        result = aot.run_pipeline_drill()
        if not result.get("ok"):
            pytest.skip(f"no workable TPU topology: {result}")
        drill = result["pipeline_drill"]
        double, single = drill.get("double", {}), drill.get("single", {})
        assert "error" not in double and "error" not in single, drill
        assert double["n_permutes"] >= 1
        # The decoupled hop measurably hides under stage compute; the
        # single-buffered control (out -> ppermute data dependency
        # within the tick) does not.
        assert double["permute_max_overlap"] > 0.0
        assert (double["overlap"]["overlap_ratio"]
                > single["overlap"]["overlap_ratio"])


class TestAotProbeContainment:
    def test_timeout_is_contained_and_structured(self):
        from polyaxon_tpu.perf import aot

        import time as _time

        t0 = _time.time()
        result = aot.run_probe(timeout_s=2.0,
                               extra_child_args=["--sleep", "60"])
        wall = _time.time() - t0
        assert result["timed_out"] is True
        assert result["ok"] is False
        assert "timeout" in result["error"]
        # SIGTERM grace is 60s on top of the timeout; a contained probe
        # must come back well before a CI-stage budget would notice.
        assert wall < 70

    def test_probe_returns_dict_never_raises(self):
        from polyaxon_tpu.perf import aot

        result = aot.run_probe(timeout_s=1.0,
                               extra_child_args=["--sleep", "30"])
        assert isinstance(result, dict) and result.get("ok") is False


@pytest.mark.slow
class TestAuditGolden:
    """Golden collective counts per schedule: a fresh compile of the
    real train step must reproduce the committed budgets exactly.
    Each case compiles on the 8-device mesh (seconds-to-minutes on this
    host), so the module's slow tier runs in the ci.sh audit stage."""

    @pytest.fixture(scope="class")
    def budget_table(self):
        return budgets.load_budgets()

    @pytest.mark.parametrize("name", [p.name for p in audit.STANDARD_POINTS])
    def test_golden_counts_match_budgets(self, name, budget_table,
                                         cpu_devices):
        report = audit.audit_point(audit.point_by_name(name),
                                   devices=cpu_devices)
        assert report["counts"] == budget_table[name]["counts"]
        assert budgets.check_report(report, budget_table) == []

    def test_cp_schedules_keep_batch_sharded(self, cpu_devices):
        """The r6 reshard fix, locked in: neither manual attention
        schedule may all-gather Q/K/V over the batch axes (the
        pre-fix full-manual specs cost 4 all-gathers + dp-redundant
        attention compute per step)."""
        for name in ("ring-cp", "ulysses-cp"):
            report = audit.audit_point(audit.point_by_name(name),
                                       devices=cpu_devices)
            assert report["counts"].get("all-gather", 0) == 0, report

    def test_injected_reshard_fails_the_gate(self, budget_table,
                                             cpu_devices):
        report = audit.audit_point(audit.point_by_name("dp"),
                                   inject_reshard=True,
                                   devices=cpu_devices)
        violations = budgets.check_report(report, budget_table)
        assert violations, "an injected reshard must trip the budget gate"

    def test_report_artifact_is_json_serializable(self, cpu_devices):
        report = audit.audit_point(audit.point_by_name("dp"),
                                   devices=cpu_devices, keep_ops=True)
        parsed = json.loads(json.dumps(report))
        assert parsed["ops"], "keep_ops should include the instruction list"

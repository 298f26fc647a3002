"""Each kernel's bytes and flops on a case worked by hand."""

import json
import os

import pytest

from harness import flops, spec

V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def config(name):
    with open(os.path.join(spec.BENCH_DIR, "configs", f"{name}.json")) as fh:
        return json.load(fh)


def test_paged_decode_counts_live_pages_only():
    k = spec.load_kernel("paged_decode")
    assert k.BOUND == "bytes"
    # one row of 17 tokens, pages of 16, 8 kv heads of 128, bf16:
    # 2 pages x (K and V) x 8 x 16 x 128 x 2 B = 131,072; q + o 16,384
    assert k.bytes_moved([17], 16, 8, 32, 128) == 131_072 + 16_384
    # a page boundary: 16 tokens are one page, idle rows cost nothing
    assert k.bytes_moved([16, 0, -1], 16, 8, 32, 128) == 65_536 + 16_384
    # 16 rows of 600 tokens (38 pages each)
    rows = k.bytes_moved([600] * 16, 16, 8, 32, 128)
    assert rows == 16 * (2 * 38 * 8 * 16 * 128 * 2 + 16_384)
    least = k.least_seconds(V5E, [600] * 16, 16, 8, 32, 128)
    assert least == pytest.approx(rows / 819e9)           # memory-bound
    assert k.flops([600] * 16, 32, 128) / 197e12 < least


def test_flash_fwd_counts_the_causal_half():
    k = spec.load_kernel("flash_fwd")
    assert k.BOUND == "flops"
    # B=1, S=4096, H=32, Hd=128: QK^T and PV are 2*S*S*Hd each per head,
    # half of it causal: 2 * 32 * 4096^2 * 128 = 137.4 GFLOP
    assert k.flops(1, 4096, 32, 128) == 2 * 32 * 4096 ** 2 * 128
    least = k.least_seconds(V5E, 3, 4096, 32, 8, 128)
    assert least == pytest.approx(3 * 2 * 32 * 4096 ** 2 * 128 / 197e12)


def test_model_flops_leave_out_the_embedding_and_follow_the_cut():
    c = config("mistral_7b_v03")
    proj = 2 * (4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336)
    attn = 2 * 4096 * 4096
    head = 2 * 4096 * 32768
    assert flops.forward_flops_per_token(c, 2, 4096) == 2 * (proj + attn) + head
    assert flops.train_flops_per_token(c, 2, 4096) == \
        3 * flops.forward_flops_per_token(c, 2, 4096)
    # depth as cut, not the published 32
    assert flops.forward_flops_per_token(c, 32, 4096) > \
        10 * flops.forward_flops_per_token(c, 2, 4096) - 10 * head


def test_moe_flops_count_the_experts_a_token_uses():
    c = config("mixtral_8x7b_v01")
    proj = 2 * (4096 * 4096 * 2 + 2 * 4096 * 1024
                + 2 * 3 * 4096 * 14336 + 4096 * 8)
    assert flops.forward_flops_per_token(c, 1, 4096) == \
        proj + 2 * 4096 * 4096 + 2 * 4096 * 32000


def test_unknown_device_kind_is_an_error():
    assert spec.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v9 imaginary")

"""The yardstick's counts against hand counts at qwen2-1.5b's sizes."""
import json
import os

import pytest

import work
from conftest import BENCH

L, D, H, KV, DH, FF, V = 28, 1536, 12, 2, 128, 8960, 151936


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


DENSE, SPARSE = config("qwen2-1.5b"), config("qwen2-1.5b-bsffn")

# per layer: q (D x D + D bias), k and v (D x 256 + 256 bias each), o
# (D x D), two norm scales
ATTN = D * D + D + 2 * (D * KV * DH + KV * DH) + D * D
NORMS = 2 * D
# the embedding (tied output head) and the final norm
OUTER = V * D + D
# 8960/16 x 1536/16 = 560 x 96 = 53,760 blocks, an eighth of them
NNZ = 53_760 // 8


@pytest.mark.parametrize("c, ffn, millions", [
    (DENSE, 3 * D * FF, 1543.7),
    (SPARSE, 3 * NNZ * 16 * 16, 532.2),
])
def test_param_count(c, ffn, millions):
    want = L * (ATTN + NORMS + ffn) + OUTER
    assert work.param_count(c) == want
    assert round(want / 1e6, 1) == millions


def test_nonzero_blocks_per_ffn_matrix():
    assert NNZ == 6_720
    masks = work.ffn_masks(SPARSE)
    assert {k: int(m.sum()) for k, m in masks.items()} == {
        "up": NNZ, "gate": NNZ, "down": NNZ}
    assert masks["up"].shape == (FF // 16, D // 16)
    assert masks["down"].shape == (D // 16, FF // 16)
    # three different patterns
    assert (masks["up"] != masks["gate"]).any()
    assert work.ffn_masks(DENSE) is None


def test_kv_cache_and_weight_bytes():
    # 28 layers x (k, v) x 2 heads x 128 x 2 bytes per position,
    # 32 slots x 2048 positions
    assert work.kv_cache_bytes(DENSE, 32, 2048) == 1_879_048_192
    norms = (2 * L + 1) * D
    assert work.weight_bytes(DENSE) == \
        (work.param_count(DENSE) - norms) * 2 + norms * 4
    assert round(work.weight_bytes(DENSE) / 1e9, 2) == 3.09
    assert round(work.weight_bytes(SPARSE) / 1e9, 2) == 1.06


def test_decode_and_prefill_work():
    per_token = 2 * (ATTN - D - 2 * KV * DH + 3 * D * FF)   # matmuls only
    flops, nbytes = work.decode_step(DENSE, [10, 20])
    assert flops == 2 * (L * per_token + 2 * V * D) \
        + 4 * L * H * DH * 30
    assert nbytes == work.weight_bytes(DENSE) + L * 2 * KV * DH * 2 * 30
    flops, nbytes = work.prefill(DENSE, 4)
    assert flops == 4 * L * per_token + 4 * L * H * DH * 10 + 2 * V * D
    assert nbytes == work.weight_bytes(DENSE) + L * 2 * KV * DH * 2 * 4


def test_matmul_call_and_kernel_bound():
    assert work.matmul_call(8, 4, 2) == (2 * 8 * 4 * 2, (32 + 8 + 16) * 2)
    assert work.matmul_call(32, 32, 2, nnz=1, b=16) == (
        2 * 256 * 2, (256 + 64 + 64) * 2)
    peaks = {"flops_per_s": 1.0, "bytes_per_s": 1e30}
    plans = [{"route": "static_pallas", "kind": "static",
              "shape": (FF, D, 32)},
             {"route": "static_balanced", "kind": "static",
              "shape": (FF, D, 32)},
             {"route": "static_pallas", "kind": "static",
              "shape": (D, FF, 32)},
             {"route": "dense_pallas", "kind": "dense",
              "shape": (D, D, 32)}]
    # all three FFN matrices, every layer; compute bound at 1 FLOP/s
    sparse = work.kernel_bound_s(SPARSE, plans, 32,
                                 ("static_pallas", "static_balanced"), peaks)
    assert sparse == L * 3 * 2 * NNZ * 256 * 32
    # up and gate share one shape: one of their two plans is balanced
    only = work.kernel_bound_s(SPARSE, plans, 32, ("static_balanced",),
                               peaks)
    assert only == L * 2 * (2 * NNZ * 256 * 32) / 2
    # q and o share the one dense plan of their shape
    dense = work.kernel_bound_s(SPARSE, plans, 32, ("dense_pallas",), peaks)
    assert dense == L * 2 * 2 * D * D * 32

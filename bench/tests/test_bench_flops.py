"""The benchmark's operation and byte counts against hand counts."""
import json
import os

from bench import flops

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "configs")


def _conf(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_gpt2_small_matmul_params_by_hand():
    # per layer: q, k, v, o of 768 x 768 and the MLP's 768 x 3072 twice;
    # the tied head 50257 x 768; the embedding lookup is not counted
    layer = 4 * 768 * 768 + 2 * 768 * 3072
    assert layer == 7_077_888
    assert flops.matmul_params(_conf("gpt2-small")) == 12 * layer + 50257 * 768
    assert flops.matmul_params(_conf("gpt2-small")) == 123_532_032


def test_gpt2_small_flops_per_token_by_hand():
    # PaLM appendix B: 6 N + 12 L (H Q) S, with H Q = 768 and S = 1024
    want = 6 * 123_532_032 + 12 * 12 * 768 * 1024
    assert want == 854_438_400
    assert flops.train_flops_per_token(_conf("gpt2-small"), 1024) == want


def test_starcoder2_3l_flops_per_token_by_hand():
    # GQA: k and v project to 4 heads of 128; H Q = 36 * 128 = 4608
    layer = 2 * 4608 * 4608 + 2 * 4608 * 512 + 2 * 4608 * 18432
    n = 3 * layer + 49152 * 4608
    assert n == 877_658_112
    got = flops.train_flops_per_token(_conf("starcoder2-7b-3l"), 4096)
    assert got == 6 * n + 12 * 3 * 4608 * 4096


def test_boundary_kernel_bytes_by_hand():
    # gpt2's cut at 16 x 1024 tokens: a bf16 read and a bf16 write
    want = 2 * 16 * 1024 * 768 * 2
    assert flops.boundary_kernel_bytes(16, 1024, 768) == want
    assert flops.boundary_kernel_bytes(1, 4096, 4608, elem_bytes=4) == \
        2 * 4096 * 4608 * 4

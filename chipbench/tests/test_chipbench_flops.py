"""Operation and byte counts against hand counts for both configurations."""
import json

from chipbench_tiny import ROOT

from chipbench import flops


def _cfg(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                      .read_text())


def test_qwen3_0_6b_counts():
    cfg = _cfg("qwen3-0.6b")
    # q, k, v: 1024 x (16 + 8 + 8) x 128; o: 16 x 128 x 1024;
    # gate, up, down: 3 x 1024 x 3072
    assert flops.layer_params(cfg) == 4_194_304 + 2_097_152 + 9_437_184
    # 2 x (28 layers x 15,728,640 + tied head 1024 x 151,936)
    assert flops.matmul_flops_per_token(cfg) == 2 * (440_401_920
                                                     + 155_582_464)
    # 28 layers x 4 x 16 heads x 128 x 1000 positions
    assert flops.attn_flops(cfg, 1000) == 229_376_000
    # 28 x 2 B x (K and V: 2 x 8 x 128 x 1000 + q and out: 2 x 16 x 128 x 10)
    assert flops.attn_bytes(cfg, 1000, 10) == 56 * (2_048_000 + 40_960)
    assert flops.decode_flops(cfg, 10, 1000) == (
        10 * 1_191_968_768 + 229_376_000)


def test_qwen3_8b_18l_counts():
    cfg = _cfg("qwen3-8b-18l")
    # 4096 x (32 + 8 + 8) x 128 + 32 x 128 x 4096 + 3 x 4096 x 12288
    assert flops.layer_params(cfg) == 192_937_984
    # 18 layers and the untied head 4096 x 151,936
    assert flops.matmul_flops_per_token(cfg) == 2 * (3_472_883_712
                                                     + 622_329_856)
    # 18 x 4 x 32 heads x 128 x 1
    assert flops.attn_flops(cfg, 1) == 294_912
    assert flops.attn_bytes(cfg, 0, 1) == 18 * 2 * 2 * 32 * 128

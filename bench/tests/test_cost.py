"""Operations and bytes against counts made by hand, and the peak table."""

import pytest

from bench.cost.attention import paged_decode
from bench.cost.lowrank import lowrank_call
from bench.cost.model import per_token
from bench.harness import spec


def test_lowrank_single_and_batched():
    # (2x3) @ (3x1) @ (1x4): 2*2*1*3 + 2*2*1*4 = 28; bytes 2*(6 + 3 + 4 + 8)
    assert lowrank_call(2, 3, 1, 4) == (28, 42)
    assert lowrank_call(2, 3, 1, 4, L=5) == (140, 210)


def test_paged_decode_counts_valid_kv_only():
    # 2 tokens attending 4 and 6 positions, 2 heads over 1 KV head of 4, 3 layers
    flops, nbytes = paged_decode(10, 2, heads=2, kv_heads=1, head_dim=4, layers=3)
    assert flops == 3 * 4 * 10 * 2 * 4
    assert nbytes == 3 * 10 * 1 * 4 * 2 * 2 + 3 * 2 * 2 * 4 * 2 * 2


def test_model_flops_by_hand():
    import numpy as np

    bf16 = np.dtype("float32")
    leaves = [
        ("embed", (10, 4), bf16, None),
        ("layers/attn/wq", (2, 4, 4), bf16, 1),  # 2 layers x 2*1*(4+4)
        ("layers/mlp/w_up", (2, 4, 6), bf16, None),  # 2 layers x 2*4*6
        ("lm_head", (4, 10), bf16, None),
        ("final_norm/scale", (4,), bf16, None),
    ]
    cfg = {"num_hidden_layers": 2, "num_attention_heads": 3, "head_dim": 5}
    trunk, head, attn = per_token(leaves, cfg)
    assert trunk == 2 * 16 + 2 * 48
    assert head == 80
    assert attn == 4 * 2 * 3 * 5


@pytest.mark.parametrize("held, width", [(3, 12), (3, 3)])
def test_moe_and_latent_attention_flops_by_hand(held, width):
    """Routed experts count k / R of their stacked experts, k and R the
    built model's experts per token and router width; shared experts,
    routers and dense layers count whole; attention is latent, ``2 L H
    (2 kv_lora + rope)``."""
    import numpy as np

    f = np.dtype("float32")
    leaves = [
        ("dense_layers/mlp/w_up", (1, 4, 8), f, None),  # 1 layer x 2*4*8
        ("layers/attn/wq_a", (2, 4, 6), f, 2),  # 2 layers x 2*2*(4+6)
        ("layers/moe/experts/w_up", (2, held, 4, 6), f, None),  # 2*3 x 2*4*6 = 288
        ("layers/moe/shared/w_up", (2, 4, 6), f, None),  # 2 x 48
        ("layers/moe/router/gate_w", (2, 4, 12), f, None),  # 2 x 96
        ("lm_head", (4, 10), f, None),
    ]
    cfg = {"num_hidden_layers": 3, "num_attention_heads": 2, "kv_lora_rank": 5,
           "qk_rope_head_dim": 3}
    trunk, head, attn = per_token(leaves, cfg, route=(2, width))
    assert trunk == 64 + 80 + 288 * 2 / width + 96 + 192
    assert head == 80
    assert attn == 2 * 3 * 2 * (2 * 5 + 3)
    with pytest.raises(ValueError, match="route"):
        per_token(leaves, cfg)


def test_peak_table_has_v5e_and_refuses_others():
    row = spec.peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        spec.peaks_for("TPU v9 imaginary")

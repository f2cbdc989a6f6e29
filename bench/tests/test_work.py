"""The configuration work counts, against counts made by hand."""
import run


def _work():
    return run.module("configs", "qwen2-0.5b.work.py")


def test_qwen2_param_count_by_hand():
    _, cfg = run.cell("train.qwen2-0.5b.pbsp")
    # per layer: q 896x896 + 896, k and v 896x128 + 128 each, o 896x896,
    # gate+up 2 x 896x4864, down 4864x896, two norms of 896
    layer = (802_816 + 896) + 2 * (114_688 + 128) + 802_816 \
        + 8_716_288 + 4_358_144 + 1_792
    assert layer == 14_912_384
    total = 24 * layer + 151_936 * 896 + 896   # tied embedding, final norm
    assert total == 494_032_768
    assert _work().param_count(cfg) == 494_032_768


def test_qwen2_train_flops_per_token_by_hand():
    _, cfg = run.cell("train.qwen2-0.5b.pbsp")
    s = 512
    proj = 2 * s * 24 * 14_909_440                     # q k v o, MLP
    attn = 24 * 14 * 2 * 2 * 64 * (s * (s + 1) // 2)   # causal QK and PV
    head = 2 * (s - 1) * 896 * 151_936                 # logits, tied
    want = 3 * (proj + attn + head) / s
    assert _work().train_flops_per_token(cfg, s) == want
    # close to, and above, the 6N rule of thumb (attention adds)
    assert 1.0 < want / (6 * 494_032_768) < 1.05

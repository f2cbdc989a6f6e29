"""The work of qwen2-0.5b training, counted from the configuration's shapes.

Counts what the algorithm needs, never what an implementation runs: matrix
products of a forward pass at 2 FLOPs per multiply-add, causal attention
over the keys each query may see, the logits of every position but the
last, and a backward pass of twice the forward.  Recomputation, padding
and masked-out attention tiles are not work.
"""
from __future__ import annotations


def shapes(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "ff": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"]}


def param_count(cfg: dict) -> int:
    """Every parameter, the tied embedding counted once."""
    s = shapes(cfg)
    d, hq, hk, ff = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"], s["ff"]
    layer = (d * hq + hq) + 2 * (d * hk + hk) + hq * d + 3 * d * ff + 2 * d
    return s["L"] * layer + s["v"] * d + d


def matmul_weights_per_layer(cfg: dict) -> int:
    """Multiply-adds per token of one layer's projections and MLP."""
    s = shapes(cfg)
    d, hq, hk, ff = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"], s["ff"]
    return d * hq + 2 * d * hk + hq * d + 3 * d * ff


def forward_flops_per_sequence(cfg: dict, seq: int) -> int:
    """FLOPs of one forward pass and loss over one sequence of ``seq``."""
    s = shapes(cfg)
    proj = 2 * seq * s["L"] * matmul_weights_per_layer(cfg)
    # query i sees keys 0..i: seq(seq+1)/2 (query, key) pairs per head,
    # each a score and a weighted value of hd multiply-adds
    attn = s["L"] * s["h"] * 2 * 2 * s["hd"] * seq * (seq + 1) // 2
    head = 2 * (seq - 1) * s["d"] * s["v"]
    return proj + attn + head


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward FLOPs per trained token at length ``seq``."""
    return 3 * forward_flops_per_sequence(cfg, seq) / seq

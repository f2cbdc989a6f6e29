"""Trainer cells cut to sizes a CPU test holds.

``cell`` gives two layers of width 64, a 512-token vocabulary and
sequences of 32; ``dtype`` float32 makes the program's arithmetic the
reference's up to rounding.  ``control_cell`` is eight layers of width
384 (6 heads, GQA kv 2, head 64, as published), where float8 products
already read above the cell's limits; at width 64 they do not, and on
the chip at the published width they read higher still (PERF.md).
"""
from __future__ import annotations

import run


def cell(name: str, dtype: str = "float32") -> tuple[dict, dict]:
    wl, cfg = run.cell(name)
    cfg = dict(cfg, hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4, head_dim=16,
               num_key_value_heads=2, vocab_size=512, compute_dtype=dtype)
    wl = dict(wl, seq=32)
    return wl, cfg


def control_cell(name: str) -> tuple[dict, dict]:
    wl, cfg = run.cell(name)
    cfg = dict(cfg, hidden_size=384, intermediate_size=1536,
               num_hidden_layers=8, num_attention_heads=6,
               num_key_value_heads=2, vocab_size=4096)
    return dict(wl, seq=32), cfg

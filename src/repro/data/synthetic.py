"""Deterministic synthetic LM data pipeline.

Generates learnable token streams (a noisy order-2 Markov process over the
vocabulary) so training losses actually go down in tests/examples, with a
shard-aware iterator: each PSP worker / data shard derives its stream from
``fold_in(seed, shard_index)``, matching the paper's i.i.d.-per-node data
assumption (§5).

``make_batch_specs`` produces the ShapeDtypeStruct stand-ins the dry-run
lowers against (no allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import AxisRules


#: largest vocabulary given a dense ``(v, v)`` transition table (64 MB);
#: a published vocabulary (151,936 for qwen2) would need 92 GB of host
#: memory, so larger ones use a table-free order-1 process instead
DENSE_VOCAB_MAX = 4096


@dataclasses.dataclass
class SyntheticLM:
    vocab_size: int
    seq_len: int
    batch: int
    seed: int = 0
    n_shards: int = 1
    shard: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)   # shared task definition
        v = self.vocab_size
        self._trans = None
        if v <= DENSE_VOCAB_MAX:
            # order-1 transition logits with strong structure + noise
            self._trans = rng.normal(size=(v, v)).astype(np.float32)
            self._trans += 3.0 * np.eye(v, k=1, dtype=np.float32)[
                np.arange(v)[:, None] % v, np.arange(v)[None, :] % v]
        self._rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, self.shard]))

    def _sample_seq(self) -> np.ndarray:
        v = self.vocab_size
        seq = np.empty(self.seq_len, dtype=np.int32)
        seq[0] = self._rng.integers(v)
        if self._trans is None:
            # table-free: the successor token with probability ½, else a
            # uniform draw
            follow = self._rng.random(self.seq_len) < 0.5
            draw = self._rng.integers(v, size=self.seq_len)
            for i in range(1, self.seq_len):
                seq[i] = (seq[i - 1] + 1) % v if follow[i] else draw[i]
            return seq
        # vectorised Gumbel-max over the transition row
        for i in range(1, self.seq_len):
            logits = self._trans[seq[i - 1]]
            g = self._rng.gumbel(size=v).astype(np.float32)
            seq[i] = int(np.argmax(logits + g))
        return seq

    def __iter__(self) -> Iterator[Dict[str, jax.Array]]:
        while True:
            toks = np.stack([self._sample_seq() for _ in range(self.batch)])
            yield {"tokens": jnp.asarray(toks)}


def make_batch_specs(cfg, shape, rules: Optional[AxisRules] = None,
                     kind: Optional[str] = None) -> Dict:
    """ShapeDtypeStruct batch for (arch cfg, InputShape) — the dry-run input.

    train/prefill: {"tokens": (B, S_tok)[, "embeds": (B, F, D)]}
    decode: {"tokens": (B, 1)} (cache specs come from models.cache_defs).
    """
    kind = kind or shape.kind
    B = shape.global_batch

    def spec(shp, dtype, axes):
        sharding = rules.sharding(axes, shp) if rules else None
        return jax.ShapeDtypeStruct(shp, dtype, sharding=sharding)

    if kind == "decode":
        return {"tokens": spec((B, 1), jnp.int32, ("batch", None))}
    F = cfg.frontend_tokens
    batch = {"tokens": spec((B, shape.seq_len - F), jnp.int32,
                            ("batch", None))}
    if F:
        batch["embeds"] = spec((B, F, cfg.d_model), jnp.bfloat16,
                               ("batch", None, None))
    return batch

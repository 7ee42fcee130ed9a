"""What the algorithm needs, in operations and bytes, computed from shapes.

These counts are independent of how the program implements a pass or a
step: attention is counted over the live context (not the cache's width),
the output head only where a position's logits are used, and no recomputed
forward work is counted.  Multiply-add = 2 operations.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Iterable, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class DenseDims:
    d: int
    ff: int
    layers: int
    heads: int
    kv_heads: int
    hd: int
    vocab: int
    tied: bool
    bias: bool
    layernorm: bool
    param_bytes: int = 2          # bytes per weight as served / trained
    kv_bytes: int = 2             # bytes per cached key or value element

    @classmethod
    def from_config(cls, c: Dict) -> "DenseDims":
        heads = int(c["num_attention_heads"])
        return cls(d=int(c["hidden_size"]), ff=int(c["intermediate_size"]),
                   layers=int(c["num_hidden_layers"]), heads=heads,
                   kv_heads=int(c.get("num_key_value_heads", heads)),
                   hd=int(c.get("head_dim") or c["hidden_size"] // heads),
                   vocab=int(c["vocab_size"]),
                   tied=bool(c["tie_word_embeddings"]),
                   bias=bool(c.get("attention_bias", c.get("use_qkv_bias"))),
                   layernorm="layer_norm_eps" in c)

    @property
    def layer_matmul_params(self) -> int:
        q, kv = self.heads * self.hd, self.kv_heads * self.hd
        return self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.ff

    @property
    def layer_params(self) -> int:
        q, kv = self.heads * self.hd, self.kv_heads * self.hd
        small = 2 * self.d * (2 if self.layernorm else 1)
        return self.layer_matmul_params + small + ((q + 2 * kv) if self.bias
                                                   else 0)

    @property
    def head_params(self) -> int:
        return self.d * self.vocab

    @property
    def kv_per_position(self) -> int:
        """Bytes of keys and values one position holds over all layers."""
        return 2 * self.layers * self.kv_heads * self.hd * self.kv_bytes


def decode_pass(dm: DenseDims, lanes: Iterable[Tuple[int, bool]]
                ) -> Tuple[float, float]:
    """(operations, bytes) one serving pass needs.  ``lanes`` holds, per
    lane, the live context (positions attended, this one included) and
    whether the lane's logits are used (a generated token, not a
    teacher-forced prompt position).  Weights are read once per pass; each
    lane reads its live keys and values and writes one position's."""
    lanes = list(lanes)
    if not lanes:
        return 0.0, 0.0
    n = len(lanes)
    ctx = sum(c for c, _ in lanes)
    emit = sum(1 for _, e in lanes if e)
    flops = 2.0 * dm.layer_matmul_params * dm.layers * n
    flops += 4.0 * dm.heads * dm.hd * dm.layers * ctx
    flops += 2.0 * dm.head_params * emit
    weights = dm.layer_params * dm.layers + dm.d * (2 if dm.layernorm else 1)
    if emit:
        weights += dm.head_params
    nbytes = float(weights * dm.param_bytes)
    nbytes += n * dm.d * dm.param_bytes            # embedding rows gathered
    nbytes += dm.kv_per_position * (ctx + n)       # read live, write one
    return flops, nbytes


def train_step(dm: DenseDims, batch: int, seq: int) -> Tuple[float, float]:
    """(operations, bytes) of one training step: forward and backward of
    every matmul (6 per weight per token), causal attention (half of the
    square), the output head over every position.  Bytes: each weight read
    in the forward and twice in the backward, the gradient written, and
    AdamW's read-modify-write of parameters and two float32 moments."""
    tokens = batch * seq
    matmul = dm.layer_matmul_params * dm.layers + dm.head_params
    flops = 6.0 * matmul * tokens
    # QK^T and PV: 2 * 2 * heads * hd per (query, key) pair, causal half
    pairs = batch * seq * (seq + 1) / 2
    flops += 3.0 * 4.0 * dm.heads * dm.hd * dm.layers * pairs
    params = dm.layer_params * dm.layers + dm.head_params * (1 if dm.tied
                                                             else 2)
    nbytes = params * (3 * dm.param_bytes + dm.param_bytes       # fwd+bwd+grad
                       + 2 * dm.param_bytes + 2 * 2 * 4)         # p, m, v r/w
    return flops, float(nbytes)


def load_peaks() -> Dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a device not in the table is an error."""
    table = load_peaks()["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peaks: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The larger of the compute and the memory bound, and which binds."""
    tf = flops / peaks["bf16_flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")

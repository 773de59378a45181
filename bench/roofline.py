"""Operations and bytes that one call of a decoder program needs, computed
from shapes alone, the same whatever implements the program; and the chip's
peaks (``peaks.json``, keyed by ``device_kind``).

A call runs ``rows`` sequences of ``tokens`` new tokens each through a
Llama-architecture decoder whose weights are split evenly over ``tp`` chips
(the numbers are per chip).  Each row attends ``kv_rows`` earlier positions
from the cache.  Counted:

  FLOPs   2 per weight per token for every matrix (layers and the output
          head), plus 4 * heads * head_dim per attended key per token per
          layer (scores and the weighted sum)
  bytes   every weight once, the embedding rows the tokens look up, the K/V
          rows read, and the K/V rows the new tokens write

Norm scales are read but counted with the weights; logits are reduced on
the chip and never need to leave it, so they are not counted.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    table = json.loads(Path(path).read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path} "
                       f"(known: {', '.join(sorted(table['devices']))})")
    return table["devices"][device_kind]


@dataclasses.dataclass(frozen=True)
class Decoder:
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    bytes_per: int = 2  # bfloat16 weights and cache

    @classmethod
    def of(cls, cfg: dict, bytes_per: int = 2) -> "Decoder":
        """From a Hugging Face style configuration."""
        hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
        return cls(cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
                   cfg["num_attention_heads"], cfg["num_key_value_heads"], hd,
                   cfg["intermediate_size"], bytes_per)

    @property
    def layer_matrix_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return 2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + 3 * d * self.d_ff

    @property
    def params(self) -> int:
        """Every parameter: layers (matrices and two norm scales), embedding,
        output head and final norm."""
        layer = self.layer_matrix_params + 2 * self.d_model
        return self.n_layers * layer + 2 * self.vocab * self.d_model + self.d_model

    def param_bytes(self, tp: int = 1) -> float:
        return self.params * self.bytes_per / tp

    def kv_bytes_per_row(self) -> int:
        """K and V of one position over all layers."""
        return 2 * self.n_layers * self.n_kv_heads * self.head_dim * self.bytes_per

    def call(self, rows: int, tokens: int, kv_rows: float, tp: int = 1) -> tuple[float, float]:
        """(FLOPs, bytes) per chip of one call."""
        n = rows * tokens
        matmul = self.n_layers * self.layer_matrix_params + self.vocab * self.d_model
        attn = 4 * self.n_layers * self.n_heads * self.head_dim * (kv_rows + tokens)
        flops = (2 * matmul + attn) * n
        weights = (self.n_layers * (self.layer_matrix_params + 2 * self.d_model)
                   + self.vocab * self.d_model + self.d_model) * self.bytes_per
        lookups = n * self.d_model * self.bytes_per
        kv = rows * kv_rows * self.kv_bytes_per_row() + n * self.kv_bytes_per_row()
        return flops / tp, (weights + lookups + kv) / tp


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

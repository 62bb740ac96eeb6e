"""A configuration file's sizes, read once for the weights, the reference,
the work counts and the program's config."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    experts: int = 0                # 0: dense FFN
    top_k: int = 0
    qkv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    window: int = 0                 # 0: full attention

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        """From a configuration file's Hugging Face keys."""
        heads = c["num_attention_heads"]
        window = 0
        if c.get("sliding_window") and c.get("use_sliding_window", True):
            window = int(c["sliding_window"])
        return cls(name=c["name"], layers=c["num_hidden_layers"],
                   d=c["hidden_size"], heads=heads,
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // heads,
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   experts=c.get("num_local_experts", 0),
                   top_k=c.get("num_experts_per_tok", 0),
                   qkv_bias=bool(c.get("qkv_bias", False)),
                   norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
                   window=window)

    @classmethod
    def load(cls, path) -> "Dims":
        return cls.from_config(json.loads(Path(path).read_text()))

    def scaled(self, **kw) -> "Dims":
        return dataclasses.replace(self, **kw)

    # -- bytes ------------------------------------------------------------- #

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V over every layer."""
        return self.layers * 2 * self.kv_heads * self.head_dim * 2

    @property
    def layer_params(self) -> int:
        d, H, hk, hd, f = self.d, self.heads, self.kv_heads, self.head_dim, \
            self.d_ff
        attn = d * (H + 2 * hk) * hd + H * hd * d
        if self.qkv_bias:
            attn += (H + 2 * hk) * hd
        ffn = 3 * d * f * max(self.experts, 1)
        if self.experts:
            ffn += d * self.experts
        return attn + ffn + 2 * d

    @property
    def params(self) -> int:
        return self.layers * self.layer_params + 2 * self.vocab * self.d \
            + self.d

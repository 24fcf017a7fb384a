"""Model zoo: pure-functional JAX implementations (params are pytrees, every
forward is jit-safe) designed around the paged KV cache and GSPMD sharding.

A family is one module that holds its config class and its forward
functions (`init_params`, `param_count`, `prefill`, `prefill_chunk`,
`prefill_packed`, `decode`, ...) under the same names and signatures; the
runner asks `forward_for(config)` for the module and knows no family. A
config declares the kind of cache its layers keep (`cache_kind()`), and the
allocator, the block budget and the row scatter derive from the declaration.
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import dataclass


@dataclass(frozen=True)
class CacheKind:
    """What one layer keeps per cached token.

    `kv_heads`: two planes (keys, values) of `heads` x `width`.
    `latent`: one plane of one row: `width` values (compressed latent, then
    the shared rope key), stored `stored_width` wide (zeros behind)."""

    name: str  # "kv_heads" | "latent"
    planes: int
    heads: int
    width: int
    stored_width: int

    def stored_values_per_token(self, tp: int = 1) -> int:
        return self.planes * max(1, self.heads // tp) * self.stored_width


def kv_heads_cache(num_kv_heads: int, head_dim: int) -> CacheKind:
    return CacheKind("kv_heads", 2, num_kv_heads, head_dim, head_dim)


def latent_cache(width: int, lanes: int = 128) -> CacheKind:
    return CacheKind("latent", 1, 1, width, -(-width // lanes) * lanes)


def cache_kind(config) -> CacheKind:
    """The declaration of `config`'s layers; a config without one is the
    grouped-query family's."""
    declared = getattr(config, "cache_kind", None)
    if declared is not None:
        return declared()
    return kv_heads_cache(config.num_kv_heads, config.head_dim)


def forward_for(config):
    """The module that defines `config`'s class: its family's forward."""
    return sys.modules[type(config).__module__]


def config_from_model_dir(model_dir: str):
    """The config of the family that `config.json`'s `model_type` names."""
    with open(os.path.join(model_dir, "config.json")) as f:
        hf = json.load(f)
    from dynamo_tpu.models import mla_moe
    from dynamo_tpu.models.llama import LlamaConfig

    if hf.get("model_type") in mla_moe.MODEL_TYPES:
        return mla_moe.MlaMoeConfig.from_hf_dict(hf)
    return LlamaConfig.from_hf_dict(hf)

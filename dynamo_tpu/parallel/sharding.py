"""GSPMD sharding rules for the llama family.

Megatron-style tensor parallelism expressed as NamedShardings on the param
and KV-cache pytrees; the model code stays unchanged — XLA propagates the
shardings through the einsums and inserts the psum after the row-parallel
projections (wo, wd). This is the TPU-idiomatic equivalent of the
`--tensor-parallel-size` NCCL plumbing the reference passes to vLLM/SGLang.

Layout:
  wq/wk/wv  [E, heads*D]  -> shard out dim on tp (column parallel)
  wo        [heads*D, E]  -> shard in dim on tp (row parallel, psum after)
  wg/wu     [E, F]        -> column parallel
  wd        [F, E]        -> row parallel
  lm_head   [E, V]        -> vocab-sharded; logits all-gathered (few MB)
  embed, norms            -> replicated
  kv cache  per layer [Hkv, N, Bs, D] -> heads on tp (head-major: each
                             (head, page) a contiguous [Bs, D] pallas tile)
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models.llama import LlamaConfig


def _ns(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def put_local(arr, sharding: NamedSharding):
    """Single-controller placement (the default)."""
    return jax.device_put(arr, sharding)


def put_global(arr, sharding: NamedSharding):
    """Multi-controller placement: every process holds the same FULL host
    array and contributes its addressable shards — how params land on a
    mesh spanning hosts (multihost bring-up, parallel/multihost.py).
    global_shape == the local shape tells jax the local data is the whole
    array, not this process's slice."""
    import numpy as np

    arr = np.asarray(arr)
    return jax.make_array_from_process_local_data(
        sharding, arr, global_shape=arr.shape
    )


def _shard_linear(mesh: Mesh, w: Any, spec_in, spec_out, put=put_local) -> Any:
    """Place a (possibly int8-quantized) linear weight."""
    if isinstance(w, dict):
        return {
            "q": put(w["q"], _ns(mesh, spec_in, spec_out)),
            "s": put(w["s"], _ns(mesh, spec_out)),
        }
    return put(w, _ns(mesh, spec_in, spec_out))


def shard_llama(
    mesh: Mesh, config: LlamaConfig, params: dict, put=put_local
) -> tuple[dict, NamedSharding]:
    """Places params onto the mesh; returns (params, the sharding of one
    layer's [Hkv, N, Bs, D] cache array).

    `put` is the placement primitive: jax.device_put on one controller,
    put_global under multi-host (every process passes identical host
    params; each contributes its local shards)."""
    if config.num_kv_heads % mesh.shape["tp"] != 0:
        raise ValueError(
            f"num_kv_heads={config.num_kv_heads} not divisible by "
            f"tp={mesh.shape['tp']}"
        )
    ep = mesh.shape.get("ep", 1)
    if config.num_experts and config.num_experts % ep != 0:
        raise ValueError(
            f"num_experts={config.num_experts} not divisible by ep={ep}"
        )
    repl = _ns(mesh, None)
    out: dict = {
        "embed": put(params["embed"], _ns(mesh, None, None)),
        "final_norm": put(params["final_norm"], repl),
        "layers": [],
    }
    for layer in params["layers"]:
        placed = {
            "attn_norm": put(layer["attn_norm"], repl),
            "wq": _shard_linear(mesh, layer["wq"], None, "tp", put),
            "wk": _shard_linear(mesh, layer["wk"], None, "tp", put),
            "wv": _shard_linear(mesh, layer["wv"], None, "tp", put),
            "wo": _shard_linear(mesh, layer["wo"], "tp", None, put),
            "mlp_norm": put(layer["mlp_norm"], repl),
        }
        if "bq" in layer:
            # qwen2 q/k/v biases follow their column-parallel outputs
            placed.update(
                bq=put(layer["bq"], _ns(mesh, "tp")),
                bk=put(layer["bk"], _ns(mesh, "tp")),
                bv=put(layer["bv"], _ns(mesh, "tp")),
            )
        if "router" in layer:
            # WideEP: experts sharded over ep, each expert's FFN over tp
            # (dsr1-wideep equivalent: dp-attention + deepep-moe flags)
            placed.update(
                router=put(layer["router"], _ns(mesh, None, None)),
                wg=put(layer["wg"], _ns(mesh, "ep", None, "tp")),
                wu=put(layer["wu"], _ns(mesh, "ep", None, "tp")),
                wd=put(layer["wd"], _ns(mesh, "ep", "tp", None)),
            )
        else:
            placed.update(
                wg=_shard_linear(mesh, layer["wg"], None, "tp", put),
                wu=_shard_linear(mesh, layer["wu"], None, "tp", put),
                wd=_shard_linear(mesh, layer["wd"], "tp", None, put),
            )
        out["layers"].append(placed)
    if "lm_head" in params:
        out["lm_head"] = _shard_linear(mesh, params["lm_head"], None, "tp", put)
    # one layer of the paged cache, [Hkv, nb, bs, D]: heads over tp
    kv_sharding = _ns(mesh, "tp", None, None, None)
    return out, kv_sharding

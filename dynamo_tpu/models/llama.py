"""Llama-family model (Llama 2/3, DeepSeek-R1-Distill-Llama, TinyLlama...)
as pure JAX functions over a paged KV cache.

This is the engine-side model math the reference delegates to vLLM/SGLang —
built TPU-first instead: bf16 (or int8-quantized) weights feeding the MXU,
per-layer paged KV blocks, RoPE with llama3 scaling, GQA, SwiGLU. Layers are
a Python loop with static indices so cache updates compile to in-place
dynamic-update-slices under jit donation; the loop's body is one jitted
function a kind of pass (`models.layer_body`), traced and lowered once for
each kind of layer and called `num_layers` times.

Tensor-parallel sharding is applied externally (parallel/sharding.py) by
placing NamedShardings on the param/cache pytrees; the einsums here are
written so GSPMD propagates head/ffn shardings without code changes.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, fields
from typing import Any, Optional

import jax
import jax.numpy as jnp

from dynamo_tpu.models import layer_body
from dynamo_tpu.ops.attention import (
    causal_prefill_attention,
    chunked_prefill_attention,
    decode_append_attention,
    live_decode_lanes,
    packed_prefill_attention,
    paged_verify_attention,
    write_chunk_kv,
    write_decode_kv,
    write_prefill_kv,
)
from dynamo_tpu.ops.basics import rms_norm, rope_freqs, swiglu
from dynamo_tpu.ops.layers import attn_out, qkv_head
from dynamo_tpu.ops.linear import (
    fused_attn_out_residual,
    fused_qkv_rope,
    linear,
    maybe_quantize,
)


# `config.json` `model_type`s this family's config is built from
# (`models.config_from_model_dir`); one without a `model_type` is taken for it
MODEL_TYPES = (
    "llama", "mistral", "mixtral", "qwen2", "gemma", "gemma2", "gemma3",
    "gemma3_text",
)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    rope_scaling: Optional[dict] = None
    # Qwen2-family: bias on the q/k/v projections (o/mlp stay bias-free)
    attn_bias: bool = False
    # Gemma-family: GeGLU FFN instead of SwiGLU ("gelu_tanh"), embeddings
    # scaled by sqrt(hidden) at lookup, and (1+w) RMSNorm weights — the
    # +1 is folded into the stored weights at load time, so the forward
    # pass stays identical
    mlp_act: str = "silu"
    embed_scale: bool = False
    norm_plus_one: bool = False
    # attention kernel choice for THIS model instance (None -> process
    # default): lets two runners in one process use different impls
    # without stomping the ops-level global (e.g. a TP-meshed engine on
    # the XLA path next to a single-chip engine on the pallas path)
    attn_impl: Optional[str] = None
    # Fused decode step (DYN_FUSED_DECODE): norm+QKV+rope in one pallas
    # program and attn-out+O-proj+residual in another, cutting per-layer
    # decode launches and activation HBM round-trips. Applies to the
    # unsharded decode path of plain/bias models (qk-norm and sandwich
    # norms fall back to the unfused head); bit-identical by construction
    # (ops/linear.py fused kernels mirror the unfused op sequence).
    # Under a mesh (ISSUE 19) the fused programs run per-shard via
    # shard_map over the tp axis (ops/collective.py) whenever the head
    # counts divide tp; qk-norm and sandwich-norm layers still fall back.
    fused_decode: bool = False
    # DYN_COLLECTIVE_OVERLAP: decompose the meshed decode step's two
    # per-layer tp all-reduces into reduce-scatter/all-gather rings
    # pipelined against the o-proj/MLP matmul chunks
    # (ops/collective.fused_tail_overlap). Token-identical to the plain
    # psum path (ring summation reorders f32 adds); inert off-mesh.
    collective_overlap: bool = False
    # Sliding-window attention (Mistral / Gemma2 / Gemma3 local layers):
    # token i attends to (i-window, i]. None = full attention. The paged
    # cache still stores every position (the mask, not a rolling buffer,
    # enforces the window), so prefix-cache hashes stay exact.
    sliding_window: Optional[int] = None
    # Per-layer pattern: tuple[bool] (True = sliding) of len num_layers.
    # None with sliding_window set = every layer slides (Mistral).
    layer_pattern: Optional[tuple] = None
    # Gemma2: logit soft-caps (cap*tanh(x/cap)) on attention scores and
    # final logits; custom attention scale via query_pre_attn_scalar.
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    query_pre_attn_scalar: Optional[float] = None
    # Gemma2/3: sandwich norms — post-attention and post-feedforward
    # RMSNorms applied to each sublayer's OUTPUT before the residual add
    # (the pre-norms are the standard attn_norm/mlp_norm slots).
    sandwich_norms: bool = False
    # Gemma3: per-head RMSNorm on q and k after projection, before RoPE.
    qk_norm: bool = False
    # Gemma3: local (sliding) layers use their own rope theta (10k) with
    # no scaling; global layers use rope_theta (1M) + rope_scaling.
    rope_local_theta: Optional[float] = None
    # MoE (Mixtral-style): num_experts == 0 means dense SwiGLU FFN
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # the all-to-all dispatch's bucket a destination shard (`_mlp`)
    moe_capacity_factor: float = 1.25

    @classmethod
    def from_hf_dict(cls, d: dict[str, Any]) -> "LlamaConfig":
        num_heads = d.get("num_attention_heads", 32)
        hidden = d.get("hidden_size", 4096)
        # Qwen2/Qwen2.5 are llama-shaped with q/k/v bias; HF marks them by
        # model_type (qwen2) / architectures (Qwen2ForCausalLM)
        is_qwen2 = d.get("model_type", "").startswith("qwen2") or any(
            a.startswith("Qwen2") for a in d.get("architectures") or []
        )
        # Gemma (v1): GeGLU + scaled embeddings + (1+w) norms + tied head.
        # Gemma2 adds soft-caps + alternating local/global attention +
        # sandwich norms; Gemma3 swaps soft-caps for qk-norm, runs 5
        # local : 1 global with a separate local rope theta.
        mt = d.get("model_type", "")
        archs = d.get("architectures") or []
        is_gemma2 = mt == "gemma2" or any(a.startswith("Gemma2") for a in archs)
        is_gemma3 = mt in ("gemma3", "gemma3_text") or any(
            a.startswith("Gemma3") for a in archs
        )
        is_gemma = mt == "gemma" or any(a.startswith("GemmaFor") for a in archs)
        gemma_like = is_gemma or is_gemma2 or is_gemma3
        num_layers = d.get("num_hidden_layers", 32)
        # Sliding window (Mistral/Qwen2 full-depth; Gemma2/3 patterned).
        # Qwen2-family configs ship a numeric sliding_window with
        # use_sliding_window=false — window disabled, full attention is
        # exact over the whole declared context (ADVICE r4 #1).
        sliding = d.get("sliding_window")
        if not d.get("use_sliding_window", True):
            sliding = None
        layer_pattern = None
        if d.get("layer_types"):
            # HF's explicit per-layer list ("sliding_attention"/"full_…")
            layer_pattern = tuple(
                t == "sliding_attention" for t in d["layer_types"]
            )
        elif is_gemma2 and sliding:
            layer_pattern = tuple(i % 2 == 0 for i in range(num_layers))
        elif is_gemma3 and sliding:
            pat = d.get("sliding_window_pattern", 6)
            layer_pattern = tuple(
                (i + 1) % pat != 0 for i in range(num_layers)
            )
        if layer_pattern is not None and not any(layer_pattern):
            sliding, layer_pattern = None, None
        return cls(
            attn_bias=is_qwen2,
            mlp_act="gelu_tanh" if gemma_like else "silu",
            embed_scale=gemma_like,
            norm_plus_one=gemma_like,
            vocab_size=d.get("vocab_size", 32000),
            hidden_size=hidden,
            intermediate_size=d.get("intermediate_size", 4 * hidden),
            num_layers=num_layers,
            num_heads=num_heads,
            num_kv_heads=d.get("num_key_value_heads", num_heads),
            head_dim=d.get("head_dim", hidden // num_heads),
            rope_theta=d.get("rope_theta", 10000.0),
            rms_eps=d.get("rms_norm_eps", 1e-5),
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            tie_word_embeddings=d.get("tie_word_embeddings", gemma_like),
            rope_scaling=d.get("rope_scaling"),
            num_experts=d.get("num_local_experts", 0),
            num_experts_per_tok=d.get("num_experts_per_tok", 2),
            sliding_window=sliding,
            layer_pattern=layer_pattern,
            attn_logit_softcap=d.get("attn_logit_softcapping")
            if is_gemma2
            else None,
            final_logit_softcap=d.get("final_logit_softcapping")
            if is_gemma2
            else None,
            query_pre_attn_scalar=d.get("query_pre_attn_scalar")
            if (is_gemma2 or is_gemma3)
            else None,
            sandwich_norms=is_gemma2 or is_gemma3,
            qk_norm=is_gemma3,
            rope_local_theta=d.get("rope_local_base_freq", 10000.0)
            if is_gemma3
            else None,
        )

    def __hash__(self) -> int:
        # a config is a static argument of the layer bodies' `jax.jit`
        # (`models.layer_body`); `rope_scaling` is HF's dict, hashed by its text
        return hash(tuple(
            json.dumps(v, sort_keys=True) if isinstance(v, dict) else v
            for v in (getattr(self, f.name) for f in fields(self))
        ))

    def layer_window(self, i: int) -> Optional[int]:
        """This layer's sliding window, or None for full attention."""
        if self.sliding_window is None:
            return None
        if self.layer_pattern is None:
            return self.sliding_window  # Mistral: every layer slides
        return self.sliding_window if self.layer_pattern[i] else None

    @property
    def attn_scale(self) -> Optional[float]:
        """Custom attention score scale (Gemma2/3), or None for 1/sqrt(D)."""
        if self.query_pre_attn_scalar is None:
            return None
        return self.query_pre_attn_scalar ** -0.5

    @classmethod
    def from_model_dir(cls, model_dir: str) -> "LlamaConfig":
        with open(os.path.join(model_dir, "config.json")) as f:
            return cls.from_hf_dict(json.load(f))

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        """DeepSeek-R1-Distill-Llama-8B / Llama-3.1-8B shapes."""
        return cls(
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=500000.0,
        )

    @classmethod
    def tiny(cls, vocab_size: int = 256) -> "LlamaConfig":
        """CPU-test config (mirrors the reference's mocker: all logic, no scale)."""
        return cls(
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            rope_theta=10000.0,
            max_position_embeddings=512,
        )

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


CONFIG = LlamaConfig  # `models.served_model_types` reads it


# ------------------------------------------------------------------ params


def init_params(
    config: LlamaConfig,
    rng: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    quantize: bool = False,
) -> dict:
    """Random-init parameter pytree (bench/test path; loading is separate)."""
    c = config
    keys = iter(jax.random.split(rng, 4 + 10 * c.num_layers))

    def dense(key, shape, scale_dim):
        w = jax.random.normal(key, shape, dtype=jnp.float32) / jnp.sqrt(scale_dim)
        return maybe_quantize(w.astype(dtype), quantize)

    layers = []
    for _ in range(c.num_layers):
        layer = {
            "attn_norm": jnp.ones((c.hidden_size,), dtype),
            "wq": dense(next(keys), (c.hidden_size, c.q_dim), c.hidden_size),
            "wk": dense(next(keys), (c.hidden_size, c.kv_dim), c.hidden_size),
            "wv": dense(next(keys), (c.hidden_size, c.kv_dim), c.hidden_size),
            "wo": dense(next(keys), (c.q_dim, c.hidden_size), c.q_dim),
            "mlp_norm": jnp.ones((c.hidden_size,), dtype),
        }
        if c.attn_bias:
            layer.update(
                bq=jnp.zeros((c.q_dim,), dtype),
                bk=jnp.zeros((c.kv_dim,), dtype),
                bv=jnp.zeros((c.kv_dim,), dtype),
            )
        if c.sandwich_norms:
            layer.update(
                post_attn_norm=jnp.ones((c.hidden_size,), dtype),
                post_mlp_norm=jnp.ones((c.hidden_size,), dtype),
            )
        if c.qk_norm:
            layer.update(
                q_norm=jnp.ones((c.head_dim,), dtype),
                k_norm=jnp.ones((c.head_dim,), dtype),
            )
        if c.num_experts:
            # Mixtral MoE FFN: router + stacked expert SwiGLU weights
            # (experts kept bf16; expert einsums go through ops/moe.py)
            E, D, F = c.num_experts, c.hidden_size, c.intermediate_size
            def expert(key, shape, scale_dim):
                w = jax.random.normal(key, shape, dtype=jnp.float32)
                return (w / jnp.sqrt(scale_dim)).astype(dtype)
            layer.update(
                router=expert(next(keys), (D, E), D),
                wg=expert(next(keys), (E, D, F), D),
                wu=expert(next(keys), (E, D, F), D),
                wd=expert(next(keys), (E, F, D), F),
            )
        else:
            layer.update(
                wg=dense(next(keys), (c.hidden_size, c.intermediate_size), c.hidden_size),
                wu=dense(next(keys), (c.hidden_size, c.intermediate_size), c.hidden_size),
                wd=dense(next(keys), (c.intermediate_size, c.hidden_size), c.intermediate_size),
            )
        layers.append(layer)
    params = {
        "embed": (
            jax.random.normal(next(keys), (c.vocab_size, c.hidden_size), jnp.float32)
            * 0.02
        ).astype(dtype),
        "layers": layers,
        "final_norm": jnp.ones((c.hidden_size,), dtype),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = dense(
            next(keys), (c.hidden_size, c.vocab_size), c.hidden_size
        )
    return params


def param_count(config: LlamaConfig) -> int:
    c = config
    ffn = 3 * c.hidden_size * c.intermediate_size
    if c.num_experts:
        # MoE: E expert FFNs + the router table
        ffn = c.num_experts * ffn + c.hidden_size * c.num_experts
    per_layer = (
        c.hidden_size * (c.q_dim + 2 * c.kv_dim)
        + c.q_dim * c.hidden_size
        + ffn
        + 2 * c.hidden_size
        + ((c.q_dim + 2 * c.kv_dim) if c.attn_bias else 0)
    )
    total = c.num_layers * per_layer + 2 * c.vocab_size * c.hidden_size
    return total


def expert_param_count(config: LlamaConfig) -> int:
    """Parameters in routed expert stacks (kept bf16 whatever the dense
    projections' precision): 0 for a dense model."""
    c = config
    return c.num_layers * c.num_experts * 3 * c.hidden_size * c.intermediate_size


# ----------------------------------------------------------------- forward


def _embed(params, cfg, tokens):
    """Token embedding lookup; Gemma scales by sqrt(hidden) here."""
    x = params["embed"][tokens].astype(params["embed"].dtype)
    if cfg.embed_scale:
        x = x * jnp.sqrt(jnp.float32(cfg.hidden_size)).astype(x.dtype)
    return x


def _rope_pair(cfg):
    """(global_freqs, local_freqs): Gemma3 runs its sliding layers on a
    separate unscaled theta; everyone else shares one table."""
    g = rope_freqs(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
    if cfg.rope_local_theta is None:
        return g, g
    return g, rope_freqs(cfg.head_dim, cfg.rope_local_theta, None)


def _layer_freqs(cfg, li, pair):
    """This layer's rope table: local freqs on sliding layers (Gemma3)."""
    return pair[1] if cfg.layer_window(li) is not None else pair[0]


# the shared projection head / output projection live in ops/layers.py so
# the pipeline-parallel stage scan uses the SAME definition (a hand-copied
# head is how qwen2 biases once went missing from pp)
_qkv = qkv_head
_attn_out = attn_out


def _attn_prefill(x, layer, cfg, inv_freqs, positions, valid_len, k_cache_l, v_cache_l, block_table, mesh=None, head_axis=None, window=None):
    q, k, v = _qkv(x, layer, cfg, inv_freqs, positions)
    k_cache_l, v_cache_l = write_prefill_kv(k_cache_l, v_cache_l, k, v, block_table)
    attn = causal_prefill_attention(
        q, k, v, valid_len, impl=cfg.attn_impl, mesh=mesh, head_axis=head_axis,
        window=window, scale=cfg.attn_scale,
        logit_softcap=cfg.attn_logit_softcap,
    )
    return _attn_out(attn, x, layer, cfg), k_cache_l, v_cache_l


def _use_fused_decode(cfg, layer, mesh) -> bool:
    """Fused decode applies when enabled and for layers the fused heads
    cover exactly (no per-head qk-norm, no sandwich post-attention norm).
    Independent of the attention kernel choice — the fused projections
    are their own pallas programs. Under a mesh (ISSUE 19) the fused
    programs run per-shard via shard_map over the tp axis whenever the
    Megatron head split divides evenly; a mesh without a tp axis (or with
    indivisible heads) falls back unfused."""
    if (
        not cfg.fused_decode
        or "q_norm" in layer
        or "post_attn_norm" in layer
    ):
        return False
    if mesh is None:
        return True
    tp = mesh.shape.get("tp", 0)
    return bool(
        tp and cfg.num_heads % tp == 0 and cfg.num_kv_heads % tp == 0
    )


def _use_overlap_tail(cfg, layer, mesh) -> bool:
    """The decomposed collective-matmul tail replaces BOTH the fused
    o-proj and the dense MLP, so it needs a real tp axis, a plain dense
    FFN (no MoE router, no Gemma post-MLP sandwich norm), and evenly
    divisible feature dims for the ring chunks."""
    if not (
        cfg.collective_overlap
        and mesh is not None
        and _use_fused_decode(cfg, layer, mesh)
        and "router" not in layer
        and "post_mlp_norm" not in layer
    ):
        return False
    tp = mesh.shape.get("tp", 0)
    return bool(
        tp > 1
        and cfg.hidden_size % tp == 0
        and cfg.intermediate_size % tp == 0
    )


def _fused_interpret() -> bool:
    """Interpret the fused kernels off-TPU (CPU tests/benches); never on
    a TPU."""
    return jax.default_backend() != "tpu"


def _fused_qkv_dispatch(x, layer, cfg, inv_freqs, positions, mesh):
    """The fused norm+QKV+RoPE program, shard_map'd over tp under a mesh
    (ops/collective.py) and direct otherwise. cos/sin are computed
    exactly as apply_rope's angle formula; the rotation itself runs
    inside the fused program."""
    interp = _fused_interpret()
    angles = positions[..., None].astype(jnp.float32) * inv_freqs
    kwargs = dict(
        eps=cfg.rms_eps,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        bq=layer.get("bq"), bk=layer.get("bk"), bv=layer.get("bv"),
        interpret=interp,
    )
    if mesh is not None:
        from dynamo_tpu.ops.collective import fused_qkv_rope_meshed

        return fused_qkv_rope_meshed(
            mesh, x, layer["attn_norm"],
            layer["wq"], layer["wk"], layer["wv"],
            jnp.cos(angles), jnp.sin(angles), **kwargs,
        )
    return fused_qkv_rope(
        x, layer["attn_norm"], layer["wq"], layer["wk"], layer["wv"],
        jnp.cos(angles), jnp.sin(angles), **kwargs,
    )


def _fused_out_dispatch(attn_flat, layer, cfg, x, mesh):
    """The fused o-proj+residual program, meshed (f32 psum before the
    scale/cast/residual) or direct."""
    if mesh is not None:
        from dynamo_tpu.ops.collective import fused_attn_out_residual_meshed

        return fused_attn_out_residual_meshed(
            mesh, attn_flat, layer["wo"], x,
            interpret=_fused_interpret(),
        )
    return fused_attn_out_residual(
        attn_flat, layer["wo"], x, interpret=_fused_interpret()
    )


def _attn_decode(x, layer, cfg, inv_freqs, positions, k_cache_l, v_cache_l, block_tables, slot_indices, mesh=None, head_axis=None, window=None, overlap_tail=False):
    """One layer's decode attention. With ``overlap_tail`` (gated by
    `_use_overlap_tail`) the layer's whole post-attention tail — o-proj,
    residual, MLP — runs as the decomposed collective-matmul program and
    the returned x is already post-MLP (the caller skips `_mlp`)."""
    fused = _use_fused_decode(cfg, layer, mesh)
    if fused:
        q, k, v = _fused_qkv_dispatch(x, layer, cfg, inv_freqs, positions, mesh)
    else:
        q, k, v = _qkv(x, layer, cfg, inv_freqs, positions)
    live = live_decode_lanes(k_cache_l, slot_indices)
    attn, k_cache_l, v_cache_l = decode_append_attention(
        q, k_cache_l, v_cache_l, k, v, slot_indices, block_tables,
        jnp.where(live, positions + 1, 0),
        impl=cfg.attn_impl, mesh=mesh, head_axis=head_axis,
        window=window, scale=cfg.attn_scale,
        logit_softcap=cfg.attn_logit_softcap,
    )
    if fused:
        attn_flat = attn.reshape(x.shape[0], cfg.q_dim)
        if overlap_tail:
            from dynamo_tpu.ops.collective import fused_tail_overlap

            out = fused_tail_overlap(
                mesh, attn_flat, layer["wo"], x, layer["mlp_norm"],
                layer["wg"], layer["wu"], layer["wd"],
                eps=cfg.rms_eps, mlp_act=cfg.mlp_act,
                interpret=_fused_interpret(),
            )
        else:
            out = _fused_out_dispatch(attn_flat, layer, cfg, x, mesh)
        return out, k_cache_l, v_cache_l
    return _attn_out(attn, x, layer, cfg), k_cache_l, v_cache_l


def _mlp(x, layer, cfg, mesh=None):
    h = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
    if "router" in layer:
        from dynamo_tpu.ops.moe import (
            moe_ffn_dropless,
            moe_ffn_ep_a2a,
            moe_ffn_shard_map,
        )

        T = x.shape[0]
        args = (
            h, layer["router"], layer["wg"], layer["wu"], layer["wd"],
        )
        k = cfg.num_experts_per_tok
        if mesh is not None and mesh.shape.get("ep", 1) > 1:
            ep = mesh.shape["ep"]
            tp_axis = "tp" if mesh.shape.get("tp", 1) > 1 else None
            if T % ep == 0 and T >= 4 * ep:
                # prefill-size batches: token-sharded all-to-all dispatch
                y = moe_ffn_ep_a2a(
                    mesh, *args, top_k=k,
                    capacity_factor=cfg.moe_capacity_factor,
                    tp_axis=tp_axis,
                )
            else:
                # decode-size batches: replicated-token psum (dropless)
                y = moe_ffn_shard_map(mesh, *args, top_k=k)
        else:
            # single chip / pure-TP mesh: dropless grouped-GEMM (exact
            # serving semantics); GSPMD shards the FFN feature dim over tp
            y = moe_ffn_dropless(*args, top_k=k)
        return x + y
    gate = linear(h, layer["wg"])
    up = linear(h, layer["wu"])
    if cfg.mlp_act == "gelu_tanh":  # Gemma GeGLU
        act = jax.nn.gelu(gate.astype(jnp.float32), approximate=True).astype(
            gate.dtype
        ) * up
    else:
        act = swiglu(gate, up)
    y = linear(act, layer["wd"])
    if "post_mlp_norm" in layer:  # Gemma2/3 sandwich norm
        y = rms_norm(y, layer["post_mlp_norm"], cfg.rms_eps)
    return x + y


def _logits(x, params, cfg):
    h = rms_norm(x, params["final_norm"], cfg.rms_eps)
    w = params.get("lm_head")
    if w is None:
        out = jnp.matmul(h, params["embed"].T.astype(h.dtype)).astype(jnp.float32)
    else:
        out = linear(h, w).astype(jnp.float32)
    if cfg.final_logit_softcap is not None:  # Gemma2
        cap = cfg.final_logit_softcap
        out = cap * jnp.tanh(out / cap)
    return out


# One body for each kind of pass over the layers, called once a layer by the
# forwards below (`models.layer_body`: traced and lowered once a program for
# each distinct layer, by what the code observes of it: the window is a
# static argument, the rope table an array, the layer's keys the argument's
# tree; never its index). Each layer's own cache buffers go in and come out.


@layer_body("cfg", "mesh", "head_axis", "window")
def _prefill_layer(x, layer, inv_freqs, positions, valid_len, k_cache_l, v_cache_l, block_table, *, cfg, mesh, head_axis, window):
    x, kc, vc = _attn_prefill(
        x, layer, cfg, inv_freqs, positions, valid_len, k_cache_l, v_cache_l,
        block_table, mesh=mesh, head_axis=head_axis, window=window,
    )
    return _mlp(x, layer, cfg, mesh), kc, vc


@layer_body("cfg", "mesh", "window")
def _chunk_layer(x, layer, inv_freqs, positions, k_cache_l, v_cache_l, block_table, chunk_start, *, cfg, mesh, window):
    q, k, v = _qkv(x, layer, cfg, inv_freqs, positions)
    kc, vc = write_chunk_kv(k_cache_l, v_cache_l, k, v, block_table, chunk_start)
    attn = chunked_prefill_attention(
        q, kc, vc, block_table, chunk_start,
        window=window, scale=cfg.attn_scale,
        logit_softcap=cfg.attn_logit_softcap,
    )
    x = _attn_out(attn, x, layer, cfg)
    return _mlp(x, layer, cfg, mesh), kc, vc


@layer_body("cfg", "mesh", "window")
def _packed_layer(x, layer, inv_freqs, positions, segment_ids, slot_indices, k_cache_l, v_cache_l, *, cfg, mesh, window):
    q, k, v = _qkv(x, layer, cfg, inv_freqs, positions)
    kc, vc = write_decode_kv(k_cache_l, v_cache_l, k, v, slot_indices)
    attn = packed_prefill_attention(
        q, k, v, segment_ids,
        window=window, scale=cfg.attn_scale,
        logit_softcap=cfg.attn_logit_softcap,
    )
    x = _attn_out(attn, x, layer, cfg)
    return _mlp(x, layer, cfg, mesh), kc, vc


@layer_body("cfg", "mesh", "head_axis", "window")
def _ring_layer(x, layer, inv_freqs, positions, valid_len, cache, *, cfg, mesh, head_axis, window):
    """`cache` is (this layer's K buffer, its V buffer, the block table) or
    None; returns x and the layer's keys and values, in the buffers where
    given and as computed otherwise."""
    from dynamo_tpu.parallel.ring_attention import ring_prefill_attention

    q, k, v = _qkv(x, layer, cfg, inv_freqs, positions)
    # sliding layers ride the same ring; hops whose KV chunk is wholly
    # outside [i-window, i] skip their flash update (window masking is
    # exact inside ring_attention_body), so Mistral/Gemma2/3 long
    # prefills context-parallelize like everyone else
    attn = ring_prefill_attention(
        mesh, q, k, v, valid_len, head_axis=head_axis,
        window=window, scale=cfg.attn_scale,
        logit_softcap=cfg.attn_logit_softcap,
    )
    x = _attn_out(attn, x, layer, cfg)
    x = _mlp(x, layer, cfg, mesh)
    if cache is not None:
        k, v = write_prefill_kv(cache[0], cache[1], k, v, cache[2])
    return x, k, v


@layer_body("cfg", "window")
def _pooled_layer(x, layer, inv_freqs, positions, valid_len, *, cfg, window):
    q, k, v = _qkv(x, layer, cfg, inv_freqs, positions)
    attn = causal_prefill_attention(
        q, k, v, valid_len, impl=cfg.attn_impl,
        window=window, scale=cfg.attn_scale,
        logit_softcap=cfg.attn_logit_softcap,
    )
    x = _attn_out(attn, x, layer, cfg)
    return _mlp(x, layer, cfg)


@layer_body("cfg", "mesh", "head_axis", "window")
def _verify_layer(x, layer, inv_freqs, positions, k_cache_l, v_cache_l, block_tables, slot_indices, *, cfg, mesh, head_axis, window):
    """`positions` and `slot_indices` are [B, S]; x is the flat [B*S] rows."""
    B, S = positions.shape
    pos_flat = positions.reshape(-1)
    fused = _use_fused_decode(cfg, layer, mesh)
    if fused:
        # the fused kernels are row-count generic: the verify window's
        # flat [B*S] rows ride the same norm+QKV+RoPE program decode
        # uses (meshed via shard_map under a mesh)
        q, k, v = _fused_qkv_dispatch(x, layer, cfg, inv_freqs, pos_flat, mesh)
    else:
        q, k, v = _qkv(x, layer, cfg, inv_freqs, pos_flat)
    kc, vc = write_decode_kv(k_cache_l, v_cache_l, k, v, slot_indices.reshape(-1))
    attn = paged_verify_attention(
        q.reshape(B, S, cfg.num_heads, cfg.head_dim), kc, vc,
        block_tables, positions,
        window=window, scale=cfg.attn_scale,
        logit_softcap=cfg.attn_logit_softcap,
        impl=cfg.attn_impl, mesh=mesh, head_axis=head_axis,
    )
    if fused:
        x = _fused_out_dispatch(
            attn.reshape(B * S, cfg.q_dim), layer, cfg, x, mesh
        )
    else:
        x = _attn_out(attn.reshape(B * S, cfg.num_heads, cfg.head_dim), x, layer, cfg)
    return _mlp(x, layer, cfg, mesh), kc, vc


@layer_body("cfg", "mesh", "head_axis", "window")
def _decode_layer(x, layer, inv_freqs, positions, k_cache_l, v_cache_l, block_tables, slot_indices, *, cfg, mesh, head_axis, window):
    overlap = _use_overlap_tail(cfg, layer, mesh)
    x, kc, vc = _attn_decode(
        x, layer, cfg, inv_freqs, positions, k_cache_l, v_cache_l,
        block_tables, slot_indices,
        mesh=mesh, head_axis=head_axis, window=window, overlap_tail=overlap,
    )
    if not overlap:  # the overlap tail already ran the MLP
        x = _mlp(x, layer, cfg, mesh)
    return x, kc, vc


def prefill(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [P] int32, padded to a multiple of block_size
    valid_len: jax.Array,  # scalar int32
    k_cache: tuple,  # per layer [Hkv, num_blocks, block_size, D]
    v_cache: tuple,
    block_table: jax.Array,  # [P // block_size] int32
    *,
    mesh=None,  # with attn_head_axis: run pallas attention under shard_map
    attn_head_axis=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Process a prompt; returns (last_token_logits [V], k_cache, v_cache)."""
    x = _embed(params, cfg, tokens)
    return _prefill_from_embeds(
        params, cfg, x, valid_len, k_cache, v_cache, block_table,
        mesh=mesh, attn_head_axis=attn_head_axis,
    )


def prefill_mm(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [P] int32, image placeholders pre-expanded
    valid_len: jax.Array,
    k_cache: tuple,
    v_cache: tuple,
    block_table: jax.Array,
    mm_embeds: jax.Array,  # [M, hidden] vision-projector output
    mm_start: jax.Array,  # scalar int32; embeds overwrite [start, start+M)
    *,
    mesh=None,
    attn_head_axis=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Multimodal prefill: token embeddings with the vision tower's patch
    embeddings spliced over the expanded image-placeholder span — the
    splice the reference does in vLLM's prompt_embeds path
    (examples/multimodal/components/prefill_worker.py:249-258). One static
    [M, hidden] dynamic-update-slice keeps this a single compiled program
    regardless of where the image sits in the prompt."""
    x = _embed(params, cfg, tokens)
    x = jax.lax.dynamic_update_slice(
        x, mm_embeds.astype(x.dtype), (mm_start, jnp.int32(0))
    )
    return _prefill_from_embeds(
        params, cfg, x, valid_len, k_cache, v_cache, block_table,
        mesh=mesh, attn_head_axis=attn_head_axis,
    )


def _prefill_from_embeds(
    params: dict,
    cfg: LlamaConfig,
    x: jax.Array,  # [P, hidden]
    valid_len: jax.Array,
    k_cache: tuple,
    v_cache: tuple,
    block_table: jax.Array,
    *,
    mesh=None,
    attn_head_axis=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    freqs = _rope_pair(cfg)
    positions = jnp.arange(x.shape[0], dtype=jnp.int32)
    k_out, v_out = [], []  # each layer's own buffer, written in place
    for i, layer in enumerate(params["layers"]):
        x, kc, vc = _prefill_layer(
            x, layer, _layer_freqs(cfg, i, freqs), positions, valid_len,
            k_cache[i], v_cache[i], block_table,
            cfg=cfg, mesh=mesh, head_axis=attn_head_axis,
            window=cfg.layer_window(i),
        )
        k_out.append(kc)
        v_out.append(vc)
    logits = _logits(x[valid_len - 1][None, :], params, cfg)[0]
    return logits, tuple(k_out), tuple(v_out)


def prefill_chunk(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [C] int32 — one chunk (C = fixed chunk size)
    chunk_start: jax.Array,  # scalar int32 — position of tokens[0]
    valid_len: jax.Array,  # scalar int32 — TOTAL prompt length
    k_cache: tuple,  # per layer [Hkv, num_blocks, block_size, D]
    v_cache: tuple,
    block_table: jax.Array,  # [max_nb] int32 — the whole prompt's blocks
    *,
    mesh=None,  # for MoE dispatch-path selection in _mlp
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One chunk of a chunked prefill (vLLM-style; the reference's engines
    chunk prefill and its mocker models it — mocker/scheduler.rs:28-43).

    Chunks are processed in order; each writes its K/V into the paged cache
    then attends over everything written so far. ONE compiled program
    serves every chunk of every prompt (C and the table width are static;
    chunk_start/valid_len are dynamic scalars). Returns (last-valid-token
    logits [V], caches) — logits are meaningful only on the final chunk.
    """
    C = tokens.shape[0]
    freqs = _rope_pair(cfg)
    positions = chunk_start + jnp.arange(C, dtype=jnp.int32)
    x = _embed(params, cfg, tokens)
    k_out, v_out = [], []
    for i, layer in enumerate(params["layers"]):
        x, kc, vc = _chunk_layer(
            x, layer, _layer_freqs(cfg, i, freqs), positions,
            k_cache[i], v_cache[i], block_table, chunk_start,
            cfg=cfg, mesh=mesh, window=cfg.layer_window(i),
        )
        k_out.append(kc)
        v_out.append(vc)
    idx = jnp.clip(valid_len - 1 - chunk_start, 0, C - 1)
    logits = _logits(x[idx][None, :], params, cfg)[0]
    return logits, tuple(k_out), tuple(v_out)


def prefill_packed(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [P] int32 — several prompts packed back-to-back
    positions: jax.Array,  # [P] int32 — restart at 0 per segment
    segment_ids: jax.Array,  # [P] int32; -1 marks padding lanes
    slot_indices: jax.Array,  # [P] int32 flat cache slots per token
    k_cache: tuple,  # per layer [Hkv, num_blocks, block_size, D]
    v_cache: tuple,
    last_idx: jax.Array,  # [N] int32 — index of each prompt's last token
    *,
    mesh=None,  # for MoE dispatch-path selection in _mlp
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched prefill: N short prompts packed into ONE [P] program.

    The engine admits waiting prompts up to a token budget per iteration
    and prefills them together (the reference's engines batch prefill
    tokens across requests — vLLM behavior its mocker models,
    mocker/scheduler.rs:28-43). Per-token flat slots route each segment's
    K/V into its own blocks (write_decode_kv generalizes to P tokens);
    attention is causal-within-segment. Returns (per-segment last-token
    logits [N, V], caches). Unused last_idx lanes read token 0 — callers
    ignore those rows.
    """
    freqs = _rope_pair(cfg)
    x = _embed(params, cfg, tokens)
    k_out, v_out = [], []
    for i, layer in enumerate(params["layers"]):
        x, kc, vc = _packed_layer(
            x, layer, _layer_freqs(cfg, i, freqs), positions, segment_ids,
            slot_indices, k_cache[i], v_cache[i],
            cfg=cfg, mesh=mesh, window=cfg.layer_window(i),
        )
        k_out.append(kc)
        v_out.append(vc)
    logits = _logits(x[last_idx], params, cfg)
    return logits, tuple(k_out), tuple(v_out)


def prefill_context_parallel(
    params: dict,
    cfg: LlamaConfig,
    mesh,  # jax.sharding.Mesh with an "sp" axis (optionally "tp")
    tokens: jax.Array,  # [P] int32, P divisible by sp size (pad with 0s)
    valid_len: jax.Array,  # scalar int32
    *,
    head_axis=None,  # "tp" when kv heads are TP-sharded
    k_cache=None,  # per layer [Hkv, nb, bs, D] — paginate when given
    v_cache=None,
    block_table=None,  # [P // bs] int32
):
    """Long-context prefill with the sequence sharded over the `sp` mesh
    axis (ring attention, parallel/ring_attention.py). The reference has no
    sequence parallelism (SURVEY.md §2.7) — long prefills there are just
    routed to dedicated engines; here one prefill worker spans a slice.

    With a cache: each layer's K/V scatters into the (donated) paged cache
    inside the layer loop — peak extra memory is ONE layer's [P, Hkv, D],
    not all L of them (the long-context regime is exactly where an
    [L, P, Hkv, D] stack would blow HBM). Returns (logits [V], k_cache,
    v_cache). Without a cache: returns (logits, k_new [L, P, Hkv, D],
    v_new) for shipping to a decode worker (disagg).
    """
    paginate = k_cache is not None
    P_len = tokens.shape[0]
    freqs = _rope_pair(cfg)
    positions = jnp.arange(P_len, dtype=jnp.int32)
    x = _embed(params, cfg, tokens)
    k_all, v_all = [], []
    k_out, v_out = [], []
    for i, layer in enumerate(params["layers"]):
        x, k, v = _ring_layer(
            x, layer, _layer_freqs(cfg, i, freqs), positions, valid_len,
            (k_cache[i], v_cache[i], block_table) if paginate else None,
            cfg=cfg, mesh=mesh, head_axis=head_axis,
            window=cfg.layer_window(i),
        )
        (k_out if paginate else k_all).append(k)
        (v_out if paginate else v_all).append(v)
    logits = _logits(x[valid_len - 1][None, :], params, cfg)[0]
    if paginate:
        return logits, tuple(k_out), tuple(v_out)
    return logits, jnp.stack(k_all), jnp.stack(v_all)


def embed_pooled(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [P] int32, padded
    valid_len: jax.Array,  # scalar int32
) -> jax.Array:
    """Pooled sequence embedding: full forward pass (no cache), final-norm
    hidden states mean-pooled over valid tokens. The /v1/embeddings path
    (ref http/service/openai.rs:222) — cacheless because embedding traffic
    never decodes."""
    freqs = _rope_pair(cfg)
    P = tokens.shape[0]
    positions = jnp.arange(P, dtype=jnp.int32)
    x = _embed(params, cfg, tokens)
    for i, layer in enumerate(params["layers"]):
        x = _pooled_layer(
            x, layer, _layer_freqs(cfg, i, freqs), positions, valid_len,
            cfg=cfg, window=cfg.layer_window(i),
        )
    h = rms_norm(x, params["final_norm"], cfg.rms_eps).astype(jnp.float32)
    mask = (positions < valid_len)[:, None].astype(jnp.float32)
    return (h * mask).sum(axis=0) / jnp.maximum(valid_len, 1)


def decode_verify(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S] int32 — last accepted token + draft window
    positions: jax.Array,  # [B, S] int32 true positions
    k_cache: tuple,  # per layer [Hkv, num_blocks, block_size, D]
    v_cache: tuple,
    block_tables: jax.Array,  # [B, max_blocks] int32
    slot_indices: jax.Array,  # [B, S] int32 flat cache slots (0 = null sink)
    *,
    mesh=None,  # for MoE dispatch-path selection in _mlp
    attn_head_axis=None,  # with mesh: shard_map the pallas verify kernel
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Draft-verify forward for speculative decoding: ONE weight pass
    scores S positions per sequence (vs S chained decode steps, each a
    full weight read — on a weight-bandwidth-bound chip that is the whole
    point of drafting). Each lane's S tokens write K/V into their real
    slots first, then attend causally over the lane's paged context
    (draft tokens see each other through the cache, like chunked prefill).
    Returns (logits [B, S, V], caches)."""
    freqs = _rope_pair(cfg)
    B, S = tokens.shape
    x = _embed(params, cfg, tokens.reshape(-1))  # [B*S, hidden]
    k_out, v_out = [], []
    for i, layer in enumerate(params["layers"]):
        x, kc, vc = _verify_layer(
            x, layer, _layer_freqs(cfg, i, freqs), positions,
            k_cache[i], v_cache[i], block_tables, slot_indices,
            cfg=cfg, mesh=mesh, head_axis=attn_head_axis,
            window=cfg.layer_window(i),
        )
        k_out.append(kc)
        v_out.append(vc)
    logits = _logits(x, params, cfg).reshape(B, S, -1)
    return logits, tuple(k_out), tuple(v_out)


def decode(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B] int32
    positions: jax.Array,  # [B] int32 (0-indexed position of this token)
    k_cache: tuple,  # per layer [Hkv, num_blocks, block_size, D]
    v_cache: tuple,
    block_tables: jax.Array,  # [B, max_blocks] int32
    slot_indices: jax.Array,  # [B] int32 flat cache slots for the new token
    *,
    mesh=None,  # with attn_head_axis: run pallas attention under shard_map
    attn_head_axis=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for a batch; returns (logits [B, V], caches)."""
    freqs = _rope_pair(cfg)
    x = _embed(params, cfg, tokens)
    k_out, v_out = [], []
    for i, layer in enumerate(params["layers"]):
        x, kc, vc = _decode_layer(
            x, layer, _layer_freqs(cfg, i, freqs), positions,
            k_cache[i], v_cache[i], block_tables, slot_indices,
            cfg=cfg, mesh=mesh, head_axis=attn_head_axis,
            window=cfg.layer_window(i),
        )
        k_out.append(kc)
        v_out.append(vc)
    return _logits(x, params, cfg), tuple(k_out), tuple(v_out)

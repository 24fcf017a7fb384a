"""Weight loading: HF safetensors -> our param pytree, or random init.

Role-equivalent of the weight-loading half of the reference's delegated
engines (and of LocalModel resolution, lib/llm/src/local_model.rs): given an
HF snapshot dir, map `model.layers.N.*` tensors into the functional param
tree, with optional int8 weight-only quantization applied at load.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp

from dynamo_tpu.models import forward_for, served_model_types
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.ops.linear import maybe_quantize
from dynamo_tpu.runtime.logging import get_logger

logger = get_logger("dynamo_tpu.engine.weights")


def load_or_init_params(
    model_dir: Optional[str],
    config: LlamaConfig,
    *,
    quantize: bool = False,
    dtype: jnp.dtype = jnp.bfloat16,
    seed: int = 0,
) -> Any:
    if model_dir:
        files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
        if files:
            load = LOADERS[type(config)]
            return load(model_dir, config, quantize=quantize, dtype=dtype)
        logger.warning(
            "%s has no *.safetensors; falling back to random init", model_dir
        )
    return forward_for(config).init_params(
        config, jax.random.PRNGKey(seed), dtype, quantize
    )


def _read_safetensors(model_dir: str) -> dict[str, Any]:
    from safetensors import safe_open

    tensors: dict[str, Any] = {}
    for path in sorted(glob.glob(os.path.join(model_dir, "*.safetensors"))):
        with safe_open(path, framework="flax") as f:
            for name in f.keys():
                tensors[name] = f.get_tensor(name)
    return tensors


def load_latent_moe_safetensors(
    model_dir: str,
    config: Any,  # models.mla_moe.MlaMoeConfig
    *,
    quantize: bool = False,
    dtype: jnp.dtype = jnp.bfloat16,
) -> Any:
    """The latent-attention, sparse-expert family's checkpoint names
    (DeepSeek-V3's): `self_attn.{q_a_proj, q_a_layernorm, q_b_proj,
    kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj}`, `mlp.gate.weight`
    (the router), `mlp.gate.e_score_correction_bias`,
    `mlp.experts.N.{gate,up,down}_proj`, `mlp.shared_experts.*`; a dense
    layer's `mlp.{gate,up,down}_proj`. Layers behind `num_hidden_layers`
    (the multi-token-prediction module) are left in the files, in words."""
    forward_for(config).refuse_int8_weights(quantize)
    tensors = _read_safetensors(model_dir)
    c = config

    def get(name: str) -> jax.Array:
        return jnp.asarray(tensors.pop(name)).astype(dtype)

    def lin(name: str) -> jax.Array:  # HF stores [out, in]; we use [in, out]
        return get(name).T

    layers = []
    for i in range(c.num_layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        layer = {
            "attn_norm": get(p + "input_layernorm.weight"),
            "wq_a": lin(a + "q_a_proj.weight"),
            "q_norm": get(a + "q_a_layernorm.weight"),
            "wq_b": lin(a + "q_b_proj.weight"),
            "wkv_a": lin(a + "kv_a_proj_with_mqa.weight"),
            "kv_norm": get(a + "kv_a_layernorm.weight"),
            "wkv_b": lin(a + "kv_b_proj.weight"),
            "wo": lin(a + "o_proj.weight"),
            "mlp_norm": get(p + "post_attention_layernorm.weight"),
        }
        m = p + "mlp."
        if c.is_moe_layer(i):
            experts = range(c.n_routed_experts)
            layer.update(
                router=lin(m + "gate.weight"),
                router_bias=jnp.asarray(
                    tensors.pop(m + "gate.e_score_correction_bias")
                ).astype(jnp.float32),
                wg=jnp.stack([lin(f"{m}experts.{e}.gate_proj.weight") for e in experts]),
                wu=jnp.stack([lin(f"{m}experts.{e}.up_proj.weight") for e in experts]),
                wd=jnp.stack([lin(f"{m}experts.{e}.down_proj.weight") for e in experts]),
            )
            if c.n_shared_experts:
                layer.update(
                    sg=lin(m + "shared_experts.gate_proj.weight"),
                    su=lin(m + "shared_experts.up_proj.weight"),
                    sd=lin(m + "shared_experts.down_proj.weight"),
                )
        else:
            layer.update(
                wg=lin(m + "gate_proj.weight"),
                wu=lin(m + "up_proj.weight"),
                wd=lin(m + "down_proj.weight"),
            )
        layers.append(layer)
    params: dict[str, Any] = {
        "embed": get("model.embed_tokens.weight"),
        "layers": layers,
        "final_norm": get("model.norm.weight"),
    }
    if not c.tie_word_embeddings and "lm_head.weight" in tensors:
        params["lm_head"] = lin("lm_head.weight")
    behind = sorted(
        n for n in tensors
        if n.startswith("model.layers.")
        and int(n.split(".")[2]) >= c.num_layers
    )
    if behind:
        logger.info(
            "ignored %d tensors of layers behind the %d served (%s ...): the "
            "multi-token-prediction module takes no part in the next-token "
            "logits and is not served", len(behind), c.num_layers, behind[0],
        )
    logger.info(
        "loaded latent-attention checkpoint from %s (%d tensors unused)",
        model_dir, len(tensors),
    )
    return params


def load_hf_safetensors(
    model_dir: str,
    config: LlamaConfig,
    *,
    quantize: bool = False,
    dtype: jnp.dtype = jnp.bfloat16,
) -> Any:
    tensors = _read_safetensors(model_dir)

    def get(name: str) -> jax.Array:
        t = tensors.pop(name)
        return jnp.asarray(t).astype(dtype)

    def norm(name: str) -> jax.Array:
        # Gemma stores RMSNorm weights as w with output (1+w)*x̂ — fold the
        # +1 here so the forward pass stays family-agnostic
        w = get(name)
        return w + 1 if config.norm_plus_one else w

    def lin(name: str) -> Any:
        # HF stores [out, in]; we use [in, out]
        return maybe_quantize(get(name).T, quantize)

    layers = []
    for i in range(config.num_layers):
        p = f"model.layers.{i}."
        layer = {
            "attn_norm": norm(p + "input_layernorm.weight"),
            "wq": lin(p + "self_attn.q_proj.weight"),
            "wk": lin(p + "self_attn.k_proj.weight"),
            "wv": lin(p + "self_attn.v_proj.weight"),
            "wo": lin(p + "self_attn.o_proj.weight"),
        }
        if config.sandwich_norms:
            # Gemma2/3: HF's post_attention_layernorm is the sandwich
            # post-ATTENTION norm (not the pre-MLP norm it names in
            # llama-family checkpoints); the pre-MLP norm is
            # pre_feedforward_layernorm
            layer.update(
                post_attn_norm=norm(p + "post_attention_layernorm.weight"),
                mlp_norm=norm(p + "pre_feedforward_layernorm.weight"),
                post_mlp_norm=norm(p + "post_feedforward_layernorm.weight"),
            )
        else:
            layer["mlp_norm"] = norm(p + "post_attention_layernorm.weight")
        if config.qk_norm:
            layer.update(
                q_norm=norm(p + "self_attn.q_norm.weight"),
                k_norm=norm(p + "self_attn.k_norm.weight"),
            )
        if config.attn_bias:
            layer.update(
                bq=get(p + "self_attn.q_proj.bias"),
                bk=get(p + "self_attn.k_proj.bias"),
                bv=get(p + "self_attn.v_proj.bias"),
            )
        if config.num_experts:
            # Mixtral block_sparse_moe: gate = router; per-expert
            # w1 = gate proj, w3 = up proj, w2 = down proj. Experts stay
            # unquantized bf16 stacks [E, D, F] / [E, F, D].
            m = p + "block_sparse_moe."
            layer["router"] = get(m + "gate.weight").T
            layer["wg"] = jnp.stack(
                [get(f"{m}experts.{e}.w1.weight").T
                 for e in range(config.num_experts)]
            )
            layer["wu"] = jnp.stack(
                [get(f"{m}experts.{e}.w3.weight").T
                 for e in range(config.num_experts)]
            )
            layer["wd"] = jnp.stack(
                [get(f"{m}experts.{e}.w2.weight").T
                 for e in range(config.num_experts)]
            )
        else:
            layer.update(
                wg=lin(p + "mlp.gate_proj.weight"),
                wu=lin(p + "mlp.up_proj.weight"),
                wd=lin(p + "mlp.down_proj.weight"),
            )
        layers.append(layer)
    params: dict[str, Any] = {
        "embed": get("model.embed_tokens.weight"),
        "layers": layers,
        "final_norm": norm("model.norm.weight"),
    }
    if not config.tie_word_embeddings:
        if "lm_head.weight" in tensors:
            params["lm_head"] = lin("lm_head.weight")
        # else: tied despite config — fall back to embed.T at logits time
    if tensors:
        logger.debug("unused tensors: %s", sorted(tensors)[:5])
    per_layer = 6 + (1 + 3 * config.num_experts if config.num_experts else 3)
    per_layer += 3 if config.attn_bias else 0
    per_layer += 2 if config.sandwich_norms else 0
    per_layer += 2 if config.qk_norm else 0
    mapped = 2 + per_layer * config.num_layers + (
        1 if "lm_head" in params else 0
    )
    logger.info(
        "loaded %d HF tensors from %s (quantize=%s, %d unused)",
        mapped,
        model_dir,
        quantize,
        len(tensors),
    )
    return params


def load_hybrid_ssm_safetensors(
    model_dir: str,
    config: Any,  # models.hybrid_ssm.HybridSsmConfig
    *,
    quantize: bool = False,
    dtype: jnp.dtype = jnp.bfloat16,
) -> Any:
    """The hybrid state-space family's checkpoint names (Hugging Face
    Jamba's): `input_layernorm`, `pre_ff_layernorm`, `feed_forward.{gate,up,
    down}_proj`; an attention layer's `self_attn.{q,k,v,o}_proj`; a Mamba
    layer's `mamba.{in_proj, conv1d, x_proj, dt_proj, out_proj}`, `mamba.A_log`
    and `mamba.D` (float32, `[d_inner, d_state]`: held transposed here, as
    the state is), `mamba.{dt,b,c}_layernorm`; `model.final_layernorm`. The
    convolution's weight is `[d_inner, 1, d_conv]`; here `[d_conv, d_inner]`."""
    forward_for(config).refuse_int8_weights(quantize)
    tensors = _read_safetensors(model_dir)
    c = config

    def get(name: str, as_dtype=dtype) -> jax.Array:
        return jnp.asarray(tensors.pop(name)).astype(as_dtype)

    def lin(name: str) -> jax.Array:  # HF stores [out, in]; we use [in, out]
        return get(name).T

    layers = []
    for i in range(c.num_layers):
        p = f"model.layers.{i}."
        layer = {"mix_norm": get(p + "input_layernorm.weight")}
        if c.is_attn_layer(i):
            a = p + "self_attn."
            layer.update(
                wq=lin(a + "q_proj.weight"), wk=lin(a + "k_proj.weight"),
                wv=lin(a + "v_proj.weight"), wo=lin(a + "o_proj.weight"),
            )
        else:
            m = p + "mamba."
            layer.update(
                w_in=lin(m + "in_proj.weight"),
                conv_w=get(m + "conv1d.weight")[:, 0, :].T,
                conv_b=get(m + "conv1d.bias"),
                w_x=lin(m + "x_proj.weight"),
                dt_norm=get(m + "dt_layernorm.weight"),
                b_norm=get(m + "b_layernorm.weight"),
                c_norm=get(m + "c_layernorm.weight"),
                w_dt=lin(m + "dt_proj.weight"),
                b_dt=get(m + "dt_proj.bias", jnp.float32),
                A_log=get(m + "A_log", jnp.float32).T,
                D=get(m + "D", jnp.float32),
                w_out=lin(m + "out_proj.weight"),
            )
        f = p + "feed_forward."
        layer.update(
            mlp_norm=get(p + "pre_ff_layernorm.weight"),
            wg=lin(f + "gate_proj.weight"), wu=lin(f + "up_proj.weight"),
            wd=lin(f + "down_proj.weight"),
        )
        layers.append(layer)
    params: dict[str, Any] = {
        "embed": get("model.embed_tokens.weight"),
        "layers": layers,
        "final_norm": get("model.final_layernorm.weight"),
    }
    if not c.tie_word_embeddings and "lm_head.weight" in tensors:
        params["lm_head"] = lin("lm_head.weight")
    tensors.pop("lm_head.weight", None)  # a tied head written out again
    logger.info(
        "loaded %d layers from %s (%d tensors left in the files)",
        len(layers), model_dir, len(tensors),
    )
    return params


def load_conv_moe_safetensors(
    model_dir: str,
    config: Any,  # models.conv_moe.ConvMoeConfig
    *,
    quantize: bool = False,
    dtype: jnp.dtype = jnp.bfloat16,
) -> Any:
    """The short-convolution, sparse-expert family's checkpoint names, written
    from Hugging Face's `Lfm2Moe*` classes and NOT yet tried on a real
    checkpoint (none is in the repository; a synthetic state dict under these
    names round-trips in `tests/test_conv_moe.py`): `operator_norm`,
    `ffn_norm`; a convolution layer's `conv.{in_proj, conv, out_proj}` (the
    convolution's weight is `[hidden, 1, conv_L_cache]`; here
    `[conv_L_cache, hidden]`); an attention layer's `self_attn.{q_proj,
    k_proj, v_proj, out_proj, q_layernorm, k_layernorm}`; a dense layer's
    `feed_forward.{w1, w3, w2}` (gate, up, down); an expert layer's
    `feed_forward.gate` (the router), `feed_forward.expert_bias` (float32)
    and `feed_forward.experts.N.{w1, w3, w2}`; `model.embedding_norm`."""
    forward_for(config).refuse_int8_weights(quantize)
    tensors = _read_safetensors(model_dir)
    c = config

    def get(name: str, as_dtype=dtype) -> jax.Array:
        return jnp.asarray(tensors.pop(name)).astype(as_dtype)

    def lin(name: str) -> jax.Array:  # HF stores [out, in]; we use [in, out]
        return get(name).T

    layers = []
    for i in range(c.num_layers):
        p = f"model.layers.{i}."
        layer = {"op_norm": get(p + "operator_norm.weight")}
        if c.is_attn_layer(i):
            a = p + "self_attn."
            layer.update(
                wq=lin(a + "q_proj.weight"), wk=lin(a + "k_proj.weight"),
                wv=lin(a + "v_proj.weight"), wo=lin(a + "out_proj.weight"),
                q_norm=get(a + "q_layernorm.weight"),
                k_norm=get(a + "k_layernorm.weight"),
            )
        else:
            m = p + "conv."
            layer.update(
                w_in=lin(m + "in_proj.weight"),
                conv_w=get(m + "conv.weight")[:, 0, :].T,
                w_out=lin(m + "out_proj.weight"),
            )
        layer["ffn_norm"] = get(p + "ffn_norm.weight")
        f = p + "feed_forward."
        if c.is_moe_layer(i):
            experts = range(c.num_experts)
            layer.update(
                router=lin(f + "gate.weight"),
                router_bias=(
                    get(f + "expert_bias", jnp.float32) if c.use_expert_bias
                    else jnp.zeros((c.num_experts,), jnp.float32)
                ),
                wg=jnp.stack([lin(f"{f}experts.{e}.w1.weight") for e in experts]),
                wu=jnp.stack([lin(f"{f}experts.{e}.w3.weight") for e in experts]),
                wd=jnp.stack([lin(f"{f}experts.{e}.w2.weight") for e in experts]),
            )
        else:
            layer.update(
                wg=lin(f + "w1.weight"), wu=lin(f + "w3.weight"),
                wd=lin(f + "w2.weight"),
            )
        layers.append(layer)
    params: dict[str, Any] = {
        "embed": get("model.embed_tokens.weight"),
        "layers": layers,
        "final_norm": get("model.embedding_norm.weight"),
    }
    if not c.tie_word_embeddings and "lm_head.weight" in tensors:
        params["lm_head"] = lin("lm_head.weight")
    tensors.pop("lm_head.weight", None)  # a tied head written out again
    logger.info(
        "loaded %d layers from %s (%d tensors left in the files); this "
        "family's checkpoint names are untried on a published checkpoint",
        len(layers), model_dir, len(tensors),
    )
    return params


def load_ssm2_moe_safetensors(
    model_dir: str,
    config: Any,  # models.ssm2_moe.Ssm2MoeConfig
    *,
    quantize: bool = False,
    dtype: jnp.dtype = jnp.bfloat16,
) -> Any:
    """The Mamba-2, latent-expert family's checkpoint names, written from
    Hugging Face's `NemotronH*` classes and untried on a published
    checkpoint (none is at hand; a synthetic state dict under these names
    round-trips in `tests/test_ssm2_moe.py`): `backbone.embeddings`,
    `backbone.layers.N.norm`; a Mamba-2 layer's `mixer.{in_proj, conv1d,
    out_proj}`, `mixer.{dt_bias, A_log, D}` (float32, `[heads]`),
    `mixer.norm` (the gated norm); an attention layer's `mixer.{q,k,v,o}_proj`;
    an expert layer's `mixer.gate.weight`, `mixer.gate.e_score_correction_bias`
    (float32), `mixer.fc1_latent_proj`, `mixer.fc2_latent_proj`,
    `mixer.experts.K.{up,down}_proj`, `mixer.shared_experts.{up,down}_proj`;
    `backbone.norm_f`, `lm_head`. The convolution's weight is `[channels, 1,
    kernel]`; here `[kernel, channels]`. Of a layer's experts only those this
    chip holds (`[first_held_expert, first_held_expert + num_experts)`) are
    read; the others' tensors stay in the files."""
    forward_for(config).refuse_int8_weights(quantize)
    tensors = _read_safetensors(model_dir)
    c = config

    def get(name: str, as_dtype=dtype) -> jax.Array:
        return jnp.asarray(tensors.pop(name)).astype(as_dtype)

    def lin(name: str) -> jax.Array:  # HF stores [out, in]; we use [in, out]
        return get(name).T

    layers = []
    for i in range(c.num_layers):
        p = f"backbone.layers.{i}."
        m = p + "mixer."
        layer = {"norm": get(p + "norm.weight")}
        kind = c.kind(i)
        if kind == "*":
            layer.update(
                wq=lin(m + "q_proj.weight"), wk=lin(m + "k_proj.weight"),
                wv=lin(m + "v_proj.weight"), wo=lin(m + "o_proj.weight"),
            )
        elif kind == "M":
            layer.update(
                w_in=lin(m + "in_proj.weight"),
                conv_w=get(m + "conv1d.weight")[:, 0, :].T,
                conv_b=get(m + "conv1d.bias"),
                dt_bias=get(m + "dt_bias", jnp.float32),
                A_log=get(m + "A_log", jnp.float32),
                D=get(m + "D", jnp.float32),
                gate_norm=get(m + "norm.weight"),
                w_out=lin(m + "out_proj.weight"),
            )
        else:
            held = range(c.first_held_expert, c.first_held_expert + c.num_experts)
            layer.update(
                router=lin(m + "gate.weight"),
                router_bias=get(m + "gate.e_score_correction_bias", jnp.float32),
                w_fc1=lin(m + "fc1_latent_proj.weight"),
                w_fc2=lin(m + "fc2_latent_proj.weight"),
                wu=jnp.stack([lin(f"{m}experts.{e}.up_proj.weight") for e in held]),
                wd=jnp.stack([lin(f"{m}experts.{e}.down_proj.weight") for e in held]),
                shared_wu=lin(m + "shared_experts.up_proj.weight"),
                shared_wd=lin(m + "shared_experts.down_proj.weight"),
            )
        layers.append(layer)
    params: dict[str, Any] = {
        "embed": get("backbone.embeddings.weight"),
        "layers": layers,
        "final_norm": get("backbone.norm_f.weight"),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = lin("lm_head.weight")
    logger.info(
        "loaded %d layers from %s (%d tensors left in the files, the "
        "experts other chips hold among them); this family's checkpoint "
        "names are untried on a published checkpoint",
        len(layers), model_dir, len(tensors),
    )
    return params


def load_afmoe_safetensors(
    model_dir: str,
    config: Any,  # models.afmoe.AfmoeConfig
    *,
    quantize: bool = False,
    dtype: jnp.dtype = jnp.bfloat16,
) -> Any:
    """The window-and-full attention, sparse-expert family's checkpoint
    names, written from Hugging Face's `modeling_afmoe.py` and untried on a
    published checkpoint (none is at hand; a synthetic state dict under these
    names round-trips in `tests/test_afmoe.py`): `model.embed_tokens`;
    `model.layers.N.{input_layernorm, post_attention_layernorm,
    pre_mlp_layernorm, post_mlp_layernorm}`; `self_attn.{q,k,v,o}_proj`,
    `self_attn.gate_proj` (the output gate), `self_attn.{q,k}_norm`; a dense
    layer's `mlp.{gate,up,down}_proj`; an expert layer's
    `mlp.router.gate.weight`, `mlp.expert_bias` (float32),
    `mlp.shared_experts.{gate,up,down}_proj`,
    `mlp.experts.K.{gate,up,down}_proj`; `model.norm`, `lm_head`. Of a
    layer's experts only those this chip holds (`[first_held_expert,
    first_held_expert + num_experts)`) are read; the others' tensors stay in
    the files."""
    forward_for(config).refuse_int8_weights(quantize)
    tensors = _read_safetensors(model_dir)
    c = config

    def get(name: str, as_dtype=dtype) -> jax.Array:
        return jnp.asarray(tensors.pop(name)).astype(as_dtype)

    def lin(name: str) -> jax.Array:  # HF stores [out, in]; we use [in, out]
        return get(name).T

    layers = []
    held = range(c.first_held_expert, c.first_held_expert + c.num_experts)
    for i in range(c.num_layers):
        p = f"model.layers.{i}."
        a, m = p + "self_attn.", p + "mlp."
        layer = {
            "attn_norm": get(p + "input_layernorm.weight"),
            "post_attn_norm": get(p + "post_attention_layernorm.weight"),
            "pre_mlp_norm": get(p + "pre_mlp_layernorm.weight"),
            "post_mlp_norm": get(p + "post_mlp_layernorm.weight"),
            "wq": lin(a + "q_proj.weight"), "wk": lin(a + "k_proj.weight"),
            "wv": lin(a + "v_proj.weight"), "w_gate": lin(a + "gate_proj.weight"),
            "wo": lin(a + "o_proj.weight"),
            "q_norm": get(a + "q_norm.weight"), "k_norm": get(a + "k_norm.weight"),
        }
        if not c.is_moe_layer(i):
            layer.update(
                wg=lin(m + "gate_proj.weight"), wu=lin(m + "up_proj.weight"),
                wd=lin(m + "down_proj.weight"),
            )
        else:
            stack = lambda w: jnp.stack(
                [lin(f"{m}experts.{e}.{w}_proj.weight") for e in held]
            )
            layer.update(
                router=lin(m + "router.gate.weight"),
                router_bias=get(m + "expert_bias", jnp.float32),
                shared_wg=lin(m + "shared_experts.gate_proj.weight"),
                shared_wu=lin(m + "shared_experts.up_proj.weight"),
                shared_wd=lin(m + "shared_experts.down_proj.weight"),
                wg=stack("gate"), wu=stack("up"), wd=stack("down"),
            )
        layers.append(layer)
    params: dict[str, Any] = {
        "embed": get("model.embed_tokens.weight"),
        "layers": layers,
        "final_norm": get("model.norm.weight"),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = lin("lm_head.weight")
    logger.info(
        "loaded %d layers from %s (%d tensors left in the files, the "
        "experts other chips hold among them); this family's checkpoint "
        "names are untried on a published checkpoint",
        len(layers), model_dir, len(tensors),
    )
    return params


# A family's checkpoint names, by its config class as the families' one table
# has it (`models.served_model_types`): a loader reads the names that one of
# the family's `model_type`s publishes.
_SERVED = served_model_types()
LOADERS = {
    _SERVED["llama"]: load_hf_safetensors,
    _SERVED["joyai_llm_flash"]: load_latent_moe_safetensors,
    _SERVED["jamba"]: load_hybrid_ssm_safetensors,
    _SERVED["lfm2_moe"]: load_conv_moe_safetensors,
    _SERVED["nemotron_h"]: load_ssm2_moe_safetensors,
    _SERVED["afmoe"]: load_afmoe_safetensors,
}
assert set(LOADERS) == set(_SERVED.values()), "a family without checkpoint names"

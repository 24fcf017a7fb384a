"""Pipeline parallelism (pp mesh axis): layer-partitioned llama forward
with ppermute stage handoff.

Role-equivalent of the reference's --pipeline-parallel-size pass-through
(launch/dynamo-run/src/main.rs:39 — it hands PP to vLLM/TRT-LLM; here the
engine is ours, so PP is implemented in the model math). TPU-first shape:

  * per-layer params are STACKED ([L, ...] leading axis) and sharded over
    the mesh's "pp" axis — each stage holds L/pp layers;
  * the paged KV cache is, like the serial path's, one array per layer a
    stage walks: a tuple of L/pp arrays [pp, Hkv, nb, bs, D] whose leading
    STAGE axis is sharded over pp (`make_pp_cache`). Element j holds, on
    stage s, the pages of layer s*L/pp + j, so each stage reads/writes
    only its own layers' pages — PP divides cache HBM exactly like it
    divides weight HBM — and writes each in place in its own buffer. The
    stage's layers are therefore unrolled, not scanned: a scan would
    carry the layers stacked and slice one out and back per layer;
  * activations move stage-to-stage with `lax.ppermute` over ICI inside a
    fill/drain microbatch rotation: with M microbatches the schedule runs
    M + pp - 1 ticks, every stage computing every tick once the pipe is
    full (the classic GPipe inference schedule, SPMD-formulated so all
    stages run ONE program).

Scope: dense llama/qwen2-family layers — bf16/fp32 AND int8
weight-only quantized (each quantized weight {"q": [in,out] int8,
"s": [out]} stacks to {"q": [L,in,out], "s": [L,out]} and pp-shards on
the leading layer axis like any other leaf; the stage scan slices the
pytree per layer and ops/linear.py dequantizes inside the matmul).
Qwen2 attention biases ride along. MoE expert layers remain rejected at
stack-time — MoE wants ep over the same devices instead.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.ops.attention import NEG_INF
from dynamo_tpu.ops.basics import rms_norm, rope_freqs, swiglu
from dynamo_tpu.ops.layers import attn_out, qkv_head
from dynamo_tpu.ops.linear import linear


def stack_layer_params(params: dict) -> dict:
    """[{wq, wk, ...}] x L -> {"wq": [L, ...], ...} for pp sharding.

    int8-quantized weights ({"q", "s"} dicts) stack per leaf, so the
    scanned per-layer slice keeps the exact shape ops/linear.py consumes."""
    layers = params["layers"]
    if "router" in layers[0]:
        raise NotImplementedError(
            "pipeline parallelism over MoE layers is not supported — use "
            "expert parallelism (ep) for Mixtral-family models"
        )

    def stack_leaf(key):
        vals = [lyr[key] for lyr in layers]
        if isinstance(vals[0], dict):
            return {
                k2: jnp.stack([v[k2] for v in vals]) for k2 in vals[0]
            }
        return jnp.stack(vals)

    stacked = {k: stack_leaf(k) for k in layers[0]}
    return {
        "embed": params["embed"],
        "layers": stacked,
        "final_norm": params["final_norm"],
        **({"lm_head": params["lm_head"]} if "lm_head" in params else {}),
    }


def shard_stacked_pp(
    mesh: Mesh, stacked: dict
) -> tuple[dict, NamedSharding]:
    """Place stacked params: layer axis over pp (non-layer params
    replicated). Returns (params, kv_cache_sharding): the sharding of each
    array of the cache, whose leading STAGE axis is pp-sharded."""
    pp_first = NamedSharding(mesh, P("pp"))
    repl = NamedSharding(mesh, P())
    out = {
        "embed": jax.device_put(stacked["embed"], repl),
        "final_norm": jax.device_put(stacked["final_norm"], repl),
        # every layer leaf — including int8 {"q","s"} pairs — has the
        # stacked layer axis leading, so one prefix spec shards them all
        "layers": jax.tree.map(
            lambda v: jax.device_put(v, pp_first), stacked["layers"]
        ),
    }
    if "lm_head" in stacked:
        out["lm_head"] = jax.tree.map(
            lambda v: jax.device_put(v, repl), stacked["lm_head"]
        )
    kv_sharding = NamedSharding(mesh, P("pp"))  # [pp, Hkv, nb, bs, D]
    return out, kv_sharding


def make_pp_cache(
    kv_sharding: NamedSharding, cfg, num_blocks: int, block_size: int, dtype
) -> tuple:
    """A zeroed cache for prefill_pp/decode_pp: one array per layer of a
    stage, [pp, Hkv, nb, bs, D] with the stage axis over pp."""
    pp = kv_sharding.mesh.shape["pp"]
    shape = (pp, cfg.num_kv_heads, num_blocks, block_size, cfg.head_dim)
    return tuple(
        jax.device_put(jnp.zeros(shape, dtype), kv_sharding)
        for _ in range(cfg.num_layers // pp)
    )


def pp_cache_layers(cache: tuple) -> list:
    """The cache as the model's layers in order, [Hkv, nb, bs, D] each
    (stage s holds layers s*L/pp .. (s+1)*L/pp - 1)."""
    pp = cache[0].shape[0]
    return [c[s] for s in range(pp) for c in cache]


# ------------------------------------------------------------ stage math


def _check_pp_supported(cfg) -> None:
    """The pp forward hardcodes the llama/qwen2 dense path (SwiGLU,
    unscaled embeddings, optional attention biases); family flags it does
    not implement must refuse loudly instead of serving silently-wrong
    outputs."""
    if cfg.mlp_act != "silu" or cfg.embed_scale:
        raise NotImplementedError(
            "pipeline parallelism supports the SwiGLU/unscaled-embedding "
            "families only (llama/qwen2/mixtral-dense); gemma's GeGLU and "
            "embedding scaling are not plumbed through the pp stages"
        )
    if getattr(cfg, "sandwich_norms", False):
        raise NotImplementedError(
            "pipeline parallelism does not implement the post-MLP sandwich "
            "norm; serving gemma2/3-style layers through pp would silently "
            "skip it"
        )
    if any(cfg.layer_window(i) for i in range(cfg.num_layers)):
        raise NotImplementedError(
            "pipeline parallelism implements full attention only; a "
            "sliding-window config served through pp would silently attend "
            "past the window"
        )


def _stage_layers(cfg, layers, x, positions, attend, write_kv, k_cache, v_cache):
    """Apply this stage's local layers, unrolled.

    `attend(q, kc, vc, k, v)` and `write_kv(kc, vc, k, v)` close over the
    attention style (prefill in-buffer vs paged decode); k_cache/v_cache
    are the stage's own arrays, one [1, Hkv, nb, bs, D] per local layer."""
    inv_freqs = rope_freqs(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
    k_out, v_out = [], []
    for j, (kc, vc) in enumerate(zip(k_cache, v_cache)):
        lyr = jax.tree.map(lambda a: a[j], layers)
        # the SAME projection head as the serial/cp/decode paths
        # (ops/layers.py — handles int8 {"q","s"} weights and qwen2
        # biases); only the attention itself differs per phase
        q, k, v = qkv_head(x, lyr, cfg, inv_freqs, positions)
        kc, vc = write_kv(kc[0], vc[0], k, v)
        attn = attend(q, kc, vc, k, v)
        x = attn_out(attn, x, lyr, cfg)
        h2 = rms_norm(x, lyr["mlp_norm"], cfg.rms_eps)
        gate = linear(h2, lyr["wg"])
        up = linear(h2, lyr["wu"])
        x = x + linear(swiglu(gate, up), lyr["wd"])
        k_out.append(kc[None])
        v_out.append(vc[None])
    return x, tuple(k_out), tuple(v_out)


def _keep_if(active, new: tuple, old: tuple) -> tuple:
    return tuple(jnp.where(active, n, o) for n, o in zip(new, old))


def prefill_pp(
    params: dict,  # stacked + pp-sharded (shard_stacked_pp)
    cfg,
    mesh: Mesh,
    tokens: jax.Array,  # [Pl] int32, padded
    valid_len: jax.Array,  # scalar int32
    k_cache: tuple,  # make_pp_cache: L/pp x [pp, Hkv, nb, bs, D]
    v_cache: tuple,
    block_table: jax.Array,  # [Pl // bs] int32
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-prompt prefill through the pipeline: the activation visits
    stage 0..pp-1 in order via ppermute (one microbatch — prefill is a
    latency path; decode_pp below overlaps microbatches). Every stage
    writes its own layers' KV pages. Returns (last-token logits [V],
    caches)."""
    _check_pp_supported(cfg)
    pp = mesh.shape["pp"]
    Pl = tokens.shape[0]
    positions = jnp.arange(Pl, dtype=jnp.int32)
    causal = positions[None, :] <= positions[:, None]
    in_seq = positions[None, :] < valid_len
    mask = causal & in_seq

    def attend(q, kc, vc, k, v):
        # in-buffer causal attention (prompt K/V just computed)
        Hq, D = q.shape[1], q.shape[2]
        Hkv = k.shape[1]
        G = Hq // Hkv
        scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
        qr = q.reshape(Pl, Hkv, G, D)
        scores = jnp.einsum(
            "qhgd,khd->hgqk", qr.astype(jnp.float32), k.astype(jnp.float32)
        ) * scale
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("hgqk,khd->qhgd", w, v.astype(jnp.float32))
        return out.reshape(Pl, Hq, D).astype(q.dtype)

    def write_kv(kc, vc, k, v):
        from dynamo_tpu.ops.attention import write_prefill_kv

        return write_prefill_kv(kc, vc, k, v, block_table)

    def stage_fn(layers, embed, final_norm, lm_head, k_cache, v_cache):
        stage = jax.lax.axis_index("pp")
        x0 = embed[tokens].astype(embed.dtype)
        x = x0

        def tick(t, carry):
            x, k_cache, v_cache = carry
            y, kc2, vc2 = _stage_layers(
                cfg, layers, x, positions, attend, write_kv, k_cache, v_cache
            )
            active = stage == t  # stage s works at tick s (one microbatch)
            x = jnp.where(active, y, x)
            k_cache = _keep_if(active, kc2, k_cache)
            v_cache = _keep_if(active, vc2, v_cache)
            # hand the activation to the next stage
            x = jax.lax.ppermute(
                x, "pp", [(i, (i + 1) % pp) for i in range(pp)]
            )
            return (x, k_cache, v_cache)

        x, k_cache, v_cache = jax.lax.fori_loop(
            0, pp, tick, (x, k_cache, v_cache)
        )
        # after pp ticks the fully-processed activation has rotated back to
        # stage 0; other stages hold pipeline residue — zero them and psum
        # so the logits output is genuinely replicated
        h = rms_norm(x, final_norm, cfg.rms_eps)
        last = h[valid_len - 1]
        logits = linear(last.astype(jnp.float32), lm_head)
        logits = jnp.where(stage == 0, logits, 0.0)
        logits = jax.lax.psum(logits, "pp")
        return logits, k_cache, v_cache

    pp_spec = P("pp")
    fn = shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(pp_spec, P(), P(), P(), pp_spec, pp_spec),
        out_specs=(P(), pp_spec, pp_spec),
        check_vma=False,
    )
    lm_head = params.get("lm_head")
    if lm_head is None:
        lm_head = params["embed"].T
    return fn(
        params["layers"], params["embed"], params["final_norm"], lm_head,
        k_cache, v_cache,
    )


def decode_pp(
    params: dict,
    cfg,
    mesh: Mesh,
    tokens: jax.Array,  # [B] int32
    positions: jax.Array,  # [B] int32
    k_cache: tuple,  # make_pp_cache: L/pp x [pp, Hkv, nb, bs, D]
    v_cache: tuple,
    block_tables: jax.Array,  # [B, max_blocks] int32
    slot_indices: jax.Array,  # [B] int32
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched decode through the pipeline with the fill/drain microbatch
    rotation: B must divide by pp; microbatch m enters stage 0 at tick m,
    exits stage pp-1 at tick m+pp-1 — every stage busy in the steady
    state. Returns (logits [B, V], caches)."""
    _check_pp_supported(cfg)
    from dynamo_tpu.ops.attention import write_decode_kv

    pp = mesh.shape["pp"]
    B = tokens.shape[0]
    assert B % pp == 0, f"decode batch {B} must divide by pp={pp}"
    Mb = B // pp  # microbatch size
    n_ticks = 2 * pp - 1

    def attend_factory(bt, pos1, slots):
        def attend(q, kc, vc, k, v):
            Hq, D = q.shape[1], q.shape[2]
            Hkv, _, bs, _ = kc.shape
            G = Hq // Hkv
            S = bt.shape[1] * bs
            scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
            kw = kc[:, bt].reshape(Hkv, Mb, S, D)
            vw = vc[:, bt].reshape(Hkv, Mb, S, D)
            qr = q.reshape(Mb, Hkv, G, D)
            scores = jnp.einsum(
                "bhgd,hbsd->bhgs", qr.astype(jnp.float32),
                kw.astype(jnp.float32),
            ) * scale
            m = (jnp.arange(S)[None, :] < (pos1)[:, None])[:, None, None, :]
            scores = jnp.where(m, scores, NEG_INF)
            w = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhgs,hbsd->bhgd", w, vw.astype(jnp.float32))
            return out.reshape(Mb, Hq, D).astype(q.dtype)

        def write_kv(kc, vc, k, v):
            return write_decode_kv(kc, vc, k, v, slots)

        return attend, write_kv

    def stage_fn(layers, embed, final_norm, lm_head, k_cache, v_cache):
        stage = jax.lax.axis_index("pp")
        D = embed.shape[1]
        buf = jnp.zeros((Mb, D), embed.dtype)  # activation in flight
        meta = jnp.zeros((Mb, 3), jnp.int32)  # (seq index in B, unused...)
        out = jnp.zeros((B, cfg.vocab_size), jnp.float32)

        def tick(t, carry):
            buf, meta, out, k_cache, v_cache = carry
            m_in = t  # microbatch entering stage 0 this tick
            # stage 0 loads its incoming microbatch (if one remains)
            load = (stage == 0) & (m_in < pp)
            mb_idx = jnp.clip(m_in, 0, pp - 1)
            in_tokens = jax.lax.dynamic_slice(tokens, (mb_idx * Mb,), (Mb,))
            x_in = embed[in_tokens].astype(embed.dtype)
            idx_in = mb_idx * Mb + jnp.arange(Mb, dtype=jnp.int32)
            buf = jnp.where(load, x_in, buf)
            meta = jnp.where(
                load, jnp.stack([idx_in] * 3, axis=1), meta
            )
            # every stage processes what it holds; validity by schedule
            my_mb = t - stage  # microbatch this stage holds this tick
            active = (my_mb >= 0) & (my_mb < pp)
            seq_idx = meta[:, 0]
            pos_mb = positions[seq_idx]
            bt_mb = block_tables[seq_idx]
            slots_mb = slot_indices[seq_idx]
            attend, write_kv = attend_factory(bt_mb, pos_mb + 1, slots_mb)
            y, kc2, vc2 = _stage_layers(
                cfg, layers, buf, pos_mb, attend, write_kv, k_cache, v_cache
            )
            buf = jnp.where(active, y, buf)
            k_cache = _keep_if(active, kc2, k_cache)
            v_cache = _keep_if(active, vc2, v_cache)
            # last stage emits logits for its finished microbatch
            emit = active & (stage == pp - 1)
            h = rms_norm(buf, final_norm, cfg.rms_eps)
            logits_mb = linear(h.astype(jnp.float32), lm_head)
            upd = jnp.zeros_like(out).at[seq_idx].set(logits_mb)
            out = jnp.where(emit, out + upd, out)
            # rotate activations + metadata forward one stage
            perm = [(i, (i + 1) % pp) for i in range(pp)]
            buf = jax.lax.ppermute(buf, "pp", perm)
            meta = jax.lax.ppermute(meta, "pp", perm)
            return (buf, meta, out, k_cache, v_cache)

        buf, meta, out, k_cache, v_cache = jax.lax.fori_loop(
            0, n_ticks, tick, (buf, meta, out, k_cache, v_cache)
        )
        # logits live on the last stage only; psum replicates (zeros
        # elsewhere make it a broadcast, not a reduction error)
        out = jax.lax.psum(out, "pp")
        return out, k_cache, v_cache

    pp_spec = P("pp")
    fn = shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(pp_spec, P(), P(), P(), pp_spec, pp_spec),
        out_specs=(P(), pp_spec, pp_spec),
        check_vma=False,
    )
    lm_head = params.get("lm_head")
    if lm_head is None:
        lm_head = params["embed"].T
    return fn(
        params["layers"], params["embed"], params["final_norm"], lm_head,
        k_cache, v_cache,
    )

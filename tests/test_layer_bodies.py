"""A layer's body is one jitted function, called once a layer (PR 37).

`models.layer_body` changes how a step program is traced and lowered, and
nothing of what it computes. Held here on the CPU: a toy engine streams the
tokens the parent tree streamed (written below, read from the parent commit)
through `decode_multi`, `mixed_step` and a preemption, greedy and seeded; a
program lowers one body for each kind of layer it observes, whatever its
depth; and the goodput ledger says, for each label's first dispatch, what JAX
spent tracing, lowering and compiling it and how many bodies it called.
`tests/test_tpu_compile.py` holds the compiled programs to the parent's.
"""

import asyncio
import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import layer_bodies_called
from dynamo_tpu.models import llama as L
from dynamo_tpu.models import mla_moe as M
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.telemetry.goodput import (
    FIRST_DISPATCH_FIELDS,
    GoodputLedger,
    GoodputStats,
    first_dispatch_split,
)
from tests.test_jax_engine import collect

SAMPLING = {
    "greedy": lambda seed: SamplingOptions(greedy=True),
    "seeded": lambda seed: SamplingOptions(temperature=0.9, top_k=8, seed=seed),
}


def make_engine(num_blocks=64, decode_horizon=4, attn_impl="auto"):
    """A two-layer toy at horizon 4 with mixed steps and 8-token chunks, in
    float32: there the CPU's compiler gives both trees one program, bit for
    bit. (A bfloat16 toy's tokens are not the parent's on the CPU: XLA drops
    bfloat16 round trips where it finds them adjacent,
    `xla_allow_excess_precision`, and finds other ones across a call
    boundary. On the chip the compiled programs are the parent's:
    `tests/test_tpu_compile.py`.)"""
    from dynamo_tpu.engine.jax_engine.engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner

    cfg = L.LlamaConfig.tiny(vocab_size=64)
    params = L.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    runner = ModelRunner(
        cfg, params, num_blocks=num_blocks, block_size=4, max_batch=4,
        max_model_len=64, prefill_chunk_tokens=8, kv_dtype=jnp.float32,
        attn_impl=attn_impl,
    )
    return JaxEngine(runner, JaxEngineConfig(
        max_batch=4, block_size=4, num_blocks=num_blocks, max_model_len=64,
        watermark_blocks=2, mixed_step=True, decode_horizon=decode_horizon,
        preempt_backoff_ms=1.0,
    ))


def request(prompt, max_tokens, sampling, priority=None):
    return PreprocessedRequest(
        token_ids=prompt, sampling=sampling,
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        extra={"priority": priority} if priority else {},
    )


async def overlapped(sampling: str):
    """A short prompt decodes at horizon 4 while a 40-token prompt arrives
    and rides mixed steps in 8-token chunks; then, on a pool of 9 usable
    blocks where each of two sequences wants 6, the bulk one is preempted
    and resumes. Returns the five streams and the engines' ledgers."""
    make = SAMPLING[sampling]
    engine = make_engine()
    short = asyncio.create_task(collect(engine, request([1, 2, 3], 24, make(7))))
    await asyncio.sleep(0.05)  # the short prompt enters decode first
    long_prompt = [int(t) for t in np.random.default_rng(1).integers(1, 64, size=40)]
    long = asyncio.create_task(collect(engine, request(long_prompt, 7, make(77))))
    third = asyncio.create_task(collect(engine, request([9, 8, 7], 12, make(4242))))
    streams = [(await t)[0] for t in (short, long, third)]
    first = engine.stats.goodput.summary()
    await engine.close()

    tight = make_engine(num_blocks=10)
    bulk, inter = await asyncio.wait_for(asyncio.gather(
        collect(tight, request([5, 9, 17, 23], 20, make(424242), "bulk")),
        collect(tight, request([40, 41, 42, 43], 20, make(11), "interactive")),
    ), timeout=120)
    preempted = dict(tight.stats.preemptions_by_class)
    await tight.close()
    return streams + [bulk[0], inter[0]], first, preempted


# read from the parent commit (0312011, the tree before `models.layer_body`)
# by this same function: the tokens of its five streams
PARENT_TOKENS = {
    "greedy": [
        [19, 33, 33, 33, 33, 33, 33, 33, 33, 33, 33, 33, 33, 33, 33, 16, 39, 16, 16, 21, 21, 21, 21, 33],
        [2, 11, 55, 14, 11, 35, 11],
        [8, 48, 29, 35, 35, 35, 11, 21, 11, 43, 21, 48],
        [38, 38, 38, 59, 7, 35, 33, 35, 33, 35, 33, 35, 38, 52, 35, 35, 35, 35, 35, 35],
        [62, 1, 20, 16, 55, 16, 4, 55, 20, 17, 49, 17, 3, 39, 45, 12, 59, 45, 4, 63],
    ],
    "seeded": [
        [28, 11, 31, 16, 41, 30, 24, 18, 11, 10, 28, 22, 62, 58, 30, 36, 31, 36, 31, 2, 37, 2, 37, 52],
        [11, 36, 24, 17, 11, 11, 17],
        [55, 16, 21, 28, 31, 38, 0, 43, 28, 12, 62, 10],
        [38, 38, 59, 7, 35, 28, 38, 19, 63, 11, 33, 63, 28, 35, 28, 28, 23, 47, 27, 48],
        [4, 38, 49, 1, 49, 50, 7, 55, 31, 7, 1, 11, 55, 39, 45, 57, 49, 55, 48, 12],
    ],
}


@pytest.mark.parametrize("sampling", sorted(PARENT_TOKENS))
def test_a_toy_engine_streams_the_parents_tokens(sampling):
    streams, ledger, preempted = asyncio.run(overlapped(sampling))
    labels = set(ledger["compile_s_by_label"])
    assert "decode_multi@H4B4" in labels, labels
    assert any(l.startswith("mixed_step@c") for l in labels), labels
    assert preempted.get("bulk", 0) >= 1 and "interactive" not in preempted
    assert streams == PARENT_TOKENS[sampling]


def test_the_ledger_splits_a_first_dispatch():
    """Every label's first dispatch carries JAX's own seconds for the trace,
    the MLIR module and the backend, inside the dispatch's whole time, and
    the bodies its programs called: one for a dense model's packed prefill
    and its horizon (two layers, four steps: eight calls), two for a mixed
    step (a chunk's and a decode's)."""
    _, ledger, _ = asyncio.run(overlapped("greedy"))
    split = ledger["first_dispatch_by_label"]
    assert set(split) == set(ledger["compile_s_by_label"])
    for label, fields in split.items():
        assert tuple(fields) == FIRST_DISPATCH_FIELDS
        staged = fields["trace_s"] + fields["lower_s"] + fields["backend_s"]
        assert 0 < staged <= ledger["compile_s_by_label"][label] + 0.01, (label, fields)
    bodies = {label: fields["layer_bodies"] for label, fields in split.items()}
    assert bodies["prefill_packed"] == 1 and bodies["decode_multi@H4B4"] == 1
    mixed = {n for label, n in bodies.items() if label.startswith("mixed_step@c")}
    assert mixed == {2}, bodies


@pytest.mark.parametrize("attn_impl,folded", [("pallas_interpret", 2), ("auto", 0)])
def test_the_ledger_says_where_each_program_appends(attn_impl, folded):
    """`kv_append_folded` and `kv_append_scattered` of a label's first
    dispatch: through the kernel, the toy's horizon appends inside the paged
    decode call in both of its layers (counted once for the four steps) and
    its packed prefill, which writes many tokens a lane, scatters in both;
    on the CPU's default, the XLA form, the entry falls to the pair and the
    horizon scatters too."""
    async def run():
        engine = make_engine(attn_impl=attn_impl)
        await collect(engine, request([1, 2, 3], 12, SamplingOptions(greedy=True)))
        split = engine.stats.goodput.summary()["first_dispatch_by_label"]
        await engine.close()
        return split

    jax.clear_caches()  # a body traced by an earlier test counts from its memo
    split = asyncio.run(run())
    appends = lambda label: (split[label]["kv_append_folded"], split[label]["kv_append_scattered"])
    assert appends("decode_multi@H4B4") == (folded, 2 - folded)
    assert appends("prefill_packed") == (0, 2)
    assert all(tuple(fields) == FIRST_DISPATCH_FIELDS for fields in split.values())


def make_expert_engine(attn_impl):
    """A dense layer and an expert layer of the latent family at widths the
    grouped product's kernel can tile (128 lanes; 16 lanes x 8 experts a
    token and a 16-token chunk are one tile of 128 rows each)."""
    from dynamo_tpu.engine.jax_engine.engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner

    cfg = M.MlaMoeConfig(
        vocab_size=64, hidden_size=128, intermediate_size=128,
        moe_intermediate_size=128, num_layers=2, first_k_dense=1, num_heads=2,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0,
        max_position_embeddings=64, n_routed_experts=16, num_experts_per_tok=8,
    )
    runner = ModelRunner(
        cfg, M.init_params(cfg, jax.random.PRNGKey(0)), num_blocks=96,
        block_size=4, max_batch=16, max_model_len=64, prefill_chunk_tokens=16,
        attn_impl=attn_impl,
    )
    return JaxEngine(runner, JaxEngineConfig(
        max_batch=16, block_size=4, num_blocks=96, max_model_len=64,
        watermark_blocks=2, mixed_step=True, decode_horizon=4,
        preempt_backoff_ms=1.0,
    ))


@pytest.mark.parametrize("attn_impl,kernel", [("pallas_interpret", 3), ("auto", 0)])
def test_the_ledger_says_which_form_the_grouped_products_took(attn_impl, kernel):
    """`grouped_product_kernel` and `grouped_product_xla` of a label's first
    dispatch: through the kernel, the toy's one expert layer runs its three
    products in the Pallas grouped product in the horizon (counted once for
    the four steps) and in the packed prefill; on the CPU's default, the XLA
    form, all three are `lax.ragged_dot` in both."""
    async def run():
        engine = make_expert_engine(attn_impl)
        await collect(engine, request([1, 2, 3], 12, SamplingOptions(greedy=True)))
        split = engine.stats.goodput.summary()["first_dispatch_by_label"]
        await engine.close()
        return split

    jax.clear_caches()  # a body traced by an earlier test counts from its memo
    split = asyncio.run(run())
    products = lambda label: (split[label]["grouped_product_kernel"], split[label]["grouped_product_xla"])
    assert products("decode_multi@H4B16") == (kernel, 3 - kernel)
    assert products("prefill_packed") == (kernel, 3 - kernel)
    assert all(tuple(fields) == FIRST_DISPATCH_FIELDS for fields in split.values())


def test_a_nested_trace_counts_once():
    """JAX reports a jitted function traced inside another before the outer
    one, whose span covers it: the split keeps the outer's seconds alone,
    so the three stages fit inside the block's wall time."""
    inner = jax.jit(lambda x: x * 2)
    outer = jax.jit(lambda x: inner(x) + inner(x + 1))
    into: dict = {}
    t0 = time.time()
    with first_dispatch_split(into):
        outer(jnp.ones(3)).block_until_ready()
        jax.jit(lambda x: x - 1)(jnp.ones(5)).block_until_ready()
    wall = time.time() - t0
    assert tuple(into) == FIRST_DISPATCH_FIELDS and into["layer_bodies"] == 0
    assert 0 < into["trace_s"] and 0 < into["lower_s"] and 0 < into["backend_s"]
    assert into["trace_s"] + into["lower_s"] + into["backend_s"] <= wall


def test_the_split_merges_and_crosses_the_wire():
    a, b = GoodputLedger(enabled=True), GoodputLedger(enabled=True)
    a.record_compile("decode", 12.0, {"trace_s": 1.0, "lower_s": 0.5, "backend_s": 9.0, "layer_bodies": 1, "kv_append_folded": 32, "grouped_product_kernel": 42, "ssd_step_kernel": 20})
    b.record_compile("decode", 11.0, {"trace_s": 2.0, "lower_s": 0.25, "backend_s": 8.0, "layer_bodies": 1, "kv_append_scattered": 2, "grouped_product_xla": 3})
    b.record_compile("prefill_packed", 3.0)  # no split given: none kept
    merged = GoodputStats.from_dict(a.to_dict())
    merged.merge(GoodputStats.from_dict(b.to_dict()))
    assert merged.first_dispatch_by_label == {
        "decode": {
            "trace_s": 2.0, "lower_s": 0.5, "backend_s": 9.0, "layer_bodies": 1.0,
            "kv_append_folded": 32.0, "kv_append_scattered": 2.0,
            "grouped_product_kernel": 42.0, "grouped_product_xla": 3.0,
            "ssd_step_kernel": 20.0, "ssd_step_xla": 0.0,
        },
    }
    assert merged.summary()["first_dispatch_by_label"]["decode"]["trace_s"] == 2.0
    off = GoodputLedger(enabled=False)
    off.record_compile("decode", 1.0, {"trace_s": 1.0})
    assert off.first_dispatch_by_label == {}


# ------------------------------------------------- bodies by what is observed


def private_functions(text: str) -> int:
    return len(re.findall(r"func\.func private", text))


def lowered_decode(cfg, family=L):
    """`decode` of `cfg` lowered on the CPU at a toy size: (StableHLO text,
    distinct bodies called)."""
    params = family.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    kind = cfg.cache_kind() if family is M else None
    shape = (1, 8, 4, kind.stored_width) if kind else (cfg.num_kv_heads, 8, 4, cfg.head_dim)
    k_cache = tuple(jnp.zeros(shape, jnp.float32) for _ in range(cfg.num_layers))
    v_cache = () if kind else k_cache
    ids = jnp.zeros((2,), jnp.int32)
    fn = jax.jit(lambda p, k, v: family.decode(
        p, cfg, ids, ids, k, v, jnp.zeros((2, 2), jnp.int32), ids + 4,
    ))
    with layer_bodies_called() as bodies:
        text = fn.lower(params, k_cache, v_cache).as_text(debug_info=True)
    return text, len(bodies)


WINDOWED = dict(sliding_window=16, attn_impl="xla")


@pytest.mark.parametrize("name,cfg,bodies", [
    ("every layer slides", dict(WINDOWED), 1),
    ("alternating windows", dict(WINDOWED, layer_pattern=(True, False) * 2), 2),
    ("a local rope table", dict(WINDOWED, layer_pattern=(True, True, False, True), rope_local_theta=1e4), 2),
    ("scaled rope", dict(rope_scaling={"rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0, "high_freq_factor": 4.0, "original_max_position_embeddings": 64}), 1),
])
def test_a_body_for_each_kind_of_layer_never_for_its_index(name, cfg, bodies):
    """The static key of a body is what the code observes of a layer (its
    window, its keys, the shapes): four layers that look alike share one body
    and an alternating pattern gets two; the rope table is an argument, so a
    layer that only rotates differently shares its body too. A config stays
    hashable with HF's `rope_scaling` dict in it."""
    cfg = dataclasses.replace(L.LlamaConfig.tiny(vocab_size=64), num_layers=4, **cfg)
    assert hash(cfg) == hash(dataclasses.replace(cfg))
    text4, called = lowered_decode(cfg)
    assert called == bodies
    text2, _ = lowered_decode(dataclasses.replace(
        cfg, num_layers=2,
        layer_pattern=cfg.layer_pattern and cfg.layer_pattern[2:],
    ))
    assert private_functions(text4) == private_functions(text2)


def test_the_latent_family_has_a_dense_and_an_expert_body():
    from tests.test_mla_moe import HF

    cfg = M.MlaMoeConfig.from_hf_dict(dict(HF, num_hidden_layers=4))
    text4, called = lowered_decode(cfg, M)
    assert called == 2
    text2, _ = lowered_decode(dataclasses.replace(cfg, num_layers=2), M)
    assert private_functions(text4) == private_functions(text2)
    # the named scopes cross the call boundary into the operations' locations
    for scope in ("mla.attend", "moe.route", "moe.experts", "moe.shared"):
        assert scope in text4, scope


def test_the_short_convolution_family_has_three_bodies_whatever_its_depth():
    """`models/conv_moe.py`: a dense convolution layer, an expert convolution
    layer and an expert attention layer are three bodies a program, lowered
    once each: a model of twice the depth, its literal `layer_types` repeated,
    lowers to as many private functions."""
    from dynamo_tpu.models import conv_moe as C
    from dynamo_tpu.models import layer_bodies_called
    from tests.test_conv_moe import HF

    def lowered(cfg):
        jax.clear_caches()
        params = C.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        pages = lambda: jnp.zeros((cfg.num_kv_heads // cfg.kv_pack, 8, 4, cfg.kv_pack * cfg.head_dim), jnp.float32)
        k_cache = tuple(
            pages() if cfg.is_attn_layer(i) else jnp.zeros((3, 2 * cfg.hidden_size), jnp.float32)
            for i in range(cfg.num_layers)
        )
        v_cache = tuple(pages() if cfg.is_attn_layer(i) else None for i in range(cfg.num_layers))
        ids = jnp.zeros((2,), jnp.int32)
        fn = jax.jit(lambda p, k, v: C.decode(
            p, cfg, ids, ids, k, v, jnp.zeros((2, 2), jnp.int32), ids + 4,
        ))
        with layer_bodies_called() as bodies:
            text = fn.lower(params, k_cache, v_cache).as_text(debug_info=True)
        return text, len(bodies)

    cfg = C.ConvMoeConfig.from_hf_dict(HF)
    text6, called = lowered(cfg)
    assert called == 3
    kinds = list(HF["layer_types"])
    deep = C.ConvMoeConfig.from_hf_dict(dict(
        HF, num_hidden_layers=10, layer_types=kinds + kinds[2:],
    ))
    text10, called10 = lowered(deep)
    assert called10 == 3
    assert private_functions(text10) == private_functions(text6)
    # the named scopes cross the call boundary into the operations' locations
    for scope in ("conv.mix", "moe.route", "moe.experts"):
        assert scope in text6, scope


def test_the_mamba2_expert_family_has_three_bodies_whatever_its_depth():
    """`models/ssm2_moe.py`: a Mamba-2 layer, an expert layer (which keeps
    nothing) and an attention layer are three bodies a program, lowered once
    each, told apart by what a layer is and never by its index: a model of
    twice the depth, its literal pattern repeated, lowers to as many private
    functions; and the spans of step 6 are in the operations' locations."""
    from dynamo_tpu.models import ssm2_moe as S
    from tests.test_ssm2_moe import HF, caches

    def lowered(cfg):
        jax.clear_caches()
        params = S.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        k_cache, v_cache = caches(cfg, lanes=2, blocks=8)
        ids = jnp.zeros((2,), jnp.int32)
        fn = jax.jit(lambda p, k, v: S.decode(
            p, cfg, ids, ids, k, v, jnp.zeros((2, 2), jnp.int32), ids + 4,
        ))
        with layer_bodies_called() as bodies:
            text = fn.lower(params, k_cache, v_cache).as_text(debug_info=True)
        return text, sorted(b[1] for b in bodies)

    cfg = S.Ssm2MoeConfig.from_hf_dict(HF)
    text6, called = lowered(cfg)
    assert called == ["_attn_decode_layer", "_experts", "_mamba_decode_layer"]
    pattern = HF["hybrid_override_pattern"]
    deep = S.Ssm2MoeConfig.from_hf_dict(dict(
        HF, num_hidden_layers=2 * len(pattern), hybrid_override_pattern=2 * pattern,
    ))
    text12, called12 = lowered(deep)
    assert called12 == called
    assert private_functions(text12) == private_functions(text6)
    for scope in ("ssm2.mix", "ssm2.update", "moe.route", "moe.latent", "moe.experts", "moe.shared"):
        assert scope in text6, scope

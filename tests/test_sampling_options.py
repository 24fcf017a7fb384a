"""Sampling-option completeness: penalties, per-request seed, logprobs,
min_tokens, n>1 fanout (round-2 VERDICT item #2 — the reference validates
these in openai/validate.rs:95-125; here they must actually change the
sampled stream)."""

import asyncio

import jax
import numpy as np
import pytest

from dynamo_tpu.ops.sampling import (
    apply_penalties,
    make_key_data,
    sample_tokens,
    sample_tokens_full,
)
from dynamo_tpu.pipeline.context import Context
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

from tests.test_jax_engine import collect, greedy_request, make_engine


# ------------------------------------------------------------------ unit


def test_apply_penalties_semantics():
    import jax.numpy as jnp

    V = 10
    logits = jnp.zeros((1, V), jnp.float32).at[0, 3].set(2.0).at[0, 4].set(-1.0)
    # hist: prompt = [3], generated = [4, 4]
    hist = jnp.array([[3, 4, 4, 0]], jnp.int32)
    hist_len = jnp.array([3], jnp.int32)
    prompt_len = jnp.array([1], jnp.int32)
    out = apply_penalties(
        logits, hist, hist_len, prompt_len,
        jnp.array([0.5], jnp.float32),  # freq
        jnp.array([0.25], jnp.float32),  # pres
        jnp.array([2.0], jnp.float32),  # rep
    )
    out = np.asarray(out)[0]
    # token 4: generated twice -> freq 0.5*2 + pres 0.25 subtracted, then
    # rep on the (already negative) value multiplies by 2
    assert out[4] == pytest.approx((-1.0 - 1.0 - 0.25) * 2.0)
    # token 3: prompt-only -> no freq/pres, rep divides the positive logit
    assert out[3] == pytest.approx(2.0 / 2.0)
    # untouched token
    assert out[0] == pytest.approx(0.0)


def test_per_row_key_streams_deterministic():
    import jax.numpy as jnp

    logits = jnp.asarray(np.random.default_rng(0).normal(size=(2, 64)), jnp.float32)
    temps = jnp.ones(2, jnp.float32)
    ones = jnp.ones(2, jnp.float32)
    zeros = jnp.zeros(2, jnp.int32)
    keys_a = np.stack([make_key_data(7, 0), make_key_data(7, 1)])
    toks1 = np.asarray(sample_tokens(logits, None, temps, ones, zeros, keys=jnp.asarray(keys_a)))
    toks2 = np.asarray(sample_tokens(logits, None, temps, ones, zeros, keys=jnp.asarray(keys_a)))
    assert (toks1 == toks2).all()  # same streams -> same draw
    keys_b = np.stack([make_key_data(8, 0), make_key_data(8, 1)])
    many_a = [
        int(
            sample_tokens(
                logits, None, temps, ones, zeros,
                keys=jnp.asarray(np.stack([make_key_data(7, c), make_key_data(7, c + 1)])),
            )[0]
        )
        for c in range(8)
    ]
    many_b = [
        int(
            sample_tokens(
                logits, None, temps, ones, zeros,
                keys=jnp.asarray(np.stack([make_key_data(8, c), make_key_data(8, c + 1)])),
            )[0]
        )
        for c in range(8)
    ]
    assert many_a != many_b  # different stream ids -> different sequences


def test_sample_tokens_full_logprob_surface():
    import jax.numpy as jnp

    logits = jnp.asarray(np.random.default_rng(1).normal(size=(3, 32)), jnp.float32)
    toks, lps, tids, tlps = sample_tokens_full(
        logits, jax.random.PRNGKey(0),
        jnp.zeros(3, jnp.float32),  # greedy
        jnp.ones(3, jnp.float32), jnp.zeros(3, jnp.int32),
        jnp.ones(3, bool), num_top=4,
    )
    toks, lps, tids, tlps = map(np.asarray, (toks, lps, tids, tlps))
    assert (lps <= 0).all()
    # greedy: chosen token is the argmax == first top entry, logprob equal
    assert (tids[:, 0] == toks).all()
    np.testing.assert_allclose(lps, tlps[:, 0], rtol=1e-5)
    # top list is sorted descending
    assert (np.diff(tlps, axis=1) <= 1e-6).all()


# ------------------------------------------- the pool under its conditional


def _two_draw_sample_tokens(logits, rng, temperature, top_p, top_k, keys=None):
    """`sample_tokens` as it was before the candidate pool moved under a
    `lax.cond` (PR 32): the pool and both draws for every row of every
    call, the choice by `where`. The reference of the identity tests."""
    import jax.numpy as jnp

    from dynamo_tpu.ops.sampling import _filtered_candidates

    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    vals, idx = _filtered_candidates(scaled, top_p, top_k)
    wide_nucleus = (top_k <= 0) & (top_p >= 0.99) & (temperature > 1.25)
    unrestricted = ((top_k <= 0) & (top_p >= 1.0)) | wide_nucleus
    if keys is not None:
        def draw(kd, pool_lg, full_lg):
            k = jax.random.wrap_key_data(kd.astype(jnp.uint32))
            return (
                jax.random.categorical(k, pool_lg),
                jax.random.categorical(k, full_lg),
            )

        choice, full_choice = jax.vmap(draw)(keys, vals, scaled)
    else:
        choice = jax.random.categorical(rng, vals, axis=-1)
        full_choice = jax.random.categorical(rng, scaled, axis=-1)
    pool_sampled = jnp.take_along_axis(
        idx, choice[:, None].astype(jnp.int32), axis=-1
    )[:, 0]
    sampled = jnp.where(
        unrestricted, full_choice.astype(jnp.int32), pool_sampled.astype(jnp.int32)
    )
    return jnp.where(temperature <= 0.0, greedy_ids, sampled)


_LANES = 8

# name -> (vocabulary, temperature, top_p, top_k), a scalar for every lane
# or one value a lane
DRAW_CASES = {
    "all_unrestricted_at_0.7": (1024, 0.7, 1.0, 0),
    "all_greedy": (1024, 0.0, 1.0, 0),
    "greedy_lanes_that_carry_a_top_k": (
        1024, [0.0, 0.7, 0.0, 0.7, 0.7, 0.0, 0.7, 0.7], 1.0,
        [5, 0, 40, 0, 0, 1, 0, 0],
    ),
    "one_top_k_lane_among_unrestricted": (
        1024, 0.7, 1.0, [0, 0, 0, 20, 0, 0, 0, 0],
    ),
    "one_top_p_0.9_lane": (
        1024, 0.7, [1.0, 1.0, 0.9, 1.0, 1.0, 1.0, 1.0, 1.0], 0,
    ),
    "top_k_and_top_p_together": (
        1024, [0.7, 1.0, 0.7, 0.0, 1.2, 0.7, 0.7, 0.3],
        [0.8, 1.0, 0.9, 0.5, 0.95, 1.0, 0.7, 0.9],
        [20, 0, 300, 7, 50, 3, 0, 1],
    ),
    "wide_nucleus_above_1.25": (
        1024, [1.5, 1.3, 1.25, 2.0, 0.7, 1.5, 1.5, 0.0],
        [0.99, 0.995, 0.99, 0.98, 0.99, 1.0, 0.99, 0.99],
        [0, 0, 0, 0, 0, 0, 4, 0],
    ),
    "vocabulary_smaller_than_the_pool": (
        100, [0.7, 0.0, 1.0, 0.7, 0.7, 1.3, 0.7, 0.7],
        [1.0, 1.0, 0.9, 1.0, 0.5, 1.0, 1.0, 0.8],
        [0, 3, 0, 150, 0, 0, 10, 99],
    ),
}


def _draw_inputs(case: str, seed: int = 0):
    import jax.numpy as jnp

    V, temp, top_p, top_k = DRAW_CASES[case]
    lanes = lambda v, dtype: jnp.asarray(np.broadcast_to(v, (_LANES,)), dtype)
    logits = 3.0 * np.random.default_rng(seed).normal(size=(_LANES, V))
    keys = np.stack([make_key_data(11 + i, 5 * i + seed) for i in range(_LANES)])
    return (
        jnp.asarray(logits, jnp.float32), lanes(temp, jnp.float32),
        lanes(top_p, jnp.float32), lanes(top_k, jnp.int32), jnp.asarray(keys),
    )


@pytest.mark.parametrize("per_lane_keys", [True, False], ids=["keys", "rng"])
@pytest.mark.parametrize("case", list(DRAW_CASES))
def test_sample_tokens_is_the_two_draw_sampler_bit_for_bit(case, per_lane_keys):
    """Whatever the batch holds, every lane gets the token the sampler gave
    it when the pool was computed for all of them: an unrestricted lane its
    full-vocabulary draw, a restricted one the pool's, a greedy one its
    argmax, each from its own key row."""
    import jax.numpy as jnp

    for seed in range(3):
        logits, temp, top_p, top_k, keys = _draw_inputs(case, seed)
        rng = jax.random.PRNGKey(seed)
        kw = {"keys": keys} if per_lane_keys else {}
        want = _two_draw_sample_tokens(logits, rng, temp, top_p, top_k, **kw)
        got = jax.jit(sample_tokens)(logits, rng, temp, top_p, top_k, **kw)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        if not per_lane_keys:
            continue
        # the log-prob surface hangs on the token alone and is as it was
        full = sample_tokens_full(
            logits, None, temp, top_p, top_k, jnp.ones(_LANES, bool), keys=keys
        )
        for g, w in zip(full, (want, *_surface(logits, want))):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize(
    "neighbour", [{"top_k": 20}, {"top_p": 0.9}, {"temperature": 0.0, "top_k": 5}],
    ids=["top_k", "top_p", "greedy_with_top_k"],
)
def test_a_lanes_token_does_not_depend_on_its_neighbours(neighbour):
    """What per-lane keys promise: an unrestricted lane draws the same
    token whether or not the lane beside it restricts its draw (and so
    whether or not the batch took the pool's branch)."""
    logits, temp, top_p, top_k, keys = _draw_inputs("all_unrestricted_at_0.7")
    alone = np.asarray(sample_tokens(logits, None, temp, top_p, top_k, keys=keys))
    temp = temp.at[3].set(neighbour.get("temperature", 0.7))
    top_p = top_p.at[3].set(neighbour.get("top_p", 1.0))
    top_k = top_k.at[3].set(neighbour.get("top_k", 0))
    beside = np.asarray(sample_tokens(logits, None, temp, top_p, top_k, keys=keys))
    others = np.arange(_LANES) != 3
    np.testing.assert_array_equal(beside[others], alone[others])


def _surface(logits, tokens, num_top: int = 20):
    """(chosen, top_ids, top_lps) as `sample_tokens_full` gave them for
    every lane of every call until PR 53."""
    import jax.numpy as jnp

    logz = jax.nn.log_softmax(logits, axis=-1)
    top_lps, top_ids = jax.lax.top_k(logz, num_top)
    chosen = jnp.take_along_axis(logz, tokens[:, None], axis=-1)[:, 0]
    return chosen, top_ids, top_lps


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if hasattr(x, "eqns"):  # Jaxpr
                yield x
            elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):  # ClosedJaxpr
                yield x.jaxpr


def _primitives(jaxpr, out=None) -> list[str]:
    """Every primitive of a jaxpr and of what it calls, by name."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for sub in _sub_jaxprs(eqn):
            _primitives(sub, out)
    return out


@pytest.mark.parametrize("per_lane_keys", [True, False], ids=["keys", "rng"])
def test_the_pool_is_inside_one_conditional(per_lane_keys):
    """The cost, not only the result: one `cond`; the `top_k` of the pool,
    the cumulative sum and the pool's draw are in one branch and nowhere
    else; the other branch hands the full-vocabulary draw through."""
    from dynamo_tpu.ops.sampling import SAMPLE_CANDIDATES

    logits, temp, top_p, top_k, keys = _draw_inputs("all_unrestricted_at_0.7")
    kw = {"keys": keys} if per_lane_keys else {}
    jaxpr = jax.make_jaxpr(
        lambda *a: sample_tokens(a[0], jax.random.PRNGKey(0), *a[1:], **kw)
    )(logits, temp, top_p, top_k).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1 and _primitives(jaxpr).count("cond") == 1
    selection = {"top_k", "sort", "cumsum", "cumlogsumexp", "cummax"}
    outside = [
        e.primitive.name for e in jaxpr.eqns if e.primitive.name != "cond"
    ]
    for e in jaxpr.eqns:
        if e.primitive.name != "cond":
            for sub in _sub_jaxprs(e):
                _primitives(sub, outside)
    assert not selection & set(outside), outside
    branches = [_primitives(b.jaxpr) for b in conds[0].params["branches"]]
    with_pool = [b for b in branches if "top_k" in b]
    without = [b for b in branches if "top_k" not in b]
    assert len(with_pool) == 1 and len(without) == 1
    assert "cumsum" in with_pool[0]
    assert not selection & set(without[0])
    assert not {"random_bits", "threefry2x32", "argmax"} & set(without[0])
    pool_top_k = [
        e for b in conds[0].params["branches"]
        for e in b.jaxpr.eqns if e.primitive.name == "top_k"
    ]
    assert [e.params["k"] for e in pool_top_k] == [SAMPLE_CANDIDATES]


ASKING = {"no_lane_asks": [], "one_of_several_asks": [2], "all_ask": range(8)}


@pytest.mark.parametrize("per_lane_keys", [True, False], ids=["keys", "rng"])
@pytest.mark.parametrize("asking", list(ASKING))
def test_the_logprob_surface_is_computed_where_a_lane_asked(asking, per_lane_keys):
    """The result: every lane's token is the one the sampler gave it when
    the surface was computed for all (the pool's reference again), whoever
    asks; the surface is the parent's for EVERY lane where one lane asks
    and zeros where none does. The cost: the top 20, `log_softmax`'s
    reductions and the chosen id's gather sit in one branch of one `cond`
    and nowhere else, and the other branch computes nothing."""
    import jax.numpy as jnp

    want_lps = np.zeros(_LANES, bool)
    want_lps[list(ASKING[asking])] = True
    want_lps = jnp.asarray(want_lps)
    # lanes of every kind in one batch: greedy, unrestricted, top_k, top_p
    logits, temp, top_p, top_k, keys = _draw_inputs("all_unrestricted_at_0.7", 4)
    temp = temp.at[0].set(0.0)
    top_k, top_p = top_k.at[5].set(20), top_p.at[6].set(0.9)
    rng = jax.random.PRNGKey(4)
    kw = {"keys": keys} if per_lane_keys else {}
    tokens = _two_draw_sample_tokens(logits, rng, temp, top_p, top_k, **kw)
    got = jax.jit(sample_tokens_full)(logits, rng, temp, top_p, top_k, want_lps, **kw)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(tokens))
    surface = _surface(logits, tokens)
    for g, w in zip(got[1:], surface):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w) if ASKING[asking] else np.zeros(w.shape)
        )
    jaxpr = jax.make_jaxpr(
        lambda *a: sample_tokens_full(a[0], rng, *a[1:], **kw)
    )(logits, temp, top_p, top_k, want_lps).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 2 and _primitives(jaxpr).count("cond") == 2  # the pool's too
    top_20 = lambda eqns: [
        e for e in eqns if e.primitive.name == "top_k" and e.params["k"] == 20
    ]
    holds = [
        [len(top_20(b.jaxpr.eqns)) for b in c.params["branches"]] for c in conds
    ]
    assert sorted(map(sorted, holds)) == [[0, 0], [0, 1]], holds
    (surface_cond,) = [c for c, h in zip(conds, holds) if sum(h)]
    computes, nothing = sorted(
        (_primitives(b.jaxpr) for b in surface_cond.params["branches"]),
        key=lambda b: "top_k" not in b,
    )
    assert {"top_k", "reduce_max", "exp", "reduce_sum", "log", "gather"} <= set(computes)
    assert set(nothing) <= {"broadcast_in_dim"}, nothing
    outside = [e.primitive.name for e in jaxpr.eqns if e.primitive.name != "cond"]
    for e in jaxpr.eqns:
        if e.primitive.name != "cond":
            for sub in _sub_jaxprs(e):
                _primitives(sub, outside)
    # beside the conditionals: the argmax, the draw and the predicates; no
    # selection and no reduction of `log_softmax` over the vocabulary
    assert not {"top_k", "reduce_max", "exp", "reduce_sum", "gather"} & set(outside), outside


def test_draw_restrictions_is_one_rule_for_host_and_device():
    """The engine's counter asks numpy arrays what `sample_tokens` asks the
    device's: the same function, the same answers."""
    import jax.numpy as jnp

    from dynamo_tpu.ops.sampling import draw_restrictions

    for case in DRAW_CASES:
        _, temp, top_p, top_k, _ = _draw_inputs(case)
        on_host = draw_restrictions(*map(np.asarray, (temp, top_p, top_k)))
        on_device = draw_restrictions(temp, top_p, top_k)
        np.testing.assert_array_equal(on_host[0], np.asarray(on_device[0]))
        assert bool(on_host[1]) == bool(on_device[1]), case
    needs = {c: bool(draw_restrictions(*_draw_inputs(c)[1:4])[1]) for c in DRAW_CASES}
    assert needs == {
        "all_unrestricted_at_0.7": False, "all_greedy": False,
        "greedy_lanes_that_carry_a_top_k": False,
        "one_top_k_lane_among_unrestricted": True, "one_top_p_0.9_lane": True,
        "top_k_and_top_p_together": True, "wide_nucleus_above_1.25": True,
        "vocabulary_smaller_than_the_pool": True,
    }
    # the log-prob surface's predicate likewise: one function for both
    from dynamo_tpu.ops.sampling import surface_wanted

    for asking, lanes in ASKING.items():
        want_lps = np.zeros(_LANES, bool)
        want_lps[list(lanes)] = True
        on_host, on_device = surface_wanted(want_lps), surface_wanted(jnp.asarray(want_lps))
        assert bool(on_host) == bool(on_device) == bool(len(lanes)), asking


# ---------------------------------------------------------------- engine


def sampled_request(prompt, max_tokens, **sampling):
    return PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(**sampling),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )


async def test_seed_determinism_across_batching():
    """Same seed + prompt => same output, alone or batched with others."""
    engine = make_engine(max_batch=4)
    prompt = [3, 1, 4, 1, 5]
    req = lambda: sampled_request(prompt, 8, temperature=1.0, seed=42)
    alone, _ = await collect(engine, req())
    # now run the same seeded request while unseeded traffic shares the batch
    others = [
        collect(engine, sampled_request([9, 2, 6], 8, temperature=1.0))
        for _ in range(3)
    ]
    batched_task = collect(engine, req())
    results = await asyncio.gather(batched_task, *others)
    batched = results[0][0]
    assert alone == batched
    # different seed differs (overwhelmingly likely over 8 tokens, V=64)
    other, _ = await collect(engine, sampled_request(prompt, 8, temperature=1.0, seed=43))
    assert other != alone
    await engine.close()


async def test_penalties_change_output():
    engine = make_engine(max_batch=2)
    prompt = [7, 7, 7, 7, 11, 11]
    plain, _ = await collect(engine, greedy_request(prompt, 12))
    pen, _ = await collect(
        engine,
        PreprocessedRequest(
            token_ids=prompt,
            sampling=SamplingOptions(
                greedy=True, frequency_penalty=2.0, presence_penalty=2.0,
                repetition_penalty=1.5,
            ),
            stop=StopConditions(max_tokens=12, ignore_eos=True),
        ),
    )
    assert len(pen) == 12
    assert pen != plain  # penalties must actually steer the argmax
    # greedy without penalties is repetition-prone on a tiny random model;
    # the penalized stream must repeat strictly less
    def max_run(xs):
        best = run = 1
        for a, b in zip(xs, xs[1:]):
            run = run + 1 if a == b else 1
            best = max(best, run)
        return best

    assert len(set(pen)) >= len(set(plain))
    await engine.close()


async def test_logprobs_populated():
    engine = make_engine()
    req = PreprocessedRequest(
        token_ids=[2, 4, 6],
        sampling=SamplingOptions(greedy=True, logprobs=True, top_logprobs=3),
        stop=StopConditions(max_tokens=4, ignore_eos=True),
    )
    outs = []
    async for out in engine.generate(req, Context()):
        if out.token_ids:
            outs.append(out)
    # an item carries what one dispatch produced: its lists are aligned
    assert sum(len(out.token_ids) for out in outs) == 4
    for out in outs:
        assert out.log_probs is not None and out.top_logprobs is not None
        assert len(out.log_probs) == len(out.top_logprobs) == len(out.token_ids)
        for tok, lp, tops in zip(out.token_ids, out.log_probs, out.top_logprobs):
            assert lp <= 0.0
            assert len(tops) == 3
            # greedy: the chosen token leads the top list
            assert tops[0][0] == tok
            assert tops[0][1] == pytest.approx(lp, rel=1e-5)
    await engine.close()


async def test_logprobs_beside_streams_that_asked_for_none():
    """A stream that asks for `top_logprobs` reads the same log-probs
    beside streams that do not as when it runs alone (its first token from
    a packed prefill among theirs, the others from horizons whose batch
    holds it), and the others carry none; when the asking stream has ended,
    the lanes left take the branch that computes nothing and their tokens
    are what they are without it."""
    engine = make_engine(max_batch=4)

    async def items(req):
        return [o async for o in engine.generate(req, Context()) if o.token_ids]

    asks = lambda: PreprocessedRequest(
        token_ids=[2, 4, 6],
        sampling=SamplingOptions(temperature=0.7, seed=5, logprobs=True, top_logprobs=3),
        stop=StopConditions(max_tokens=6, ignore_eos=True),
    )
    quiet = lambda: [
        sampled_request([3, 1, 4, 1, 5], 14, temperature=0.7, seed=1),
        greedy_request([9, 2, 6], 14),
    ]
    try:
        alone = await items(asks())
        others_alone = await asyncio.gather(*map(items, quiet()))
        beside, *others = await asyncio.gather(items(asks()), *map(items, quiet()))
        flat = lambda outs, field: [x for o in outs for x in getattr(o, field)]
        assert flat(beside, "token_ids") == flat(alone, "token_ids")
        assert len(flat(alone, "log_probs")) == 6
        np.testing.assert_allclose(
            flat(beside, "log_probs"), flat(alone, "log_probs"), rtol=1e-5
        )
        for got, want in zip(flat(beside, "top_logprobs"), flat(alone, "top_logprobs")):
            assert [t for t, _ in got] == [t for t, _ in want] and len(got) == 3
            np.testing.assert_allclose(
                [lp for _, lp in got], [lp for _, lp in want], rtol=1e-5
            )
        assert any(lp < 0.0 for lp in flat(alone, "log_probs"))  # not the zeros
        for outs, outs_alone in zip(others, others_alone):
            assert all(o.log_probs is None and o.top_logprobs is None for o in outs)
            assert flat(outs, "token_ids") == flat(outs_alone, "token_ids")
            assert len(flat(outs, "token_ids")) == 14
        counted = engine.stats.goodput.summary()["sampler"]
        assert 0 < counted["logprob_dispatches"] < counted["dispatches"]
    finally:
        await engine.close()


async def test_packed_prefill_parity_with_sequential():
    """Batched (packed) prefill admission must produce identical greedy
    outputs to one-at-a-time serving (segment masking = exact causal
    attention per prompt)."""
    engine = make_engine(max_batch=4)
    prompts = [[5, 9, 17, 23], [40, 2, 7], [11, 13, 19, 29, 31]]
    sequential = []
    for p in prompts:
        toks, _ = await collect(engine, greedy_request(p, 5))
        sequential.append(toks)
    # concurrent: all three admitted in one engine iteration -> one packed
    # prefill program covers them
    results = await asyncio.gather(
        *(collect(engine, greedy_request(p, 5)) for p in prompts)
    )
    for (toks, reason), want in zip(results, sequential):
        assert reason is FinishReason.LENGTH
        assert toks == want
    await engine.close()


async def test_min_tokens_suppresses_eos():
    engine = make_engine()
    prompt = [5, 9, 17]
    # discover the greedy continuation, then declare its SECOND token as eos
    toks, _ = await collect(engine, greedy_request(prompt, 6))
    eos_tok = toks[1]
    base = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(greedy=True),
        stop=StopConditions(max_tokens=6),
        eos_token_ids=[eos_tok],
    )
    stopped, reason = await collect(engine, base)
    assert reason is FinishReason.EOS
    assert len(stopped) < 6
    forced = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(greedy=True),
        stop=StopConditions(max_tokens=6, min_tokens=6),
        eos_token_ids=[eos_tok],
    )
    full, reason2 = await collect(engine, forced)
    assert reason2 is FinishReason.LENGTH
    assert len(full) == 6
    await engine.close()


@pytest.mark.parametrize("beside", ["one_top_p", "none", "one_logprobs"])
async def test_ledger_counts_the_dispatches_that_needed_the_pool(beside):
    """`/debug/goodput`'s `sampler` slot (the ledger's summary) and its
    Prometheus twin: two lanes that draw over the whole vocabulary, and
    for a few tokens one with `top_p` 0.9 beside them, or one that asks
    for log-probs (`logprob_dispatches`: the dispatches whose steps
    computed the log-prob surface)."""
    from prometheus_client import generate_latest

    from dynamo_tpu.http.metrics import ServiceMetrics

    engine = make_engine(max_batch=4)
    try:
        reqs = [
            sampled_request([3, 1, 4, 1, 5], 24, temperature=0.7, seed=1),
            sampled_request([9, 2, 6, 5], 24, temperature=0.7, seed=2),
        ]
        extra = {"one_top_p": {"top_p": 0.9}, "one_logprobs": {"logprobs": True}}
        if beside in extra:
            reqs.append(sampled_request(
                [2, 7, 1, 8], 4, temperature=0.7, seed=3, **extra[beside]
            ))
        out = await asyncio.gather(*(collect(engine, r) for r in reqs))
        assert [len(t) for t, _ in out] == [24, 24, 4][: len(reqs)]
        summary = engine.stats.goodput.summary()
        counted, by_label = summary["sampler"], summary["steps_by_label"]
        assert counted["dispatches"] == sum(
            v["count"] for k, v in by_label.items() if not k.startswith("prefill")
        ) > 0
        for name, case in (
            ("pool_dispatches", "one_top_p"), ("logprob_dispatches", "one_logprobs"),
        ):
            if beside == case:
                assert 0 < counted[name] < counted["dispatches"]
            else:
                assert counted[name] == 0
        metrics = ServiceMetrics()
        metrics.attach_goodput({"goodput": engine.stats.goodput}, None)
        text = generate_latest(metrics.registry).decode()
        for name, value in counted.items():
            assert f"dyn_llm_sampler_{name}_total {float(value)}" in text
    finally:
        await engine.close()

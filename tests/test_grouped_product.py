"""The Pallas grouped product (`ops/grouped_product.py`) against
`lax.ragged_dot`, interpreted on the CPU.

On the chip the two agree bit for bit (an expert's whole `k` is one tile:
`benchmarks/grouped_product_benchtop.py`); here XLA's CPU product and the
interpreter's `dot` sum a row in different orders, so a result may differ by
one bfloat16 step: the tolerance is two steps of the dtype, set from it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dynamo_tpu.ops import moe
from dynamo_tpu.ops.basics import forms_traced
from dynamo_tpu.ops.grouped_product import _tiling, grouped_product

BF16 = jnp.bfloat16
STEP = 2.0 ** -7  # two steps of bfloat16's 8 significant bits


def _operands(R, E, K, N, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    lhs = jax.random.normal(k1, (R, K), BF16)
    rhs = (jax.random.normal(k2, (E, K, N)) * K ** -0.5).astype(BF16)
    return lhs, rhs


def _spread(R, E, live, seed):
    """`live` rows over `E` groups as a decode step's routing spreads them."""
    rng = np.random.default_rng(seed)
    return np.bincount(rng.integers(0, E, live), minlength=E)


# name -> (R, E, K, N, group sizes). The cells' decode shapes, 64 lanes x the
# experts a token, cut in experts and widths: LFM2's 256 x [32, 2048, 1792],
# JoyAI's 512 x [256, 2048, 768], Nemotron's 1,408 x [128, 1024, 2688] (a
# quarter of its rows fall in a held group; 2,688 is 21 columns of 128 lanes,
# of which a block takes 7) and a down projection (k wider than n)
PRODUCTS = {
    "lfm2's decode shape, cut": (256, 8, 256, 256, _spread(256, 8, 152, 1)),
    "joyai's decode shape, cut": (512, 32, 256, 128, _spread(512, 32, 96, 2)),
    "nemotron's decode shape, cut": (1408, 16, 128, 384, _spread(1408, 16, 352, 3)),
    "a down projection": (256, 8, 384, 256, _spread(256, 8, 200, 4)),
    "empty groups at the front": (256, 8, 128, 128, [0, 0, 0, 40, 9, 30, 1, 20]),
    "empty groups in the middle": (256, 8, 128, 128, [17, 3, 0, 0, 0, 60, 0, 5]),
    "empty groups at the end": (256, 8, 128, 128, [5, 90, 11, 2, 0, 0, 0, 0]),
    "a group that crosses a row tile": (384, 4, 128, 128, [100, 90, 150, 10]),
    "a group of several row tiles": (512, 4, 128, 128, [3, 300, 0, 20]),
    "rows behind the last group": (256, 8, 128, 128, [1, 0, 2, 0, 0, 4, 0, 0]),
    "no row at all": (128, 4, 128, 128, [0, 0, 0, 0]),
    "one expert takes every row": (256, 8, 128, 256, [0, 0, 0, 0, 0, 256, 0, 0]),
    "every row tile full": (256, 2, 128, 128, [128, 128]),
}


@pytest.mark.parametrize("case", list(PRODUCTS))
def test_the_kernel_is_ragged_dot(case):
    R, E, K, N, sizes = PRODUCTS[case]
    lhs, rhs = _operands(R, E, K, N)
    sizes = jnp.asarray(sizes, jnp.int32)
    live = int(sizes.sum())
    assert live <= R and _tiling(R, K, N, 2) is not None
    with forms_traced() as counted:
        got = grouped_product(lhs, rhs, sizes, impl="pallas_interpret")
    assert counted == {"grouped_product_kernel": 1}
    want = lax.ragged_dot(lhs, rhs, sizes)
    assert got.dtype == want.dtype and got.shape == want.shape
    # rows behind the last group are not computed by either and not compared
    np.testing.assert_allclose(
        np.asarray(got[:live], np.float32), np.asarray(want[:live], np.float32),
        rtol=STEP, atol=STEP,
    )


def _layer(E, D, F, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    draw = lambda k, shape: (jax.random.normal(k, shape) * shape[1] ** -0.5).astype(BF16)
    return draw(ks[0], (E, D, F)), draw(ks[1], (E, D, F)), draw(ks[2], (E, F, D))


@pytest.mark.parametrize("held", [None, (4, 8)], ids=["every expert", "a held range"])
@pytest.mark.parametrize("masked", [False, True], ids=["all lanes", "a valid mask"])
@pytest.mark.parametrize("form", ["swiglu", "relu2"])
def test_dropless_experts_through_the_kernel(form, masked, held):
    """Both forms of `dropless_experts`, with padding lanes and with a layer
    that holds experts 4 to 11 of a 16-wide router: the kernel's result is the
    XLA form's within the dtype's step, the group sizes are the same, and the
    layer's products are counted by the form they took."""
    T, k, D, F = 64, 4, 256, 128
    E = 16 if held is None else held[1]
    first_held = None if held is None else held[0]
    wg, wu, wd = _layer(E, D, F)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], (T, D), BF16)
    idx = jax.random.randint(ks[1], (T, k), 0, 16)
    weights = jax.nn.softmax(jax.random.normal(ks[2], (T, k)))
    valid = (jnp.arange(T) % 5 != 0) if masked else None
    args = (x, idx, weights, None if form == "relu2" else wg, wu, wd, valid)
    kw = dict(first_held=first_held, form=form)
    products = 2 if form == "relu2" else 3
    with forms_traced() as counted:
        want, want_sizes = moe.dropless_experts(*args, **kw, impl="xla")
    assert counted == {"grouped_product_xla": products}
    with forms_traced() as counted:
        got, got_sizes = moe.dropless_experts(*args, **kw, impl="pallas_interpret")
    assert counted == {"grouped_product_kernel": products}
    np.testing.assert_array_equal(np.asarray(got_sizes), np.asarray(want_sizes))
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=STEP, atol=STEP * scale
    )
    if masked:  # a padding token is given to no expert: exact zeros
        assert np.all(np.asarray(got)[~np.asarray(valid)] == 0.0)


@pytest.mark.parametrize("why,R,K,N,dtypes", [
    ("a k that is no multiple of 128 lanes", 256, 192, 128, (BF16, BF16)),
    ("an n that is no multiple of 128 lanes", 256, 128, 96, (BF16, BF16)),
    ("rows that are no whole tiles", 40, 128, 128, (BF16, BF16)),
    ("a k whose narrowest block passes 4 MiB", 128, 16512, 128, (BF16, BF16)),
    ("operands of two dtypes", 256, 128, 128, (jnp.float32, BF16)),
])
def test_an_untileable_product_falls_to_ragged_dot(why, R, K, N, dtypes):
    """By the input alone: the Pallas form is asked for, the shape cannot be
    tiled, and the program traced is `lax.ragged_dot`'s, counted as such."""
    lhs = jax.ShapeDtypeStruct((R, K), dtypes[0])
    rhs = jax.ShapeDtypeStruct((4, K, N), dtypes[1])
    sizes = jax.ShapeDtypeStruct((4,), jnp.int32)
    with forms_traced() as counted:
        got = jax.make_jaxpr(lambda a, b, c: grouped_product(a, b, c, impl="pallas"))(lhs, rhs, sizes)
    assert counted == {"grouped_product_xla": 1}
    assert str(got) == str(jax.make_jaxpr(lax.ragged_dot)(lhs, rhs, sizes))


def test_the_tiles_come_from_the_shapes():
    """The cells' products: rows in tiles of 128, an expert's whole k, the
    widest whole columns of n whose block of weights is within 4 MiB."""
    assert _tiling(256, 2048, 1792, 2) == (128, 2048, 896)  # LFM2's gate and up
    assert _tiling(256, 1792, 2048, 2) == (128, 1792, 1024)  # its down
    assert _tiling(512, 2048, 768, 2) == (128, 2048, 768)  # JoyAI's
    assert _tiling(512, 768, 2048, 2) == (128, 768, 2048)
    assert _tiling(1408, 1024, 2688, 2) == (128, 1024, 896)  # Nemotron's
    assert _tiling(1408, 2688, 1024, 2) == (128, 2688, 512)
    assert _tiling(11264, 1024, 2688, 2) == (128, 1024, 896)  # a chunk's rows
    assert _tiling(256, 2048, 1792, 4) == (128, 2048, 256)  # float32 weights

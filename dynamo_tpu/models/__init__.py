"""Model zoo: pure-functional JAX implementations (params are pytrees, every
forward is jit-safe) designed around the paged KV cache and GSPMD sharding.

A family is one module that holds its config class and its forward
functions (`init_params`, `param_count`, `prefill`, `prefill_chunk`,
`prefill_packed`, `decode`, ...) under the same names and signatures; the
runner asks `forward_for(config)` for the module and knows no family. A
config declares what each of its layers keeps (`layer_cache_kinds`): rows per
token in hashable blocks (keys and values by head, or one latent plane), or
one slot of fixed size a sequence (a recurrent state); the allocator, the
block budget and the row scatter derive from the declaration.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import importlib
import json
import math
import os
import sys
import threading
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.basics import forms_traced


@dataclass(frozen=True)
class CacheKind:
    """What one layer keeps.

    Per cached token, in blocks: `kv_heads`, two planes (keys, values) of
    `heads` rows of `width` values, a row one KV head, or `pack` narrow ones
    side by side where a head is narrower than a tile's 128 lanes (64-wide
    heads in pairs: `ops/attention.py`); `latent`, one plane of one row:
    `width` values (compressed latent, then the shared rope key), stored
    `stored_width` wide (zeros behind).
    Per sequence, whatever its length: `recurrent`, no rows per token; one
    slot a lane, `slot` its arrays as (shape, dtype name) pairs: one array
    (a short convolution's tail) or two (a state-space layer's state and
    tail). The first rides where a paged layer's keys do, the second where
    its values do; a slot of one array leaves None there.
    Nothing at all: `nothing`, a layer that is a feed-forward alone (an
    expert mixer of a model whose layers are one mixer each); None rides in
    both places.
    `window`: a paged layer that attends over the last `window` positions
    alone keeps no more than those: its blocks that have wholly left the
    window go back to the pool (`page_groups`). None: it keeps every
    position."""

    name: str  # "kv_heads" | "latent" | "recurrent" | "nothing"
    planes: int
    heads: int
    width: int
    stored_width: int
    slot: tuple = ()
    pack: int = 1
    window: Optional[int] = None

    def stored_values_per_token(self, tp: int = 1) -> int:
        return self.planes * max(1, self.heads // tp) * self.stored_width

    @property
    def slot_bytes(self) -> int:
        """Bytes of one lane's slot (0 for a paged kind)."""
        return sum(
            math.prod(shape) * jnp.dtype(dtype).itemsize
            for shape, dtype in self.slot
        )


def kv_heads_cache(
    num_kv_heads: int, head_dim: int, pack: int = 1, window: Optional[int] = None,
) -> CacheKind:
    """Keys and values by head; with `pack` > 1, that many heads a stored
    row (the same values a token, in rows a kernel can tile); with `window`,
    of the last `window` positions alone."""
    if num_kv_heads % pack:
        raise ValueError(f"{num_kv_heads} KV heads do not fill rows of {pack}")
    width = head_dim * pack
    return CacheKind(
        "kv_heads", 2, num_kv_heads // pack, width, width, pack=pack, window=window,
    )


def latent_cache(width: int, lanes: int = 128) -> CacheKind:
    return CacheKind("latent", 1, 1, width, -(-width // lanes) * lanes)


def recurrent_state(*arrays: tuple) -> CacheKind:
    """A layer that keeps one fixed-size slot a sequence: `arrays` are the
    (shape, dtype name) of what a lane holds."""
    return CacheKind("recurrent", 0, 0, 0, 0, tuple(arrays))


def keeps_nothing() -> CacheKind:
    """A layer that keeps neither rows per token nor a slot."""
    return CacheKind("nothing", 0, 0, 0, 0)


def paged_layers(config) -> int:
    """How many of `config`'s layers keep rows per token in blocks."""
    return sum(k.planes > 0 for k in layer_cache_kinds(config))


def page_groups(config) -> tuple[CacheKind, ...]:
    """The distinct kinds among `config`'s paged layers: layers of one kind
    share a block table and a pool of blocks, and a group's blocks live as
    long as its kind says. The group that keeps every position comes first:
    its table is the one every model has. One group for every model whose
    paged layers are alike; two for a model that mixes window and full
    attention layers (`models/afmoe.py`)."""
    kinds = []
    for kind in layer_cache_kinds(config):
        if kind.planes and kind not in kinds:
            kinds.append(kind)
    return tuple(sorted(kinds, key=lambda k: k.window is not None))


def layer_cache_kinds(config) -> tuple[CacheKind, ...]:
    """One kind a layer: what the config declares (`layer_cache_kinds`), or
    `num_layers` copies of the one kind it declares for layers that are all
    alike (`cache_kind`); a config that declares nothing is the grouped-query
    family's."""
    declared = getattr(config, "layer_cache_kinds", None)
    if declared is not None:
        return tuple(declared())
    alike = getattr(config, "cache_kind", None)
    kind = (
        alike() if alike is not None
        else kv_heads_cache(config.num_kv_heads, config.head_dim)
    )
    return (kind,) * config.num_layers


def cache_kind(config) -> CacheKind:
    """What `config`'s layers keep per cached token: the kind of the first
    layer that keeps rows at all (the paged layers of one model are alike)."""
    return next(k for k in layer_cache_kinds(config) if k.planes)


def recurrent_layers(config) -> int:
    """How many of `config`'s layers keep a slot a sequence."""
    return sum(k.name == "recurrent" for k in layer_cache_kinds(config))


_watching = threading.local()


def layer_body(*static: str):
    """A family's forward walks its layers in a Python loop and calls one of
    these once a layer: the loop's body under `jax.jit`, with `static` its
    static (keyword) arguments: the config, the mesh, what the code observes
    of a layer, never its index. JAX traces a body once a process for each
    distinct (static arguments, argument shapes, parameter tree) and lowers
    it to one private function that the step program calls, so a program's
    trace and its MLIR cost one layer of each kind, not `num_layers` x
    horizon. XLA inlines the calls before it optimises: the compiled program
    is the unrolled loop's (`tests/test_tpu_compile.py` holds it to that).
    Not `inline=True`, which would lower every copy again."""

    def wrap(fn):
        jitted = jax.jit(fn, static_argnames=static)

        @functools.wraps(fn)
        def call(*args, **kwargs):
            leaves, tree = jax.tree_util.tree_flatten(
                (args, {k: v for k, v in kwargs.items() if k not in static})
            )
            body = (
                fn.__module__, fn.__qualname__,
                tuple(kwargs.get(k) for k in static), tree,
                tuple((jnp.shape(a), jnp.result_type(a)) for a in leaves),
            )
            with forms_traced() as forms:
                out = jitted(*args, **kwargs)
            if forms:  # the body was traced (or ran) just now
                _BODY_FORMS[body] = forms
            seen = getattr(_watching, "bodies", None)
            if seen is not None:
                seen.add(body)
                # a layer is told by its parameters (the second argument of
                # every body), which a program's every step passes again
                params = tuple(jax.tree_util.tree_leaves(args[1]))
                _watching.layers[(body, tuple(map(id, params)))] = (
                    params, _BODY_FORMS.get(body)
                )
            return out

        return call

    return wrap


# body (as `layer_bodies_called` keys it) -> what its trace counted
# (`ops.basics.forms_traced`: the cache appends and the grouped products by
# the form each took); a body whose trace JAX has cached runs no Python, so
# the count is kept here
_BODY_FORMS: dict = {}


@contextlib.contextmanager
def layer_bodies_called():
    """Yields a set that holds, at the block's end, the distinct layer bodies
    the calling thread called inside it (a key as JAX caches them): what the
    programs traced there lower once each, whatever their depth (the goodput
    ledger's `layer_bodies` of a label's first dispatch)."""
    seen = _watching.bodies = set()
    layers = _watching.layers = {}
    try:
        yield seen
    finally:
        _watching.bodies = _watching.layers = None
        # the counts outlive the block, the layers' parameters do not
        _watching.forms = sum(
            (forms for _, forms in layers.values() if forms),
            collections.Counter(),
        )


def forms_called() -> collections.Counter:
    """What the layers counted that the calling thread's last
    `layer_bodies_called` block called, by the form's name: of the distinct
    layers (a body and its parameters: a horizon's steps call a layer again
    and count it once), how many append a decode token's rows to their cache
    inside the paged decode kernel (`kv_append_folded`) and how many by the
    row scatter before it (`kv_append_scattered`); how many grouped products
    of their experts run in the Pallas kernel (`grouped_product_kernel`) and
    how many in XLA's (`grouped_product_xla`); how many Mamba-2 state updates
    run in the Pallas kernel (`ssd_step_kernel`: a layer once for each shape
    of call a dispatch's steps make, `ops.pallas_ssm`) and how many in XLA's
    (`ssd_step_xla`). A name nothing counted reads 0."""
    return collections.Counter(getattr(_watching, "forms", None))


def forward_for(config):
    """The module that defines `config`'s class: its family's forward."""
    return sys.modules[type(config).__module__]


# The family modules. Each holds `MODEL_TYPES`, the `config.json`
# `model_type`s it serves, and `CONFIG`, its config class.
FAMILIES = ("llama", "mla_moe", "hybrid_ssm", "conv_moe", "ssm2_moe", "afmoe")


@functools.lru_cache(maxsize=None)
def served_model_types() -> dict:
    """`model_type` -> the config class of the family that serves it."""
    table = {}
    for name in FAMILIES:
        module = importlib.import_module(f"{__name__}.{name}")
        table.update(dict.fromkeys(module.MODEL_TYPES, module.CONFIG))
    return table


def config_from_model_dir(model_dir: str):
    """The config of the family that `config.json`'s `model_type` names."""
    with open(os.path.join(model_dir, "config.json")) as f:
        hf = json.load(f)
    served = served_model_types()
    model_type = hf.get("model_type")
    if model_type is not None and model_type not in served:
        raise ValueError(
            f"model_type {model_type!r} is not served: its layers are not "
            "implemented here, and a dense grouped-query model built from "
            "its widths would be another model under its name (served: "
            f"{sorted(served)})"
        )
    # a `config.json` that names no `model_type` is a grouped-query model's
    return served.get(model_type, served["llama"]).from_hf_dict(hf)

"""ModelRunner: owns the device state (params + paged KV cache) and the
jitted prefill/decode+sample executables.

TPU discipline (SURVEY.md / pallas guide):
  * caches are DONATED through every call — XLA updates them in place, no
    copy of the multi-GB KV tensors;
  * prompt lengths are padded to a small set of static buckets so XLA
    compiles a handful of programs, never per-request shapes;
  * sampling runs on device fused behind the decode step — the only
    device->host transfer per step is the [B] int32 of sampled tokens;
  * sharding: params/caches carry NamedShardings (parallel/sharding.py) and
    jit propagates them — the same code runs single-chip or TP over a mesh.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models import (
    cache_kind, forward_for, layer_cache_kinds, page_groups, recurrent_layers,
)
from dynamo_tpu.ops.sampling import (
    MAX_EOS_IDS,
    apply_penalties,
    apply_penalties_from_tables,
    penalty_count_tables,
    apply_repetition_penalty_from_prompt,
    apply_repetition_penalty_packed,
    mask_eos_logits,
    sample_tokens_full,
    spec_accept_len,
)
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.telemetry import trace as dtrace

logger = get_logger("dynamo_tpu.engine.runner")


def default_prefill_buckets(block_size: int, max_len: int) -> list[int]:
    """Power-of-two padded prompt lengths; every bucket is a whole number of
    KV blocks (prefill scatters whole blocks)."""

    def round_up(n: int) -> int:
        return ((n + block_size - 1) // block_size) * block_size

    buckets = []
    size = block_size
    while size < max_len:
        buckets.append(round_up(size))
        size *= 2
    top = round_up(max_len)
    if not buckets or buckets[-1] != top:
        buckets.append(top)
    return buckets


def _slots(state_slots) -> dict:
    """The keyword a family with a recurrent layer takes in its prefill
    forwards: the lane slot of each sequence in the call. None (every other
    family: an empty tree, so their programs do not see it) gives nothing."""
    return {} if state_slots is None else {"state_slots": state_slots}


def unrolled_steps(step, init, H: int):
    """H chained step() calls, statically unrolled — NOT a lax.scan.

    A lax.scan would compile the body once, but carrying the multi-GB KV
    caches through a scan makes XLA double-buffer them (the r04 bench OOMed
    HBM by ~0.9G exactly this way). Unrolled, each layer's own cache buffer
    threads through a chain of writes in place (the paged decode kernel's
    aliased outputs, or row scatters where the pair runs); that is
    a property of the compiled program, not of this dataflow, and
    tests/test_tpu_compile.py holds it there (no slice, copy or update the
    size of a layer's pool). H is small (<=16) and fixed per deployment.
    What unrolling costs at start-up: the compiler sees H x num_layers
    layers (a cold compile only); Python and the MLIR builder see one,
    because a layer's body is one jitted function that every step and
    layer calls (`models.layer_body`), so a horizon is traced and lowered
    in the time of a single step's embedding, head and sampler, H times.
    """
    ys = []
    carry = init
    for h in range(H):
        # the step's place twice: traced, for what it computes from it, and
        # as Python's, for what it decides while it is traced
        carry, y = step(carry, jnp.int32(h), h)
        ys.append(y)
    return carry, jnp.stack(ys)


def _packs(dtype: np.dtype) -> bool:
    """Whether a host leaf of this dtype rides a step's packed buffer: 32
    bits wide (viewed as int32, not converted) or a bool (widened to 0/1)."""
    return dtype == np.bool_ or (dtype.itemsize == 4 and dtype.kind in "iuf")


def pack_inputs(tree) -> tuple[tuple, np.ndarray, list]:
    """A step's host inputs as ONE transfer: every host leaf (`np.ndarray`
    or `np.generic`) that `_packs` is ravelled into one freshly allocated 1-D
    int32 array, in the tree's order. Fresh every call: the engine rewrites
    its lane arrays between dispatches, and a staging buffer kept across
    them could be rewritten under a transfer that still reads it.

    Returns (layout, buffer, beside). The layout is hashable, derived from
    the leaves alone: the tree's structure and, a leaf, its (shape, dtype,
    offset) in the buffer, or None for a leaf that travels beside it as it
    is (a `jax.Array` already on the device, a host leaf of another width);
    `beside` holds those, in order. `unpack_inputs` is its inverse on the
    device."""
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: isinstance(x, list)
    )
    spec, packed, beside, size = [], [], [], 0
    for leaf in leaves:
        if isinstance(leaf, (np.ndarray, np.generic)) and _packs(leaf.dtype):
            spec.append((leaf.shape, leaf.dtype, size))
            packed.append((size, leaf))
            size += leaf.size
        else:
            spec.append(None)
            beside.append(leaf)
    buf = np.empty(size, np.int32)
    for off, leaf in packed:
        flat = np.ravel(leaf)
        # a bool is cast to 0/1 by the assignment; the others keep their bits
        buf[off:off + flat.size] = (
            flat if flat.dtype == np.bool_ else flat.view(np.int32)
        )
    return (treedef, tuple(spec)), buf, beside


def unpack_inputs(layout: tuple, buf: jax.Array, beside):
    """`pack_inputs`' tree again inside a program, each packed leaf bit for
    bit with its shape and dtype: static slices of the buffer, reshaped,
    bitcast to float32 or uint32, `!= 0` for a bool."""
    treedef, spec = layout
    beside = iter(beside)
    leaves = []
    for entry in spec:
        if entry is None:
            leaves.append(next(beside))
            continue
        shape, dtype, off = entry
        x = jax.lax.slice(buf, (off,), (off + math.prod(shape),)).reshape(shape)
        if dtype == np.bool_:
            x = x != 0
        elif dtype != np.int32:
            x = jax.lax.bitcast_convert_type(x, dtype)
        leaves.append(x)
    return jax.tree_util.tree_unflatten(treedef, leaves)


class Launch:
    """What the runner's calls cost at the host's edge of the device since
    the record was last cleared: the host arrays committed (`_to_dev`: one
    array a call of a step, its packed buffer) and their bytes, the bytes
    read back, and the seconds of the three phases
    `runner.upload`, `runner.enqueue` and `runner.fetch`. The engine clears
    it before a dispatch's call and reads it after (`JaxEngine._dispatch`:
    the ledger's `launch` slot, and which part a long dispatch was long in).
    Runner calls are serialised, so plain additions do."""

    __slots__ = (
        "upload_arrays", "upload_bytes", "fetch_bytes",
        "upload_s", "enqueue_s", "fetch_s",
    )

    def __init__(self) -> None:
        self.clear()

    def clear(self) -> None:
        self.upload_arrays = self.upload_bytes = self.fetch_bytes = 0
        self.upload_s = self.enqueue_s = self.fetch_s = 0.0


class ModelRunner:
    # `decode_multi` keeps its lanes' carry on the device and takes `chain`:
    # the engine may enqueue a horizon behind one it has not read yet
    chains_horizons = True

    def __init__(
        self,
        config: Any,  # a family's config (models.forward_for finds its forward)
        params: Any,
        *,
        num_blocks: int,
        block_size: int,
        max_batch: int,
        max_model_len: int,
        # blocks of the window group's pool, for a model whose paged layers
        # are of two groups (`models.page_groups`); None: as many as
        # `num_blocks`
        window_blocks: Optional[int] = None,
        rng_seed: int = 0,
        prefill_buckets: Optional[list[int]] = None,
        # "int8" (or jnp.int8 / np.int8) => int8-resident paged cache with
        # per-(layer, head, block) scales; anything else is the plain
        # bf16/f32 cache dtype
        kv_dtype=jnp.bfloat16,
        fused_decode: bool = False,
        # DYN_COLLECTIVE_OVERLAP: decomposed collective-matmul tail for
        # the meshed fused decode step (ops/collective.fused_tail_overlap);
        # inert without a tp>1 mesh + fused_decode
        collective_overlap: bool = False,
        mesh: Optional[jax.sharding.Mesh] = None,
        kv_sharding: Optional[jax.sharding.NamedSharding] = None,
        attn_impl: str = "auto",
        cp_min_tokens: int = 512,
        prefill_chunk_tokens: int = 512,
        global_arrays: bool = False,
    ) -> None:
        # global_arrays: multi-controller mode (mesh spans hosts after
        # jax.distributed.initialize). Host inputs are committed as
        # fully-replicated GLOBAL arrays, scalar/token outputs are pinned
        # to a replicated sharding so every process can read its local
        # copy, and extract outputs are all-gathered before fetch.
        # "auto": flash pallas kernels on TPU — single-chip directly, under
        # a mesh via a shard_map wrapper over the head-sharded cache (each
        # tp shard's kernel streams only its own heads' pages). The choice
        # is pinned into THIS runner's config so concurrent runners with
        # different setups don't stomp each other.
        import dataclasses

        on_tpu = jax.default_backend() == "tpu"
        if attn_impl == "auto":
            attn_impl = "pallas" if on_tpu else "xla"
        # Mosaic tiling constraints: the decode kernel DMAs [block_size,
        # head_dim] page tiles into VMEM, so head_dim must be lane-aligned
        # (128) and block_size sublane-aligned (8).
        from dynamo_tpu.ops.attention import _pallas_tileable

        kind = cache_kind(config)
        # what each layer declares it keeps: rows per token in pages (`kind`),
        # or one slot a lane (a recurrent state)
        kinds = layer_cache_kinds(config)
        if attn_impl == "pallas" and not _pallas_tileable(
            kind.stored_width, block_size
        ):
            if on_tpu:
                # no silent XLA-gather serving on the chip: the caller
                # passes attn_impl="xla" knowingly or fixes the shape
                raise ValueError(
                    "pallas attention needs cached rows of a multiple of 128 "
                    "values (a head, or narrow heads side by side where the "
                    "family declares them so) and block_size%8==0 (got rows "
                    f"of {kind.stored_width}, block_size={block_size})"
                )
            logger.warning(
                "pallas attention needs cached rows of a multiple of 128 "
                "values and block_size%%8==0 (got %d/%d); falling back to xla",
                kind.stored_width, block_size,
            )
            attn_impl = "xla"
        self.attn_impl = attn_impl
        # head axis for the shard_map-wrapped pallas path: only set when the
        # mesh actually shards kv heads (tp>1); dp/sp/ep-only meshes keep
        # heads whole per device and the kernel runs unwrapped per shard.
        self._attn_mesh = None
        self._attn_head_axis = None
        if (
            mesh is not None
            and attn_impl.startswith("pallas")
            and mesh.shape.get("tp", 1) > 1
        ):
            self._attn_mesh = mesh
            self._attn_head_axis = "tp"
        config = dataclasses.replace(
            config, attn_impl=attn_impl,
            fused_decode=bool(fused_decode) or config.fused_decode,
            collective_overlap=bool(collective_overlap)
            or config.collective_overlap,
        )
        self.config = config
        self.params = params
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_batch = max_batch
        self.max_model_len = max_model_len
        self.max_blocks_per_seq = (max_model_len + block_size - 1) // block_size
        # the groups of paged layers, each with its own pool and a table a
        # sequence (the group that keeps every position first): one for
        # every model but one that mixes window and full attention layers.
        # A sequence's tables ride side by side in one table argument,
        # `max_blocks_per_seq` entries a group (`_table`)
        self.page_groups = page_groups(config)
        if len(self.page_groups) > 2 or (
            len(self.page_groups) == 2 and self.page_groups[0].window is not None
        ):
            raise ValueError(
                "paged layers of more than two groups, or of two windows, "
                f"are not implemented: {self.page_groups}"
            )
        self.window_blocks = (
            0 if len(self.page_groups) < 2
            else num_blocks if window_blocks is None else window_blocks
        )
        self.table_width = self.max_blocks_per_seq * len(self.page_groups)
        # each full block's companion in the window group, the allocator's
        # own array (`BlockAllocator.window_of`): the engine hands it over
        self.window_of: Optional[np.ndarray] = None
        self.mesh = mesh
        self.cp_min_tokens = cp_min_tokens
        self._rng_seed = rng_seed
        self._pack_fetch_jit = None  # lazy: see fetch_sample
        self.launch = Launch()
        self._step_counter = 0
        self._key_offset = 0  # monotonic decode-key counter (never reused)
        self.prefill_buckets = sorted(
            prefill_buckets or default_prefill_buckets(block_size, max_model_len)
        )
        # one array per layer and plane, head-major: each (head, page) is a
        # contiguous [bs, D] tile (what the pallas kernel streams; TP shards
        # the leading head axis). What a layer keeps is the config's
        # declaration: keys and values by head (a row a head, or several
        # narrow heads a row), or one latent plane; a recurrent layer keeps
        # its slot's arrays indexed by lane slot in their place (one array
        # where the keys ride and None for the values, or one in each), with
        # one slot more than lanes: the null lane's, which padding writes to
        # as it does to block 0
        self.cache_kind = kind
        self.layer_kinds = kinds
        self.recurrent_layers = recurrent_layers(config)
        self.state_slots = max_batch + 1 if self.recurrent_layers else 0
        from dynamo_tpu.ops import kv_quant

        def layer_shape(k):  # a paged layer's planes, of its group's blocks
            blocks = self.window_blocks if k.window is not None else num_blocks
            return (k.heads, blocks, block_size, k.stored_width)

        # DYN_KV_DTYPE=int8: the paged cache itself is int8-resident with
        # per-(layer, head, block) f32 scales — the PR-4 wire codec
        # promoted to device storage (ops/kv_quant.py). Halves per-step KV
        # HBM reads; dequant happens inside the attention kernels.
        if isinstance(kv_dtype, str):
            kv = kv_dtype.strip().lower()
            self.kv_quantized = kv == "int8"
            kv_dtype = (
                jnp.bfloat16
                if (self.kv_quantized or kv in ("bf16", "bfloat16"))
                else np.dtype(kv)
            )
        else:
            self.kv_quantized = np.dtype(kv_dtype) == np.dtype(np.int8)
        self.kv_dtype = jnp.bfloat16 if self.kv_quantized else kv_dtype
        self.global_arrays = global_arrays
        self._repl = (
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
            if (mesh is not None and global_arrays)
            else None
        )
        # sharding tree matching the cache container: per layer, {"q", "s"}
        # planes both head-sharded under tp; a plain array otherwise
        kv_shard_tree = kv_quant.cache_sharding(
            kv_sharding, config.num_layers, self.kv_quantized
        )
        def make_zeros(which: int):  # 0: keys or a slot's first array; 1: values or its second
            def pages(k):
                return kv_quant.make_cache(
                    1, layer_shape(k), self.kv_dtype, quantized=self.kv_quantized,
                )[0]

            def slot_array(k):  # None: a slot of one array (or none) has no second
                if which >= len(k.slot):
                    return None
                shape, dtype = k.slot[which]
                return jnp.zeros((self.state_slots,) + tuple(shape), dtype)

            return tuple(
                pages(k) if k.planes else slot_array(k) for k in kinds
            )

        if kv_sharding is not None:
            # allocate ON device under the sharding (works single- and
            # multi-controller; never materializes host zeros)
            make_zeros = jax.jit(
                make_zeros, static_argnums=0, out_shardings=kv_shard_tree
            )
        if (kind.planes == 1 or self.state_slots or self.window_blocks) and (
            self.kv_quantized or mesh is not None
        ):
            what = (
                "a model with a recurrent layer" if self.state_slots
                else "a model with window layers that give their pages back"
                if self.window_blocks else f"a {kind.name} cache"
            )
            raise ValueError(
                f"{what} is served in bfloat16 on one chip: an "
                "int8-resident cache (DYN_KV_DTYPE=int8) and a mesh are not "
                "implemented for it"
            )
        self.k_cache = make_zeros(0)
        self.v_cache = make_zeros(1) if kind.planes == 2 else ()
        logger.info(
            "kv cache: %d blocks%s x %d tokens (%s), %.2f GiB%s",
            num_blocks,
            f" and {self.window_blocks} of the window layers'"
            if self.window_blocks else "",
            block_size,
            "int8+scales" if self.kv_quantized else str(
                kv_dtype.__name__ if hasattr(kv_dtype, "__name__") else kv_dtype
            ),
            (kv_quant.cache_nbytes(self.k_cache)
             + kv_quant.cache_nbytes(self.v_cache)) / 2**30,
            f", of it {self.state_slots} state slots in "
            f"{self.recurrent_layers} recurrent layers, "
            f"{self.state_slots * sum(k.slot_bytes for k in kinds) / 2**30:.2f} GiB"
            if self.state_slots else "",
        )
        self._kv_sharding = kv_sharding
        self._kv_shard_tree = kv_shard_tree
        # Pin cache output shardings when running sharded: XLA would
        # otherwise be free to re-propagate (e.g. shard head_dim instead of
        # heads), breaking the megatron layout on the next step. Under
        # multi-controller, the token output is pinned replicated so each
        # process holds a full local copy to fetch.
        # sample outputs: (tok, logprob, top_ids, top_lps) — pinned
        # replicated under multi-controller so every process can fetch.
        cache_out = (
            ((self._repl,) * 4, kv_shard_tree, kv_shard_tree)
            if kv_sharding is not None
            else None
        )
        jit_kwargs: dict[str, Any] = {}
        if cache_out is not None:
            jit_kwargs["out_shardings"] = cache_out
        # one jitted callable each (`_step_jit`: a step's host inputs arrive
        # as one packed buffer); jit's shape cache handles the buckets.
        # The FULL mesh rides along (MoE dispatch-path selection in _mlp
        # keys on its ep size); attention shard_maps only when head_axis
        # is set.
        self._prefill_jit = self._step_jit(
            self._prefill_impl, self.config, self.mesh, self._attn_head_axis,
            **jit_kwargs,
        )
        # context-parallel (ring attention) prefill when the mesh has an sp
        # axis: the prompt is sequence-sharded, KV chunks rotate over ICI,
        # then the produced K/V paginate into this cache (long-context
        # first-class — the reference routes long prefills away instead)
        self._use_cp_prefill = (
            mesh is not None
            and "sp" in mesh.axis_names
            and mesh.shape["sp"] > 1
        )
        if self._use_cp_prefill:
            head_axis = (
                "tp" if mesh.shape.get("tp", 1) > 1 else None
            )
            self._prefill_cp_jit = self._step_jit(
                self._prefill_cp_impl, self.config, mesh, head_axis,
                **jit_kwargs,
            )
        self._decode_fn = self._step_jit(
            self._decode_impl, self.config, self.mesh, self._attn_head_axis,
            **jit_kwargs,
        )
        # horizon decode: H chained steps per dispatch (one compile per
        # distinct H; the engine uses a single configured H). Output
        # sharding: packed samples and the lanes' carry replicated, caches
        # keep theirs.
        multi_out = (
            ((self._repl, (self._repl,) * 4), kv_shard_tree, kv_shard_tree)
            if kv_sharding is not None
            else None
        )
        # what the last `decode_multi` left its lanes, on the device (see
        # `_decode_multi_impl`): the next call takes it as it is, and the
        # `chain` mask says which lanes start from it. Zeros until then
        self._horizon_carry: Optional[tuple] = None
        self._decode_multi_fn = self._step_jit(
            self._decode_multi_impl, self.config,
            self.mesh, self._attn_head_axis, self.block_size,
            n_static=1,  # H (first arg after the bound ones)
            **({"out_shardings": multi_out} if multi_out is not None else {}),
        )
        # penalty-enabled decode variant: compiled lazily on the first
        # request that sets a penalty, so the hot path (and the bench) stays
        # on the slim program with no history input.
        self._decode_pen_fn = self._step_jit(
            self._decode_pen_impl, self.config,
            self.mesh, self._attn_head_axis,
            **jit_kwargs,
        )
        # eos-mask-only variant (min_tokens set, no penalties): masks EOS
        # logits without the [B, max_model_len] history upload the penalty
        # program pays on every step.
        self._decode_eos_fn = self._step_jit(
            self._decode_eos_impl, self.config,
            self.mesh, self._attn_head_axis,
            **jit_kwargs,
        )
        # packed batched prefill: N short prompts in ONE [P] program
        # (segment-masked attention); admission batches prompts up to this
        # token budget per engine iteration. Shares the chunk budget so the
        # compile surface stays at one packed + one chunk program.
        self._packed_jit = self._step_jit(
            self._prefill_packed_impl, self.config, self.mesh, **jit_kwargs,
        )
        # chunked prefill (vLLM-style): ONE program serves every chunk of
        # every long prompt, letting the engine interleave decode steps
        # between chunks (round-1 VERDICT weak item #3: "prefill serializes
        # the world"). 0 disables. Chunk size rounds up to whole KV blocks.
        if prefill_chunk_tokens:
            prefill_chunk_tokens = (
                (prefill_chunk_tokens + block_size - 1) // block_size
            ) * block_size
        self.prefill_chunk_tokens = min(
            prefill_chunk_tokens, self.prefill_buckets[-1]
        )
        self._chunk_jit = self._step_jit(
            self._prefill_chunk_impl, self.config, self.mesh, **jit_kwargs,
        )
        # unified mixed step (Sarathi/POD-style): k prefill chunks ride
        # along the full decode batch in ONE device program, so the two
        # phases stop alternating as separate dispatches (the phase
        # bubble). One compiled variant per k — the engine's per-step
        # token budget bounds k, and tools/prebake_cache.py bakes each.
        self._mixed_jits: dict[int, Any] = {}
        # Disagg KV movement (NIXL/block_copy.cu replacement): gather whole
        # blocks out of the paged cache / scatter received blocks in. Block
        # counts are padded to bucket sizes so each compiles once per
        # bucket. Under multi-controller the gathered blocks are pinned
        # replicated (an all-gather) so every process can fetch them.
        # Int8-resident caches keep TWO gather flavors: a dequantizing one
        # (legacy bf16 consumers) and a verbatim mantissa+scale one (the
        # no-recode path for disagg frames / offload tiers).
        repl_out = (
            {"out_shardings": (self._repl, self._repl)}
            if self._repl is not None
            else {}
        )
        # The wire format is one [L, Hkv, n, bs, D] array (for the int8
        # cache a {"q", "s"} pair of such): layers are stacked on the way
        # out and scattered per layer on the way in.
        kv_out = (
            {"out_shardings": (kv_shard_tree, kv_shard_tree)}
            if kv_sharding is not None
            else {}
        )

        def gather(cache, ids):
            return jax.tree.map(
                lambda *layers: jnp.stack([a[:, ids] for a in layers]), *cache
            )

        def scatter(cache, ids, wire):
            return tuple(
                jax.tree.map(
                    lambda c, w: c.at[:, ids].set(w[i].astype(c.dtype)),
                    layer, wire,
                )
                for i, layer in enumerate(cache)
            )

        if self.kv_quantized:

            def dense(cache, ids):
                got = gather(cache, ids)
                return kv_quant.dequantize(got["q"], got["s"]).astype(
                    self.kv_dtype
                )

            self._extract_jit = jax.jit(
                lambda k, v, ids: (dense(k, ids), dense(v, ids)), **repl_out
            )

            def _extract_q(k, v, ids):
                k, v = gather(k, ids), gather(v, ids)
                return k["q"], k["s"], v["q"], v["s"]

            self._extract_q_jit = jax.jit(
                _extract_q,
                **(
                    {"out_shardings": (self._repl,) * 4}
                    if self._repl is not None
                    else {}
                ),
            )
            # whole-block quantize-on-inject: the wire codec's exact
            # per-(layer, head, block) absmax scheme, on device
            self._inject_jit = jax.jit(
                lambda k, v, ids, kb, vb: (
                    scatter(k, ids, kv_quant.quantize_blocks(kb)),
                    scatter(v, ids, kv_quant.quantize_blocks(vb)),
                ),
                donate_argnums=(0, 1),
                **kv_out,
            )
            self._inject_q_jit = jax.jit(
                lambda k, v, ids, kq, ks, vq, vs: (
                    scatter(k, ids, {"q": kq, "s": ks}),
                    scatter(v, ids, {"q": vq, "s": vs}),
                ),
                donate_argnums=(0, 1),
                **kv_out,
            )
        else:
            self._extract_jit = jax.jit(
                lambda k, v, ids: (gather(k, ids), gather(v, ids)),
                **repl_out,
            )
            self._inject_jit = jax.jit(
                lambda k, v, ids, kb, vb: (
                    scatter(k, ids, kb), scatter(v, ids, vb)
                ),
                donate_argnums=(0, 1),
                **kv_out,
            )

    # ------------------------------------------------------------- jitted

    @staticmethod
    def _sample_one(logits, prompt, n_prompt, key_data, temp, top_p, top_k,
                    want_lp, rep_pen, eos_ids, eos_suppress):
        """Shared prefill tail: prompt repetition penalty + min_tokens EOS
        mask + sample + logprobs for the single first token (freq/presence
        are zero by definition)."""
        logits = apply_repetition_penalty_from_prompt(
            logits, prompt, n_prompt, rep_pen
        )
        logits = mask_eos_logits(logits, eos_ids, eos_suppress)
        tok, lp, tids, tlps = sample_tokens_full(
            logits[None, :], None, temp[None], top_p[None], top_k[None],
            want_lp[None], keys=key_data[None, :],
        )
        return tok[0], lp[0], tids[0], tlps[0]

    @staticmethod
    def _prefill_impl(
        cfg, attn_mesh, attn_head_axis,
        params, k_cache, v_cache, tokens, valid_len, block_table,
        key_data, temp, top_p, top_k, want_lp, rep_pen, eos_ids, eos_suppress,
        state_slots=None,
    ):
        logits, k_cache, v_cache = forward_for(cfg).prefill(
            params, cfg, tokens, valid_len, k_cache, v_cache, block_table,
            mesh=attn_mesh, attn_head_axis=attn_head_axis,
            **_slots(state_slots),
        )
        out = ModelRunner._sample_one(
            logits, tokens, valid_len, key_data, temp, top_p, top_k, want_lp,
            rep_pen, eos_ids, eos_suppress,
        )
        return out, k_cache, v_cache

    @staticmethod
    def _prefill_mm_impl(
        cfg, attn_mesh, attn_head_axis,
        params, k_cache, v_cache, tokens, valid_len, block_table,
        mm_embeds, mm_start,
        key_data, temp, top_p, top_k, want_lp, rep_pen, eos_ids, eos_suppress,
    ):
        logits, k_cache, v_cache = forward_for(cfg).prefill_mm(
            params, cfg, tokens, valid_len, k_cache, v_cache, block_table,
            mm_embeds, mm_start,
            mesh=attn_mesh, attn_head_axis=attn_head_axis,
        )
        out = ModelRunner._sample_one(
            logits, tokens, valid_len, key_data, temp, top_p, top_k, want_lp,
            rep_pen, eos_ids, eos_suppress,
        )
        return out, k_cache, v_cache

    @staticmethod
    def _prefill_cp_impl(
        cfg, mesh, head_axis, params, k_cache, v_cache, tokens, valid_len,
        block_table, key_data, temp, top_p, top_k, want_lp, rep_pen, eos_ids,
        eos_suppress,
    ):
        # per-layer pagination inside the model loop: peak transient is one
        # layer's [P, Hkv, D], never the full [L, P, Hkv, D] stack
        logits, k_cache, v_cache = forward_for(cfg).prefill_context_parallel(
            params, cfg, mesh, tokens, valid_len, head_axis=head_axis,
            k_cache=k_cache, v_cache=v_cache, block_table=block_table,
        )
        out = ModelRunner._sample_one(
            logits, tokens, valid_len, key_data, temp, top_p, top_k, want_lp,
            rep_pen, eos_ids, eos_suppress,
        )
        return out, k_cache, v_cache

    @staticmethod
    def _prefill_chunk_impl(
        cfg, mesh, params, k_cache, v_cache, tokens, chunk_start, valid_len,
        block_table, key_data, temp, top_p, top_k, want_lp, rep_pen, eos_ids,
        eos_suppress, state_slots=None,
    ):
        logits, k_cache, v_cache = forward_for(cfg).prefill_chunk(
            params, cfg, tokens, chunk_start, valid_len,
            k_cache, v_cache, block_table, mesh=mesh, **_slots(state_slots),
        )
        # repetition penalty sees this chunk's tokens only (earlier chunks
        # already left the program); documented approximation for the FIRST
        # token of a chunked long prompt — decode steps use the full history
        n_in_chunk = jnp.clip(valid_len - chunk_start, 0, tokens.shape[0])
        out = ModelRunner._sample_one(
            logits, tokens, n_in_chunk, key_data, temp, top_p, top_k, want_lp,
            rep_pen, eos_ids, eos_suppress,
        )
        return out, k_cache, v_cache

    @staticmethod
    def _prefill_packed_impl(
        cfg, mesh, params, k_cache, v_cache, tokens, positions, segment_ids,
        slot_indices, last_idx, keys, temps, top_ps, top_ks, want_lps,
        rep_pens, eos_ids, eos_suppress, state_slots=None,
    ):
        logits, k_cache, v_cache = forward_for(cfg).prefill_packed(
            params, cfg, tokens, positions, segment_ids, slot_indices,
            k_cache, v_cache, last_idx, mesh=mesh, **_slots(state_slots),
        )
        logits = apply_repetition_penalty_packed(
            logits, tokens, segment_ids, rep_pens
        )
        logits = mask_eos_logits(logits, eos_ids, eos_suppress)
        out = sample_tokens_full(
            logits, None, temps, top_ps, top_ks, want_lps, keys=keys
        )
        return out, k_cache, v_cache

    @staticmethod
    def _decode_impl(
        cfg, attn_mesh, attn_head_axis,
        params, k_cache, v_cache, tokens, positions, block_tables,
        slot_indices, keys, temps, top_ps, top_ks, want_lps,
    ):
        logits, k_cache, v_cache = forward_for(cfg).decode(
            params, cfg, tokens, positions, k_cache, v_cache,
            block_tables, slot_indices,
            mesh=attn_mesh, attn_head_axis=attn_head_axis,
        )
        out = sample_tokens_full(
            logits, None, temps, top_ps, top_ks, want_lps, keys=keys
        )
        return out, k_cache, v_cache

    @staticmethod
    def _decode_multi_impl(
        cfg, attn_mesh, attn_head_axis, block_size, H,
        params, k_cache, v_cache,
        tokens,           # [B] i32 — last sampled token per lane
        positions,        # [B] i32 — position of that token (same as decode)
        block_tables,     # [B, max_blocks] i32
        keys,             # [B, 2] u32 — threefry rows for step 0; the
                          # counter column advances by 1 per step, exactly
                          # what the engine's per-token _key_row would send
        temps, top_ps, top_ks,  # [B]
        want_lps,         # [B] bool — lane's request asked for log-probs
        active,           # [B] bool — lane live at horizon start
        limit_remaining,  # [B] i32 — tokens the lane may still emit
        min_remaining,    # [B] i32 — steps during which EOS stays masked
        eos_ids,          # [B, MAX_EOS_IDS] i32, -1 pads
        chain=None,       # [B] bool — lane continues from `carry`
        carry=None,       # the previous call's carry, on the device:
                          # (tokens [B] i32, positions [B] i32, done [B]
                          # bool, advanced [B] i32); the runner always
                          # passes one. None: the horizon alone, and
                          # `packed` is all that comes back beside the caches
        pen=None,         # optional (hist [B, L] i32, hist_len [B] i32,
                          # prompt_len [B] i32, freq [B], pres [B], rep [B])
    ):
        """H chained decode steps in ONE program (statically unrolled; see
        unrolled_steps for why not lax.scan): each step's sampled token
        feeds the next step on device, so the host pays one dispatch + one
        fetch per H tokens instead of per token.

        Per-lane freeze semantics: a lane stops advancing (and scatters its
        KV writes into null block 0) once it samples an un-suppressed EOS
        or exhausts limit_remaining; frozen steps emit token -1 so the host
        skips them. The EOS token itself is emitted (the engine hides it),
        but never fed back as an input — mirroring the single-step engine
        flow where a finished sequence leaves the batch.

        Penalties (`pen` given — a second trace of the same program): the
        [B, L] history is scattered into [B, V] count tables ONCE at
        horizon start; each unrolled step applies penalties from the
        tables and adds its own sampled token (history is append-only
        during a horizon), matching the single-step penalty program
        token-for-token. Lanes without penalties run freq=0/pres=0/rep=1
        — bit-exact pass-through — so ONE dispatch serves mixed batches
        instead of dragging everyone to H=1 (VERDICT r4 weak #2).

        The carry: beside `packed` the program hands on what its last step
        left a lane (the token to feed next, its position, `done`) and how
        many tokens the lane emitted here (`advanced`, 0..H). A lane whose
        `chain` is set starts from the previous call's carry, not from the
        host's arrays, which then describe the lane as of THAT call's start:
        its limit, its `min_remaining` and its key's counter move on by what
        it advanced there, and a lane whose limit is used up starts frozen.
        So the engine can enqueue a horizon before it has read the one
        before. Every other lane starts from the host's arrays as ever.
        """
        B = tokens.shape[0]
        rows = jnp.arange(B)
        done = None
        if carry is not None:
            c_tokens, c_positions, c_done, c_advanced = carry
            advanced = jnp.where(chain, c_advanced, 0)
            tokens = jnp.where(chain, c_tokens, tokens)
            positions = jnp.where(chain, c_positions, positions)
            limit_remaining = limit_remaining - advanced
            min_remaining = min_remaining - advanced
            keys = keys.at[:, 1].add(advanced.astype(jnp.uint32))
            done = jnp.where(chain, c_done | (limit_remaining <= 0), ~active)
        eos_valid = eos_ids >= 0
        model = forward_for(cfg)
        step_stats = getattr(model, "STEP_STATS", ())
        settles = getattr(model, "DECODE_SETTLES", False)
        if pen is not None:
            hist, hist_len, prompt_len, freq, pres, rep = pen
            out_counts, seen = penalty_count_tables(
                hist, hist_len, prompt_len, cfg.vocab_size
            )

        def step(carry, h, nth):
            if pen is None:
                tokens, positions, k_cache, v_cache, done = carry
            else:
                (tokens, positions, k_cache, v_cache, done,
                 out_counts, seen) = carry
            slot_idx = (
                block_tables[rows, positions // block_size] * block_size
                + positions % block_size
            )
            slot_idx = jnp.where(done, 0, slot_idx)
            stats = [] if step_stats else None
            logits, k_cache, v_cache = model.decode(
                params, cfg, tokens, positions, k_cache, v_cache,
                block_tables, slot_idx,
                mesh=attn_mesh, attn_head_axis=attn_head_axis,
                **({"stats": stats} if step_stats else {}),
                # the dispatch's last step writes what a layer's earlier
                # steps left unwritten (`models.programs.decode`)
                **({"settle": nth == H - 1} if settles else {}),
            )
            if pen is not None:
                logits = apply_penalties_from_tables(
                    logits, out_counts, seen, freq, pres, rep
                )
            suppress = h < min_remaining  # [B] bool
            logits = mask_eos_logits(logits, eos_ids, suppress)
            step_keys = keys.at[:, 1].add(h.astype(jnp.uint32))
            tok, lp, top_ids, top_lps = sample_tokens_full(
                logits, None, temps, top_ps, top_ks, want_lps, keys=step_keys
            )
            is_eos = jnp.any((tok[:, None] == eos_ids) & eos_valid, axis=-1)
            out_tok = jnp.where(done, -1, tok)
            packed = jnp.concatenate(
                [
                    out_tok[:, None].astype(jnp.float32),
                    lp[:, None].astype(jnp.float32),
                    top_ids.astype(jnp.float32),
                    top_lps.astype(jnp.float32),
                ],
                axis=-1,
            )  # [B, 2 + 2*num_top]
            if stats:
                # what the model's layers counted in this step, summed,
                # rides the same fetch as one more row behind the lanes
                # (`step_stats` reads it)
                counted = sum(stats)  # [len(STEP_STATS)]
                row = jnp.zeros((1, packed.shape[1]), jnp.float32)
                packed = jnp.concatenate(
                    [packed, row.at[0, : counted.shape[0]].set(counted)], axis=0
                )
            next_tokens = jnp.where(done | is_eos, tokens, tok)
            next_positions = jnp.where(done, positions, positions + 1)
            if pen is not None:
                # the appended-history update: an EOS finishes the lane
                # before appending (single-step drops it from token_ids),
                # so only advancing non-EOS tokens enter the tables
                adv = (~done) & (~is_eos)
                out_counts = out_counts.at[rows, tok].add(
                    adv.astype(jnp.float32)
                )
                seen = seen.at[rows, tok].max(adv.astype(jnp.float32))
            done = done | is_eos | (h + 1 >= limit_remaining)
            carry = (next_tokens, next_positions, k_cache, v_cache, done)
            if pen is not None:
                carry = carry + (out_counts, seen)
            return carry, packed

        init = (
            tokens, positions, k_cache, v_cache,
            ~active if done is None else done,
        )
        if pen is not None:
            init = init + (out_counts, seen)
        last, packed = unrolled_steps(step, init, H)
        k_cache, v_cache = last[2], last[3]
        if carry is None:
            return packed, k_cache, v_cache  # packed [H, B, 2+2K]
        emitted = jnp.sum(packed[:, :B, 0] >= 0, axis=0).astype(jnp.int32)
        return (packed, (last[0], last[1], last[4], emitted)), k_cache, v_cache

    @staticmethod
    def _spec_verify_impl(
        cfg, attn_mesh, attn_head_axis, block_size, S, E,
        params, k_cache, v_cache,
        tokens,           # [B] i32 — last accepted token per lane
        drafts,           # [B, S-1] i32 — n-gram draft tokens (junk pads)
        draft_len,        # [B] i32 — valid drafts per lane (0 = no draft)
        positions,        # [B] i32 — position of `tokens`
        block_tables,     # [B, max_blocks] i32
        keys,             # [B, 2] u32 — threefry rows for step 0; the
                          # counter column advances by 1 per emitted
                          # position, exactly matching _key_row per token
        temps, top_ps, top_ks,  # [B]
        want_lps,         # [B] bool — lane's request asked for log-probs
        active,           # [B] bool
        limit_remaining,  # [B] i32 — tokens the lane may still emit
        min_remaining,    # [B] i32 — steps during which EOS stays masked
        eos_ids,          # [B, MAX_EOS_IDS] i32, -1 pads
        pen=None,         # optional (hist, hist_len, prompt_len, freq,
                          # pres, rep) — same 6-tuple as decode_multi
    ):
        """Draft-verify dispatch for self-drafting speculative decoding.

        ONE weight pass (llama.decode_verify) scores all S = spec_k + 1
        positions per lane: position 0 re-feeds the last accepted token,
        positions 1..draft_len feed the host drafter's n-gram proposals.
        Each position is sampled with the SAME (stream, counter+h) threefry
        key the per-token path would use, so under greedy AND temperature
        sampling the emitted stream is bit-identical to non-speculative
        decoding — acceptance (spec_accept_len) is pure token-id
        comparison on both device and host.

        Horizon composition: after the verify pass the device computes the
        accept point and chains E extra plain decode steps from the bonus
        token (decode_multi's step semantics: freeze on EOS / budget),
        so one dispatch = 1 verify weight pass + E decode weight passes
        emitting up to draft_len + 1 + E tokens. The engine passes E = 0
        for penalty batches: the on-device count tables cannot subtract a
        REJECTED draft back out, so penalties ride the verify positions
        (where rejected outputs are discarded anyway) but not the chained
        continuation.

        KV discipline: every fed position scatters into its real slot, so
        rejected draft positions leave garbage KV *ahead* of the accepted
        frontier. That is safe by construction: the engine only advances
        kv_written over ACCEPTED tokens, decode attention masks by
        position, and the very next fed token overwrites the first garbage
        slot — rejected speculation rolls back by being overwritten before
        it can ever be attended or offloaded.

        Returns packed [S + E, B, 2 + 2*num_top] f32 (token/-1, logprob,
        top ids, top lps per position).
        """
        B = tokens.shape[0]
        rows = jnp.arange(B)
        eos_valid = eos_ids >= 0
        fed = jnp.concatenate([tokens[:, None], drafts], axis=1)  # [B, S]
        step = jnp.arange(S)[None, :]
        valid = active[:, None] & (step <= draft_len[:, None])  # [B, S]
        qpos = positions[:, None] + step  # [B, S]
        slot = (
            block_tables[rows[:, None], qpos // block_size] * block_size
            + qpos % block_size
        )
        slot = jnp.where(valid, slot, 0)  # frozen lanes hit the null sink
        logits, k_cache, v_cache = forward_for(cfg).decode_verify(
            params, cfg, fed, qpos, k_cache, v_cache, block_tables, slot,
            mesh=attn_mesh, attn_head_axis=attn_head_axis,
        )
        if pen is None:
            # fold S into the batch and sample every position in ONE pass
            # (row-wise sampler => bit-identical to the per-step loop; the
            # per-row threefry counters are exactly keys[:,1] + h)
            V = logits.shape[-1]
            lg = logits.reshape(B * S, V)
            suppress = (step < min_remaining[:, None]).reshape(-1)
            lg = mask_eos_logits(lg, jnp.repeat(eos_ids, S, axis=0), suppress)
            keys_rep = jnp.repeat(keys, S, axis=0).at[:, 1].add(
                jnp.tile(jnp.arange(S, dtype=jnp.uint32), B)
            )
            tok, lp, top_ids, top_lps = sample_tokens_full(
                lg, None,
                jnp.repeat(temps, S), jnp.repeat(top_ps, S),
                jnp.repeat(top_ks, S), jnp.repeat(want_lps, S), keys=keys_rep,
            )
            t = tok.reshape(B, S)
            packed_v = jnp.concatenate(
                [
                    jnp.where(valid, t, -1)[:, :, None].astype(jnp.float32),
                    lp.reshape(B, S, 1),
                    top_ids.reshape(B, S, -1).astype(jnp.float32),
                    top_lps.reshape(B, S, -1),
                ],
                axis=-1,
            ).transpose(1, 0, 2)  # [S, B, 2+2K]
            packed_rows = [packed_v[h] for h in range(S)]
        else:
            hist, hist_len, prompt_len, freq, pres, rep = pen
            out_counts, seen = penalty_count_tables(
                hist, hist_len, prompt_len, cfg.vocab_size
            )
            toks = []
            packed_rows = []
            for h in range(S):
                lg = logits[:, h]
                if h >= 1:
                    # the draft token fed at step h entered the context;
                    # matched prefixes make this exactly the appended
                    # history of the single-step path, and a mismatch only
                    # pollutes positions whose outputs the host discards
                    adv = valid[:, h].astype(jnp.float32)
                    fed_h = jnp.clip(fed[:, h], 0, cfg.vocab_size - 1)
                    out_counts = out_counts.at[rows, fed_h].add(adv)
                    seen = seen.at[rows, fed_h].max(adv)
                lg = apply_penalties_from_tables(
                    lg, out_counts, seen, freq, pres, rep
                )
                suppress = h < min_remaining  # [B] bool
                lg = mask_eos_logits(lg, eos_ids, suppress)
                step_keys = keys.at[:, 1].add(jnp.uint32(h))
                tok, lp, top_ids, top_lps = sample_tokens_full(
                    lg, None, temps, top_ps, top_ks, want_lps, keys=step_keys
                )
                toks.append(tok)
                out_tok = jnp.where(valid[:, h], tok, -1)
                packed_rows.append(
                    jnp.concatenate(
                        [
                            out_tok[:, None].astype(jnp.float32),
                            lp[:, None].astype(jnp.float32),
                            top_ids.astype(jnp.float32),
                            top_lps.astype(jnp.float32),
                        ],
                        axis=-1,
                    )
                )
            t = jnp.stack(toks, axis=1)  # [B, S]
        if E > 0:
            m = spec_accept_len(t, drafts, draft_len)  # [B] accepted drafts
            # freeze the continuation when an EOS lands anywhere in the
            # accepted region (the host stops appending there)
            emitted = step <= m[:, None]
            t_eos = jnp.any(
                (t[:, :, None] == eos_ids[:, None, :]) & eos_valid[:, None, :],
                axis=-1,
            )
            done = (~active) | jnp.any(t_eos & emitted & valid, axis=1)
            count = m + 1  # tokens emitted by the verify pass
            last_tok = t[rows, m]  # the bonus token — next to feed
            for _ in range(E):
                alive = (~done) & (count < limit_remaining)
                qpos_e = positions + count
                slot_e = (
                    block_tables[rows, qpos_e // block_size] * block_size
                    + qpos_e % block_size
                )
                slot_e = jnp.where(alive, slot_e, 0)
                lg, k_cache, v_cache = forward_for(cfg).decode(
                    params, cfg, last_tok, qpos_e, k_cache, v_cache,
                    block_tables, slot_e,
                    mesh=attn_mesh, attn_head_axis=attn_head_axis,
                )
                suppress = count < min_remaining
                lg = mask_eos_logits(lg, eos_ids, suppress)
                step_keys = keys.at[:, 1].add(count.astype(jnp.uint32))
                tok, lp, top_ids, top_lps = sample_tokens_full(
                    lg, None, temps, top_ps, top_ks, want_lps, keys=step_keys
                )
                is_eos = jnp.any((tok[:, None] == eos_ids) & eos_valid, axis=-1)
                out_tok = jnp.where(alive, tok, -1)
                packed_rows.append(
                    jnp.concatenate(
                        [
                            out_tok[:, None].astype(jnp.float32),
                            lp[:, None].astype(jnp.float32),
                            top_ids.astype(jnp.float32),
                            top_lps.astype(jnp.float32),
                        ],
                        axis=-1,
                    )
                )
                last_tok = jnp.where(alive & (~is_eos), tok, last_tok)
                done = done | (alive & is_eos)
                count = count + alive.astype(jnp.int32)
        return jnp.stack(packed_rows), k_cache, v_cache  # [S+E, B, 2+2K]

    @staticmethod
    def _decode_pen_impl(
        cfg, attn_mesh, attn_head_axis,
        params, k_cache, v_cache, tokens, positions, block_tables,
        slot_indices, keys, temps, top_ps, top_ks, want_lps,
        hist, hist_len, prompt_len, freq_pen, pres_pen, rep_pen,
        eos_ids, eos_suppress,
    ):
        logits, k_cache, v_cache = forward_for(cfg).decode(
            params, cfg, tokens, positions, k_cache, v_cache,
            block_tables, slot_indices,
            mesh=attn_mesh, attn_head_axis=attn_head_axis,
        )
        logits = apply_penalties(
            logits, hist, hist_len, prompt_len, freq_pen, pres_pen, rep_pen
        )
        logits = mask_eos_logits(logits, eos_ids, eos_suppress)
        out = sample_tokens_full(
            logits, None, temps, top_ps, top_ks, want_lps, keys=keys
        )
        return out, k_cache, v_cache

    @staticmethod
    def _decode_eos_impl(
        cfg, attn_mesh, attn_head_axis,
        params, k_cache, v_cache, tokens, positions, block_tables,
        slot_indices, keys, temps, top_ps, top_ks, want_lps, eos_ids,
        eos_suppress,
    ):
        logits, k_cache, v_cache = forward_for(cfg).decode(
            params, cfg, tokens, positions, k_cache, v_cache,
            block_tables, slot_indices,
            mesh=attn_mesh, attn_head_axis=attn_head_axis,
        )
        logits = mask_eos_logits(logits, eos_ids, eos_suppress)
        out = sample_tokens_full(
            logits, None, temps, top_ps, top_ks, want_lps, keys=keys
        )
        return out, k_cache, v_cache

    @staticmethod
    def _mixed_impl(
        cfg, attn_mesh, attn_head_axis,
        params, k_cache, v_cache,
        chunk_args,  # tuple of per-chunk arg tuples (see mixed_step)
        tokens, positions, block_tables, slot_indices, keys, temps,
        top_ps, top_ks, want_lps, eos_ids, eos_suppress,
    ):
        """One packed device step: k chunked-prefill sub-computations
        followed by the full decode batch, threading the donated KV caches
        through in program order. Running the chunks FIRST mirrors the
        phase-separated loop's dispatch order, and every sub-computation
        touches disjoint KV blocks, so the packed step is bit-identical to
        the separate programs (the token-identity parity test pins this).
        The decode half always runs the eos-masked variant: with all-(-1)
        ids and suppress=False the mask is a bitwise no-op, keeping one
        compiled program per k instead of per sampling-feature set."""
        outs = []
        for chunk in chunk_args:
            # a chunk's tuple is `_prefill_chunk_impl`'s arguments behind the
            # caches, in its order (`mixed_step` builds it); the sequence's
            # lane slot is the last, where the model keeps a state there
            c_out, k_cache, v_cache = ModelRunner._prefill_chunk_impl(
                cfg, attn_mesh, params, k_cache, v_cache, *chunk
            )
            outs.extend(c_out)
        d_out, k_cache, v_cache = ModelRunner._decode_eos_impl(
            cfg, attn_mesh, attn_head_axis, params, k_cache, v_cache,
            tokens, positions, block_tables, slot_indices, keys, temps,
            top_ps, top_ks, want_lps, eos_ids, eos_suppress,
        )
        outs.extend(d_out)
        return tuple(outs), k_cache, v_cache

    def _mixed_jit_for(self, k: int):
        """The jitted mixed program for k chunk slots (built on first use;
        the jit object is cheap, XLA compiles on first dispatch)."""
        fn = self._mixed_jits.get(k)
        if fn is None:
            kw: dict[str, Any] = {}
            if self._kv_sharding is not None:
                kw["out_shardings"] = (
                    (self._repl,) * (4 * k + 4),
                    self._kv_shard_tree,
                    self._kv_shard_tree,
                )
            fn = self._step_jit(
                self._mixed_impl, self.config,
                self.mesh, self._attn_head_axis,
                **kw,
            )
            self._mixed_jits[k] = fn
        return fn

    def mixed_step(
        self,
        chunks,  # list of (token_chunk, chunk_start, total_len, block_ids,
                 #          temperature, top_p, top_k, rep_pen, key_data,
                 #          eos_ids, eos_suppress) — one per prefill slot
        tokens, positions, block_tables, slot_indices, keys, temps,
        top_ps, top_ks,
        eos_ids: Optional[np.ndarray] = None,  # [B, MAX_EOS_IDS] i32
        eos_suppress: Optional[np.ndarray] = None,  # [B] bool
        state_slots: Optional[list[int]] = None,  # each chunk's lane slot
        want_logprobs: Optional[np.ndarray] = None,  # [B] bool, the lanes'
        chunk_want_logprobs: Optional[list[bool]] = None,  # each chunk's
    ) -> tuple[tuple, tuple]:
        """One unified mixed step: the decode batch plus ``chunks`` packed
        prefill-chunk slots in a single dispatch. Chunks of one sequence
        must arrive in order (two slots of the SAME sequence in one step
        are fine — slots execute in list order inside the program).

        Chunk block tables here are max_model_len-wide (one compiled
        program per slot COUNT instead of per length bucket, so the whole
        mixed family prebakes exactly). That trades the bucketed table's
        smaller attention gather window for a closed program set; keep
        ``chunk_budget`` modest on long-context TPU deployments.

        Returns (chunk_outs, decode_out): a (token, logprob, top_ids,
        top_logprobs) tuple per chunk slot (meaningful only on a final
        chunk) and one for the decode batch."""
        C = self.prefill_chunk_tokens
        host_chunks = []
        slots = self._lane_slots(state_slots, len(chunks))
        chunk_wants = self._want_lanes(chunk_want_logprobs, len(chunks))
        for i, (token_chunk, chunk_start, total_len, block_ids, temperature,
                top_p, top_k, rep_pen, key_data, c_eos_ids,
                c_eos_suppress) in enumerate(chunks):
            n = len(token_chunk)
            ctoks = np.zeros(C, np.int32)
            ctoks[:n] = token_chunk
            table = self._table(block_ids, self.max_blocks_per_seq)
            if key_data is None:
                key_data = self._next_key_data()
            if c_eos_ids is None:
                c_eos_ids = np.full(MAX_EOS_IDS, -1, np.int32)
            host_chunks.append((
                ctoks, np.int32(chunk_start), np.int32(total_len), table,
                key_data, np.float32(temperature), np.float32(top_p),
                np.int32(top_k), chunk_wants[i], np.float32(rep_pen),
                np.asarray(c_eos_ids, np.int32), np.bool_(c_eos_suppress),
                *(() if slots is None else (slots[i],)),
            ))
        B = len(np.asarray(tokens))
        if eos_ids is None:
            eos_ids = np.full((B, MAX_EOS_IDS), -1, np.int32)
        if eos_suppress is None:
            eos_suppress = np.zeros(B, bool)
        k = len(host_chunks)
        out = self._launch(
            self._mixed_jit_for(k), tuple(host_chunks),
            tokens, positions, block_tables, slot_indices, keys, temps,
            top_ps, top_ks, self._want_lanes(want_logprobs, B),
            np.asarray(eos_ids, np.int32), np.asarray(eos_suppress, bool),
        )
        chunk_outs = tuple(out[4 * i: 4 * i + 4] for i in range(k))
        return chunk_outs, tuple(out[4 * k: 4 * k + 4])

    def fetch_sample(self, out: tuple) -> tuple[np.ndarray, ...]:
        """Fetch a (tokens, logprobs, top_ids, top_lps) output tuple with
        ONE host round trip: the device arrays are packed into a single
        flat f32 buffer on device (token ids < 2^24 are exact in f32) and
        split back on the host, instead of four separate fetches per
        prefill/packed/chunk call. Tuples that
        are already host numpy (multihost SpmdModelRunner pre-fetches)
        pass through untouched."""
        if isinstance(out[0], np.ndarray):
            return tuple(out)
        if self._pack_fetch_jit is None:
            self._pack_fetch_jit = jax.jit(
                lambda *xs: jnp.concatenate(
                    [jnp.ravel(x).astype(jnp.float32) for x in xs]
                ),
                **(
                    {"out_shardings": self._repl}
                    if self._repl is not None
                    else {}
                ),
            )
        flat = self._fetch(out, pack=self._pack_fetch_jit)
        outs: list[np.ndarray] = []
        off = 0
        for o in out:
            n = int(np.prod(o.shape)) if o.shape else 1
            piece = flat[off:off + n].reshape(o.shape)
            off += n
            # restore each output's dtype (ids must come back int32, not a
            # float32 trap for consumers that index/serialize with them)
            outs.append(np.asarray(piece, dtype=o.dtype))
        return tuple(outs)

    @staticmethod
    def _want_lanes(want_logprobs, n: int) -> np.ndarray:
        """[n] bool: which lanes of a call asked for log-probs. A caller
        that does not say gets the surface for every lane, as before the
        sampler learned to leave it out; the engine always says."""
        if want_logprobs is None:
            return np.ones(n, bool)
        return np.asarray(want_logprobs, bool)

    def _lane_slots(self, slots, n: int) -> Optional[np.ndarray]:
        """[n] int32 lane slots for a prefill call, or None for a model
        that keeps no slot a sequence. Such a model's prefill writes each
        sequence's state where its decode steps will read it, so it must
        be told."""
        if not self.state_slots:
            return None
        if slots is None or len(slots) != n:
            raise ValueError(
                "this model keeps a recurrent state a sequence: a prefill "
                "call must name the lane slot of each sequence it holds"
            )
        return np.asarray(slots, np.int32)

    def _next_key_data(self) -> np.ndarray:
        """Default per-call RNG stream: raw threefry key data built on the
        host with numpy (ops/sampling.make_key_data). Multi-controller:
        every process derives the identical row because followers replay
        calls in order, keeping step counters in sync."""
        from dynamo_tpu.ops.sampling import make_key_data

        self._step_counter += 1
        return make_key_data(self._rng_seed, self._step_counter)

    # Decode defaults draw from a distinct threefry stream id so (stream,
    # counter) rows can never collide with prefill's (_rng_seed, step) rows,
    # and the counter advances by B per step (monotonic offset) so rows
    # never repeat when the batch size varies across steps.
    _DECODE_STREAM_SALT = 0x9E3779B9

    def _next_decode_keys(self, B: int) -> np.ndarray:
        keys = np.stack(
            [
                np.full(
                    B,
                    (self._rng_seed ^ self._DECODE_STREAM_SALT) & 0xFFFFFFFF,
                    np.uint32,
                ),
                (np.arange(B, dtype=np.uint32)
                 + np.uint32(self._key_offset & 0xFFFFFFFF)),
            ],
            axis=1,
        )
        self._key_offset += B
        return keys

    def _to_dev(self, a) -> jax.Array:
        """Commit a host input, one transfer: local array normally;
        fully-replicated GLOBAL array under multi-controller (all processes
        pass the same value — the SPMD step channel guarantees it). What is
        on the device already stays as it is and is not counted. A step
        commits one array a call, its packed buffer (`_launch`); the block
        movement paths and `embed` commit theirs one by one."""
        host = isinstance(a, (np.ndarray, np.generic))
        if not host and isinstance(a, jax.Array):
            return a
        if self._repl is not None:
            a = np.asarray(a)
            out = jax.make_array_from_process_local_data(
                self._repl, a, global_shape=a.shape
            )
        else:
            out = jnp.asarray(a)
        self.launch.upload_arrays += 1
        # numpy's own count where there is one: a device array's `nbytes`
        # is a product computed in Python, a microsecond
        self.launch.upload_bytes += a.nbytes if host else out.nbytes
        return out

    @staticmethod
    def _step_jit(impl, *bound, n_static: int = 0, **jit_kwargs):
        """The jitted program of a step as `_launch` calls it: behind the
        layout and the impl's own `n_static` leading arguments (all static)
        come params, the two caches (donated), the packed buffer and the
        leaves beside it. It takes the buffer apart (`unpack_inputs`) and
        calls `impl`, behind its `bound` arguments, with the host arguments
        `_launch` was given, unchanged."""

        def program(layout, *args):
            static, args = args[:n_static], args[n_static:]
            params, k_cache, v_cache, buf, *beside = args
            host, host_kw = unpack_inputs(layout, buf, beside)
            return impl(
                *bound, *static, params, k_cache, v_cache, *host, **host_kw
            )

        return jax.jit(
            program,
            static_argnums=tuple(range(1 + n_static)),
            donate_argnums=(2 + n_static, 3 + n_static),  # k_cache, v_cache
            **jit_kwargs,
        )

    def _commit(self, *host, **host_kw) -> tuple:
        """A step's host inputs on the device, one array a call: the packed
        buffer of `pack_inputs` through `_to_dev`, and beside it whatever
        does not pack (already on the device: untouched and uncounted).
        Gives the layout and the program's trailing arguments."""
        layout, buf, beside = pack_inputs((host, host_kw))
        return layout, (self._to_dev(buf), *map(self._to_dev, beside))

    def _launch(self, program, *host, static: tuple = (), **host_kw):
        """Commit a step's host inputs and call its program on them, behind
        `params` and the cache arrays, which the program hands back. The two
        halves of every step method, so that each is one span: the pack and
        its one `_to_dev`, one array a call, in `runner.upload`; the jitted
        call up to the return of its output arrays in `runner.enqueue` (a
        label's first time, JAX's trace, lowering and compile; warm,
        argument handling and the hand-over to the runtime: the device's
        work is waited for in `runner.fetch`)."""
        with dtrace.phase("runner.upload") as up:
            layout, dev = self._commit(*host, **host_kw)
        with dtrace.phase("runner.enqueue") as enq:
            out, self.k_cache, self.v_cache = program(
                layout, *static, self.params, self.k_cache, self.v_cache,
                *dev,
            )
        self.launch.upload_s += up.seconds
        self.launch.enqueue_s += enq.seconds
        return out

    def _fetch(self, x, pack=None) -> np.ndarray:
        """Host-side read of a (replicated) device result, the one place a
        step's result reaches the host (`runner.fetch`): `pack`, where
        given, first makes one device array of a tuple of them."""
        with dtrace.phase("runner.fetch") as ph:
            if pack is not None:
                x = pack(*x)
            if self._repl is not None:
                out = np.asarray(x.addressable_data(0))
            else:
                out = np.asarray(jax.device_get(x))
        self.launch.fetch_bytes += out.nbytes
        self.launch.fetch_s += ph.seconds
        return out

    def fetch_horizon(self, packed: jax.Array) -> np.ndarray:
        """The packed array of a `decode_multi` or `spec_verify` dispatch as
        host numpy: the horizon's one fetch."""
        return self._fetch(packed)

    # -------------------------------------------------------------- calls

    def pick_bucket(self, length: int) -> int:
        for b in self.prefill_buckets:
            if b >= length:
                return b
        raise ValueError(
            f"prompt length {length} exceeds max_model_len {self.max_model_len}"
        )

    def prefill(
        self,
        token_ids: list[int],
        block_ids: list[int],
        temperature: float,
        top_p: float,
        top_k: int,
        rep_pen: float = 1.0,
        key_data: Optional[np.ndarray] = None,
        eos_ids: Optional[np.ndarray] = None,  # [MAX_EOS_IDS] i32, -1 pad
        eos_suppress: bool = False,  # min_tokens not yet reached
        state_slots: Optional[list[int]] = None,  # [the sequence's lane slot]
        want_logprobs: bool = True,  # False: the last three come back zeros
    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Run one prompt; returns (token, logprob, top_ids, top_logprobs)
        device arrays for the first sampled token."""
        T = len(token_ids)
        bucket = self.pick_bucket(T)
        tokens = np.zeros(bucket, np.int32)
        tokens[:T] = token_ids
        nb = bucket // self.block_size
        used = (T + self.block_size - 1) // self.block_size
        table = self._table(block_ids[:used], nb)
        # padding region scatters into the null block 0 — harmless.
        # Ring attention only pays off past a length threshold: short
        # prompts skip the sp ppermute rounds and run the serial path.
        prefill_fn = (
            self._prefill_cp_jit
            if (
                self._use_cp_prefill
                and bucket >= self.cp_min_tokens
                and bucket % self.mesh.shape["sp"] == 0
            )
            else self._prefill_jit
        )
        if key_data is None:
            key_data = self._next_key_data()
        if eos_ids is None:
            eos_ids = np.full(MAX_EOS_IDS, -1, np.int32)
        return self._launch(
            prefill_fn, tokens, np.int32(T), table, key_data,
            np.float32(temperature), np.float32(top_p), np.int32(top_k),
            np.bool_(want_logprobs), np.float32(rep_pen),
            np.asarray(eos_ids, np.int32), np.bool_(eos_suppress),
            *self._slot_args(state_slots),
        )

    def prefill_mm(
        self,
        token_ids: list[int],  # image placeholders already expanded
        block_ids: list[int],
        mm_embeds: np.ndarray,  # [M, hidden] vision embeddings
        mm_start: int,  # first expanded-placeholder index
        temperature: float,
        top_p: float,
        top_k: int,
        rep_pen: float = 1.0,
        key_data: Optional[np.ndarray] = None,
        eos_ids: Optional[np.ndarray] = None,
        eos_suppress: bool = False,
        want_logprobs: bool = True,
    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Multimodal prefill (vision embeddings spliced over placeholder
        positions — reference prefill_worker.py:249-258). Jitted lazily so
        text-only deployments never compile it; one program per (bucket,
        num_patches) pair."""
        if not hasattr(self, "_prefill_mm_jit"):
            self._prefill_mm_jit = self._step_jit(
                self._prefill_mm_impl, self.config,
                self.mesh, self._attn_head_axis,
            )
        T = len(token_ids)
        bucket = self.pick_bucket(T)
        tokens = np.zeros(bucket, np.int32)
        tokens[:T] = token_ids
        nb = bucket // self.block_size
        table = np.zeros(nb, np.int32)
        used = (T + self.block_size - 1) // self.block_size
        table[:used] = block_ids[:used]
        if key_data is None:
            key_data = self._next_key_data()
        if eos_ids is None:
            eos_ids = np.full(MAX_EOS_IDS, -1, np.int32)
        # device-path embeddings (already jax arrays, e.g. handed over via
        # transfer_embeds_device) stay on device; host payloads upload here
        if not isinstance(mm_embeds, jax.Array):
            mm_embeds = np.asarray(mm_embeds, np.float32)
        return self._launch(
            self._prefill_mm_jit, tokens, np.int32(T), table, mm_embeds,
            np.int32(mm_start), key_data, np.float32(temperature),
            np.float32(top_p), np.int32(top_k), np.bool_(want_logprobs),
            np.float32(rep_pen), np.asarray(eos_ids, np.int32),
            np.bool_(eos_suppress),
        )

    def prefill_chunk(
        self,
        token_chunk: list[int],
        chunk_start: int,
        total_len: int,
        block_ids: list[int],
        temperature: float,
        top_p: float,
        top_k: int,
        rep_pen: float = 1.0,
        key_data: Optional[np.ndarray] = None,
        eos_ids: Optional[np.ndarray] = None,
        eos_suppress: bool = False,
        state_slots: Optional[list[int]] = None,  # [the sequence's lane slot]
        want_logprobs: bool = True,
    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Run one chunk of a chunked prefill; chunks must arrive in order.

        Returns (token, logprob, top_ids, top_logprobs) — meaningful only
        on the final chunk."""
        C = self.prefill_chunk_tokens
        n = len(token_chunk)
        tokens = np.zeros(C, np.int32)
        tokens[:n] = token_chunk
        # table width = the prompt's bucket, not max_model_len: chunk
        # attention gathers the whole table window per chunk, so a static
        # max-width table would make every chunk pay O(max_model_len) HBM
        # regardless of prompt length (one compiled program per bucket,
        # same as single-shot prefill)
        nb_table = self.pick_bucket(total_len) // self.block_size
        table = self._table(block_ids, nb_table)
        if key_data is None:
            key_data = self._next_key_data()
        if eos_ids is None:
            eos_ids = np.full(MAX_EOS_IDS, -1, np.int32)
        return self._launch(
            self._chunk_jit, tokens, np.int32(chunk_start),
            np.int32(total_len), table, key_data, np.float32(temperature),
            np.float32(top_p), np.int32(top_k), np.bool_(want_logprobs),
            np.float32(rep_pen), np.asarray(eos_ids, np.int32),
            np.bool_(eos_suppress), *self._slot_args(state_slots),
        )

    def _table(self, block_ids, nb: int) -> np.ndarray:
        """One sequence's block table, `nb` entries a page group: its blocks
        (the null block behind them), and for a model with a window group
        each block's companion there behind those (the null block where it
        was given back)."""
        n = min(len(block_ids), nb)
        table = np.zeros(nb * len(self.page_groups), np.int32)
        table[:n] = block_ids[:n]
        if self.window_blocks:
            table[nb : nb + n] = self.window_of[table[:n]]
        return table

    def _slot_args(self, state_slots) -> tuple:
        """The trailing argument of a one-sequence prefill call: its lane
        slot, or nothing."""
        slots = self._lane_slots(state_slots, 1)
        return () if slots is None else (slots[0],)

    def embed(self, token_ids: list[int]) -> np.ndarray:
        """Pooled sequence embedding (llama.embed_pooled), bucket-padded;
        the jit is created lazily so serving-only deployments never compile
        it."""
        if not hasattr(self, "_embed_jit"):
            cfg = self.config
            self._embed_jit = jax.jit(
                lambda p, t, v: forward_for(cfg).embed_pooled(p, cfg, t, v)
            )
        T = len(token_ids)
        bucket = self.pick_bucket(T)
        tokens = np.zeros(bucket, np.int32)
        tokens[:T] = token_ids
        out = self._embed_jit(
            {"embed": self.params["embed"],
             "layers": self.params["layers"],
             "final_norm": self.params["final_norm"],
             **({"lm_head": self.params["lm_head"]}
                if "lm_head" in self.params else {})},
            self._to_dev(tokens),
            self._to_dev(np.int32(T)),
        )
        return self._fetch(out)

    def pack_prefill(
        self, seqs: list[tuple], state_slots: Optional[list[int]] = None,
        want_logprobs: Optional[list[bool]] = None,  # each sequence's
    ) -> dict[str, np.ndarray]:
        """Pure host-side packing for the batched-prefill program.

        seqs: [(token_ids, block_ids, temp, top_p, top_k, rep_pen,
        key_row [2] uint32, eos_row [MAX_EOS_IDS] i32, suppress bool), ...]
        with total tokens <= prefill_chunk_tokens and len(seqs) <=
        max_batch. Padding lanes carry segment -1, scatter into null
        block 0 and ask for no log-probs."""
        P = self.prefill_chunk_tokens
        N = self.max_batch
        bs = self.block_size
        assert len(seqs) <= N, f"{len(seqs)} segments > max_batch {N}"
        tokens = np.zeros(P, np.int32)
        positions = np.zeros(P, np.int32)
        segment_ids = np.full(P, -1, np.int32)
        # both groups' slots side by side where the paged layers are of two
        G = len(self.page_groups)
        slot_indices = np.zeros(G * P, np.int32)
        last_idx = np.zeros(N, np.int32)
        temps = np.zeros(N, np.float32)
        top_ps = np.ones(N, np.float32)
        top_ks = np.zeros(N, np.int32)
        rep_pens = np.ones(N, np.float32)
        keys = np.zeros((N, 2), np.uint32)
        eos_ids = np.full((N, MAX_EOS_IDS), -1, np.int32)
        eos_suppress = np.zeros(N, bool)
        want_lps = np.zeros(N, bool)
        want_lps[: len(seqs)] = self._want_lanes(want_logprobs, len(seqs))
        off = 0
        for i, (tids, bids, te, tp_, tk, rp, kd, er, sup) in enumerate(seqs):
            T = len(tids)
            assert off + T <= P, f"pack overflow: {off}+{T} > {P}"
            tokens[off : off + T] = tids
            positions[off : off + T] = np.arange(T)
            segment_ids[off : off + T] = i
            t_idx = np.arange(T)
            blocks = np.asarray(bids, np.int64)[t_idx // bs]
            slot_indices[off : off + T] = blocks * bs + t_idx % bs
            if G > 1:
                slot_indices[P + off : P + off + T] = (
                    self.window_of[blocks].astype(np.int64) * bs + t_idx % bs
                )
            last_idx[i] = off + T - 1
            temps[i], top_ps[i], top_ks[i], rep_pens[i] = te, tp_, tk, rp
            keys[i] = kd
            eos_ids[i] = er
            eos_suppress[i] = sup
            off += T
        packed = dict(
            tokens=tokens, positions=positions, segment_ids=segment_ids,
            slot_indices=slot_indices, last_idx=last_idx, temps=temps,
            top_ps=top_ps, top_ks=top_ks, rep_pens=rep_pens, keys=keys,
            eos_ids=eos_ids, eos_suppress=eos_suppress,
            want_logprobs=want_lps,
        )
        slots = self._lane_slots(state_slots, len(seqs))
        if slots is not None:
            # a segment that holds no prompt keeps slot 0 here; the program
            # sends what it computes for it to the null lane
            packed["state_slots"] = np.zeros(N, np.int32)
            packed["state_slots"][: len(seqs)] = slots
        return packed

    def prefill_packed_arrays(
        self, tokens, positions, segment_ids, slot_indices, last_idx,
        temps, top_ps, top_ks, rep_pens, keys, eos_ids=None,
        eos_suppress=None, state_slots=None, want_logprobs=None,
    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Run the packed batched-prefill program (arrays from
        pack_prefill). Returns (tokens, logprobs, top_ids, top_lps), each
        [max_batch]-major; only the first len(seqs) rows are meaningful."""
        N = len(last_idx)
        if eos_ids is None:
            eos_ids = np.full((N, MAX_EOS_IDS), -1, np.int32)
        if eos_suppress is None:
            eos_suppress = np.zeros(N, bool)
        return self._launch(
            self._packed_jit, tokens, positions, segment_ids, slot_indices,
            last_idx, keys, temps, top_ps, top_ks,
            self._want_lanes(want_logprobs, N), rep_pens,
            np.asarray(eos_ids, np.int32), np.asarray(eos_suppress, bool),
            *(() if state_slots is None else (state_slots,)),
        )

    def require_block_transfer(self, what: str) -> None:
        """Blocks leave and enter the cache as `[L, Hkv, n, bs, D]` pairs of
        keys and values (disagg frames, block-manager tiers, peer pulls):
        refuse in words for a cache that keeps another kind of plane."""
        if self.window_blocks:
            raise ValueError(
                f"{what} moves cache blocks as keys and values by head of "
                "every layer under one block id; this model's paged layers "
                "are of two groups, each with its own blocks, and its window "
                "layers give theirs back, which is not carried through "
                "transfer or tiers yet"
            )
        if self.state_slots:
            raise ValueError(
                f"{what} moves cache blocks as keys and values by head; "
                f"{self.recurrent_layers} of this model's "
                f"{len(self.layer_kinds)} layers keep a "
                "recurrent state a sequence and no block, which is not "
                "carried through transfer or tiers yet"
            )
        if self.cache_kind.planes != 2:
            raise ValueError(
                f"{what} moves cache blocks as keys and values by head; "
                f"this model keeps a {self.cache_kind.name} plane of "
                f"{self.cache_kind.width} values a token, which is not "
                "carried through transfer or tiers yet"
            )

    def _pad_block_count(self, n: int) -> int:
        """Smallest bucket block count >= n (bounds compiled program count).

        Sequences longer than the largest bucket (possible with custom
        prefill_buckets below max_model_len) pad to their exact length —
        one extra compiled program beats broken offload/shipping."""
        for b in self.prefill_buckets:
            nb = b // self.block_size
            if nb >= n:
                return nb
        return n

    def extract_blocks(
        self, block_ids: list[int]
    ) -> tuple[np.ndarray, np.ndarray]:
        """Gather dense KV blocks [L, Hkv, n, bs, D] for disagg shipping."""
        self.require_block_transfer("extract_blocks")
        n = len(block_ids)
        padded = self._pad_block_count(n)
        ids = np.zeros(padded, np.int32)
        ids[:n] = block_ids
        k, v = self._extract_jit(
            self.k_cache, self.v_cache, self._to_dev(ids)
        )
        return self._fetch(k)[:, :, :n], self._fetch(v)[:, :, :n]

    def extract_blocks_tight(
        self, block_ids: list[int]
    ) -> tuple[np.ndarray, np.ndarray]:
        """extract_blocks with tight padding for the streaming data plane.

        Per-chunk frames gather only a handful of blocks; padding those to
        the prompt's PREFILL bucket (what extract_blocks does — right for
        whole-sequence ships) would make every small frame pay a
        bucket-sized gather + fetch. Pad to the next power of two instead,
        capped at the bucket pad: compiled-program count stays O(log n),
        frame extracts stay O(frame)."""
        self.require_block_transfer("extract_blocks_tight")
        n = len(block_ids)
        pow2 = 1
        while pow2 < n:
            pow2 <<= 1
        padded = min(pow2, self._pad_block_count(n))
        ids = np.zeros(padded, np.int32)
        ids[:n] = block_ids
        k, v = self._extract_jit(
            self.k_cache, self.v_cache, self._to_dev(ids)
        )
        return self._fetch(k)[:, :, :n], self._fetch(v)[:, :, :n]

    def _quant_pad_ids(self, block_ids: list[int], tight: bool) -> np.ndarray:
        n = len(block_ids)
        if tight:
            pow2 = 1
            while pow2 < n:
                pow2 <<= 1
            padded = min(pow2, self._pad_block_count(n))
        else:
            padded = self._pad_block_count(n)
        ids = np.zeros(padded, np.int32)
        ids[:n] = block_ids
        return ids

    def extract_blocks_quant(
        self, block_ids: list[int], tight: bool = False
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Gather int8-resident blocks VERBATIM: (kq [L, Hkv, n, bs, D]
        int8, ks [L, Hkv, n] f32, vq, vs) — the exact mantissas+scales the
        wire codec would produce, so disagg frames and offload tiers ship
        them with no recode and no double quantization. Only valid on an
        int8-resident runner (kv_quantized)."""
        assert self.kv_quantized, "extract_blocks_quant needs an int8 cache"
        n = len(block_ids)
        ids = self._quant_pad_ids(block_ids, tight)
        kq, ks, vq, vs = self._extract_q_jit(
            self.k_cache, self.v_cache, self._to_dev(ids)
        )
        return (
            self._fetch(kq)[:, :, :n], self._fetch(ks)[:, :, :n],
            self._fetch(vq)[:, :, :n], self._fetch(vs)[:, :, :n],
        )

    def inject_blocks_quant(
        self,
        block_ids: list[int],
        kq: np.ndarray,  # [L, Hkv, n, bs, D] int8 mantissas
        ks: np.ndarray,  # [L, Hkv, n] f32 scales
        vq: np.ndarray,
        vs: np.ndarray,
    ) -> None:
        """Scatter already-quantized blocks verbatim (the landing half of
        the no-recode path: int8 wire frames / int8 tier pages go straight
        into the int8-resident cache)."""
        assert self.kv_quantized, "inject_blocks_quant needs an int8 cache"
        n = len(block_ids)
        ids = self._quant_pad_ids(block_ids, tight=False)
        padded = len(ids)
        if padded != n:
            pad = padded - n
            kq = np.concatenate(
                [kq, np.zeros(kq.shape[:2] + (pad,) + kq.shape[3:], kq.dtype)],
                axis=2,
            )
            vq = np.concatenate(
                [vq, np.zeros(vq.shape[:2] + (pad,) + vq.shape[3:], vq.dtype)],
                axis=2,
            )
            ks = np.concatenate(
                [ks, np.zeros(ks.shape[:2] + (pad,), ks.dtype)], axis=2
            )
            vs = np.concatenate(
                [vs, np.zeros(vs.shape[:2] + (pad,), vs.dtype)], axis=2
            )
        self.k_cache, self.v_cache = self._inject_q_jit(
            self.k_cache, self.v_cache, self._to_dev(ids),
            self._to_dev(np.ascontiguousarray(kq, np.int8)),
            self._to_dev(np.ascontiguousarray(ks, np.float32)),
            self._to_dev(np.ascontiguousarray(vq, np.int8)),
            self._to_dev(np.ascontiguousarray(vs, np.float32)),
        )

    def extract_blocks_device(
        self, block_ids: list[int]
    ) -> tuple[jax.Array, jax.Array, int]:
        """Gather dense KV blocks WITHOUT fetching to host: returns
        (k, v, n) device arrays [L, Hkv, padded, bs, D] where the first `n`
        block lanes are valid. The device-native disagg path — colocated
        decode engines consume these via inject_blocks_device and the
        blocks never leave HBM (the reference's GPUDirect-RDMA role,
        docs/architecture/disagg_serving.md:76-118)."""
        self.require_block_transfer("extract_blocks_device")
        n = len(block_ids)
        padded = self._pad_block_count(n)
        ids = np.zeros(padded, np.int32)
        ids[:n] = block_ids
        k, v = self._extract_jit(
            self.k_cache, self.v_cache, self._to_dev(ids)
        )
        return k, v, n

    def inject_blocks_device(
        self,
        block_ids: list[int],
        k_dev: jax.Array,
        v_dev: jax.Array,
    ) -> None:
        """Scatter DEVICE KV blocks (from a colocated prefill engine's
        mesh) into this cache. `jax.device_put` moves the buffers onto this
        runner's devices/sharding first — on a shared TPU slice that is an
        ICI copy, no host round-trip, no serialization. Padding lanes
        target null block 0."""
        self.require_block_transfer("inject_blocks_device")
        n = len(block_ids)
        padded = self._pad_block_count(n)
        ids = np.zeros(padded, np.int32)
        ids[:n] = block_ids
        if k_dev.shape[2] != padded:
            if k_dev.shape[2] > padded:
                k_dev = k_dev[:, :, :padded]
                v_dev = v_dev[:, :, :padded]
            else:
                pad = padded - k_dev.shape[2]
                shape = k_dev.shape[:2] + (pad,) + k_dev.shape[3:]
                zpad = jnp.zeros(shape, k_dev.dtype)
                k_dev = jnp.concatenate([k_dev, zpad], axis=2)
                v_dev = jnp.concatenate([v_dev, zpad], axis=2)
        # land the buffers on THIS runner's devices (mesh-to-mesh move);
        # replicated here — the pinned inject out_sharding reshards into
        # the paged cache's layout
        target = (
            self._repl
            if self._repl is not None
            else (
                jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec()
                )
                if self.mesh is not None
                else jax.tree_util.tree_leaves(self.k_cache)[0].devices().pop()
            )
        )
        k_dev = jax.device_put(k_dev, target)
        v_dev = jax.device_put(v_dev, target)
        self.k_cache, self.v_cache = self._inject_jit(
            self.k_cache, self.v_cache, self._to_dev(ids), k_dev, v_dev
        )

    def inject_blocks(
        self, block_ids: list[int], k_blocks: np.ndarray, v_blocks: np.ndarray
    ) -> None:
        """Scatter received dense KV blocks into this cache at block_ids.

        Padding lanes target the null block 0 (a designated garbage sink).
        When the cache is TP-sharded, the scatter's pinned out_sharding makes
        XLA reshard the incoming dense blocks — the block_copy.cu equivalent.
        """
        self.require_block_transfer("inject_blocks")
        n = len(block_ids)
        padded = self._pad_block_count(n)
        ids = np.zeros(padded, np.int32)
        ids[:n] = block_ids
        if padded != n:
            pad_shape = k_blocks.shape[:2] + (padded - n,) + k_blocks.shape[3:]
            zpad = np.zeros(pad_shape, k_blocks.dtype)
            k_blocks = np.concatenate([k_blocks, zpad], axis=2)
            v_blocks = np.concatenate([v_blocks, zpad], axis=2)
        self.k_cache, self.v_cache = self._inject_jit(
            self.k_cache,
            self.v_cache,
            self._to_dev(ids),
            self._to_dev(k_blocks),
            self._to_dev(v_blocks),
        )

    def decode(
        self,
        tokens: np.ndarray,  # [B] int32
        positions: np.ndarray,  # [B] int32
        block_tables: np.ndarray,  # [B, max_blocks_per_seq] int32
        slot_indices: np.ndarray,  # [B] int32
        temps: np.ndarray,
        top_ps: np.ndarray,
        top_ks: np.ndarray,
        keys: Optional[np.ndarray] = None,  # [B, 2] uint32 threefry rows
        penalties: Optional[tuple] = None,
        # penalties = (hist [B, L] i32, hist_len [B] i32, prompt_len [B]
        # i32, freq [B] f32, pres [B] f32, rep [B] f32,
        # eos_ids [B, MAX_EOS_IDS] i32, eos_suppress [B] bool); routes to
        # the lazily-compiled penalty program (ref validate.rs:95-125 — the
        # options are implemented here, not accepted-and-dropped; the eos
        # mask implements min_tokens)
        eos_mask: Optional[tuple] = None,
        # eos_mask = (eos_ids [B, MAX_EOS_IDS] i32, eos_suppress [B] bool):
        # min_tokens without penalties — masks EOS on device but skips the
        # [B, L] history transfer. Ignored when penalties is given.
        want_logprobs: Optional[np.ndarray] = None,  # [B] bool
        # lanes whose request asked for log-probs: the last three results
        # are computed only in a step where one did, else zeros
    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """One batched decode step. Returns (tokens, logprobs, top_ids,
        top_logprobs) device arrays, each batch-major."""
        if keys is None:
            keys = self._next_decode_keys(tokens.shape[0])
        if penalties is not None:
            program, extra = self._decode_pen_fn, penalties
        elif eos_mask is not None:
            program, extra = self._decode_eos_fn, eos_mask
        else:
            program, extra = self._decode_fn, ()
        return self._launch(
            program, tokens, positions, block_tables, slot_indices, keys,
            temps, top_ps, top_ks,
            self._want_lanes(want_logprobs, tokens.shape[0]), *extra,
        )

    def decode_multi(
        self,
        H: int,
        tokens: np.ndarray,  # [B] i32 last sampled token per lane
        positions: np.ndarray,  # [B] i32 position of that token
        block_tables: np.ndarray,  # [B, max_blocks_per_seq] i32 — must
        # already cover positions+H writes (engine preallocates)
        temps: np.ndarray,
        top_ps: np.ndarray,
        top_ks: np.ndarray,
        keys: np.ndarray,  # [B, 2] u32 step-0 threefry rows
        active: np.ndarray,  # [B] bool
        limit_remaining: np.ndarray,  # [B] i32
        min_remaining: np.ndarray,  # [B] i32
        eos_ids: np.ndarray,  # [B, MAX_EOS_IDS] i32
        penalties: Optional[tuple] = None,
        # penalties = (hist [B, L] i32, hist_len [B] i32, prompt_len [B]
        # i32, freq [B] f32, pres [B] f32, rep [B] f32): uploaded once per
        # horizon, scattered into on-device count tables (a second trace
        # of the same program; plain batches never pay the [B, L] input)
        chain: Optional[np.ndarray] = None,  # [B] bool
        # lanes that go on from where the previous `decode_multi` call left
        # them on the device, whose result the caller need not have read:
        # for them every host array describes the lane as of THAT call's
        # start (`_decode_multi_impl`). None: no lane does.
        want_logprobs: Optional[np.ndarray] = None,  # [B] bool, as `decode`'s
    ) -> jax.Array:
        """H chained decode steps; returns the packed [H, B, 2+2*num_top]
        f32 device array (token, logprob, top_ids, top_lps per step) — ONE
        host fetch per horizon. See _decode_multi_impl for freeze rules.
        The lanes' carry stays on the device for the next call."""
        B = tokens.shape[0]
        if chain is None:
            chain = np.zeros(B, bool)
        carry = self._horizon_carry
        if carry is None or carry[0].shape[0] != B:
            if chain.any():
                raise ValueError(
                    "decode_multi: no earlier call of this batch size to "
                    "chain on"
                )
            carry = self._zero_carry(B)
        packed, self._horizon_carry = self._launch(
            self._decode_multi_fn, tokens, positions, block_tables, keys,
            temps, top_ps, top_ks, self._want_lanes(want_logprobs, B),
            active, limit_remaining, min_remaining,
            eos_ids, chain, carry, static=(H,),
            **({} if penalties is None else {"pen": tuple(penalties)}),
        )
        return packed

    def _zero_carry(self, B: int) -> tuple:
        """A carry nobody chains on, of the shapes and the placement the
        program hands back, so that the first call and every later one are
        one compiled program."""
        zeros = (
            np.zeros(B, np.int32), np.zeros(B, np.int32), np.ones(B, bool),
            np.zeros(B, np.int32),
        )
        if self._repl is not None:
            return tuple(
                jax.make_array_from_process_local_data(
                    self._repl, z, global_shape=z.shape
                )
                for z in zeros
            )
        return tuple(jnp.asarray(z) for z in zeros)

    def spec_verify(
        self,
        spec_k: int,
        extras: int,
        tokens: np.ndarray,  # [B] i32 last accepted token per lane
        drafts: np.ndarray,  # [B, spec_k] i32 draft tokens (-1 pads)
        draft_len: np.ndarray,  # [B] i32
        positions: np.ndarray,  # [B] i32 position of `tokens`
        block_tables: np.ndarray,  # [B, max_blocks_per_seq] i32 — must
        # already cover positions + draft_len + extras writes
        temps: np.ndarray,
        top_ps: np.ndarray,
        top_ks: np.ndarray,
        keys: np.ndarray,  # [B, 2] u32 step-0 threefry rows
        active: np.ndarray,  # [B] bool
        limit_remaining: np.ndarray,  # [B] i32
        min_remaining: np.ndarray,  # [B] i32
        eos_ids: np.ndarray,  # [B, MAX_EOS_IDS] i32
        penalties: Optional[tuple] = None,  # decode_multi's 6-tuple
        want_logprobs: Optional[np.ndarray] = None,  # [B] bool, as `decode`'s
    ) -> jax.Array:
        """Speculative draft-verify dispatch: ONE weight pass scores the
        spec_k + 1 draft positions per lane, then `extras` chained decode
        steps ride the same dispatch from the device-computed accept point
        (see _spec_verify_impl). Returns the packed
        [spec_k + 1 + extras, B, 2 + 2*num_top] f32 device array. Jitted
        lazily so spec-off deployments never trace it; one program per
        (spec_k, extras) pair."""
        if not hasattr(self, "_spec_verify_jit"):
            spec_out = (
                (self._repl, self._kv_shard_tree, self._kv_shard_tree)
                if self._kv_sharding is not None
                else None
            )
            self._spec_verify_jit = self._step_jit(
                self._spec_verify_impl, self.config,
                self.mesh, self._attn_head_axis, self.block_size,
                n_static=2,  # S, E
                **(
                    {"out_shardings": spec_out}
                    if spec_out is not None
                    else {}
                ),
            )
        return self._launch(
            self._spec_verify_jit, tokens, drafts, draft_len, positions,
            block_tables, keys, temps, top_ps, top_ks,
            self._want_lanes(want_logprobs, tokens.shape[0]), active,
            limit_remaining, min_remaining, eos_ids,
            static=(spec_k + 1, extras),
            **({} if penalties is None else {"pen": tuple(penalties)}),
        )

    def step_stats(self, packed: np.ndarray) -> Optional[dict[str, float]]:
        """What the model counted on the device during one fetched
        `decode_multi` horizon, summed over its steps and layers (the row
        behind the lanes); None for a family that counts nothing."""
        names = getattr(forward_for(self.config), "STEP_STATS", ())
        if not names:
            return None
        # the last row, whatever batch bucket the horizon was compiled for
        row = packed[:, -1, : len(names)]  # [H, names]
        return {name: float(row[:, j].sum()) for j, name in enumerate(names)}


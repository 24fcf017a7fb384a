"""Bench-top timing of the dense paged decode attention call alone.

One decode step of a dense model calls the kernel once a layer on the same
lanes. This times a chain of `--calls` such calls (each call's output feeds
the next call's query, so they run one after another as a step's do) at a
model's head counts, for a mix of live and idle lanes, and holds the result
to the XLA form. It needs the chip: times from anywhere else mean nothing,
so it refuses to run without one.

    chiprun -- python benchmarks/paged_decode_benchtop.py
    ... --kernel-file <another tree's ops/pallas_attention.py>   # its kernel

`--kernel-file` loads another tree's kernel in this one's place (the parent
commit's, to read both on one chip); `--idle-context 1 0` also reads the idle
lanes at a context of 1, which is how they reached the kernel before PR 29.
Prints one JSON line a reading.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops import attention as A

SHAPES = {  # query heads, KV heads, layers (for the per-step column)
    "mistral7b": (32, 8, 32),
    "qwen25-7b": (28, 4, 28),
    "tp4-shard-of-mistral7b": (8, 2, 32),
    "tp4-shard-of-qwen25-7b": (7, 1, 28),
    # 64-wide heads cached two to a row (`models/conv_moe.py`): read through
    # `ops.attention.paged_decode_attention`, which widens the queries, at the
    # cell's lanes and context, with the XLA form's time beside the kernel's
    "lfm2-8b-a1b-l16": (32, 8, 4),
}
PAIRED = {"lfm2-8b-a1b-l16": (64, 2, [(64, 1100), (45, 1100), (8, 1100)])}
# prompt lengths of the single-prompt prefill program read at the paired
# shapes: a chunk (`prefill@512`), and the mix's median-plus and longest prompts
PAIRED_PREFILL = (512, 1024, 4096)
B, D, MAX_BLOCKS = 64, 128, 256


def _inputs(hq, hkv, *, live, ctx, idle_ctx, quantized, seed, D=D, pack=1):
    """`live` lanes of `ctx` tokens scattered among `B - live` idle ones
    (context `idle_ctx`, table of zeros), pages drawn without replacement.
    `pack` > 1: heads of `D` cached `pack` to a row."""
    rng = np.random.default_rng(seed)
    bs = 32 if quantized else 16
    nb = 1 + B * (-(-ctx // bs))
    lens = np.full(B, idle_ctx, np.int32)
    tables = np.zeros((B, MAX_BLOCKS * 16 // bs), np.int32)
    pages = rng.permutation(np.arange(1, nb))
    per = -(-ctx // bs)
    for n, lane in enumerate(sorted(rng.permutation(B)[:live])):
        lens[lane] = ctx
        tables[lane, :per] = pages[n * per:(n + 1) * per]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, hq, D), jnp.bfloat16)
    shape = (hkv // pack, nb, bs, D * pack)
    if quantized:
        cache = [
            {
                "q": jax.random.randint(k, shape, -127, 128, jnp.int8),
                "s": jax.random.uniform(k, (hkv, nb), jnp.float32, 0.01, 0.03),
            }
            for k in keys[1:]
        ]
    else:
        cache = [jax.random.normal(k, shape, jnp.bfloat16) for k in keys[1:]]
    return q, cache[0], cache[1], jnp.asarray(tables), jnp.asarray(lens)


def _call(kernel, **kw):
    """The kernel on this script's inputs (a cache is a pair of arrays, or of
    `{"q", "s"}` when int8-resident)."""
    def call(q, k, v, tables, lens):
        if isinstance(k, dict):
            return kernel(q, k["q"], v["q"], tables, lens,
                          k_scales=k["s"], v_scales=v["s"], **kw)
        return kernel(q, k, v, tables, lens, **kw)

    return call


def _chain(call, calls):
    def run(q, *rest):
        def body(_, q):
            return (q + call(q, *rest) * 0.125).astype(q.dtype)

        return jax.lax.fori_loop(0, calls, body, q)

    return jax.jit(run)


def _time_ms(fn, args, calls, reps):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / calls * 1e3


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel-file", default=None)
    ap.add_argument("--calls", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES))
    ap.add_argument("--pages-per-chunk", type=int, nargs="*", default=[None])
    ap.add_argument("--idle-context", type=int, nargs="*", default=[0])
    ap.add_argument(
        "--rehearse", action="store_true",
        help="interpret mode, short contexts, one repeat: the script's own "
        "control flow on a CPU; its times mean nothing and say so",
    )
    args = ap.parse_args()
    if args.rehearse:
        args.calls = 2
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit("a bench-top timing needs the chip; found " + dev.platform)

    from dynamo_tpu.ops import pallas_attention as pa

    if args.kernel_file is not None:
        spec = importlib.util.spec_from_file_location("other_pa", args.kernel_file)
        pa = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pa)
    kernel = pa.paged_decode_attention_pallas

    # (live lanes, their context, quantized): a full batch, the two dense
    # cells' traced lanes (PERF.md section 5), an int8-resident reading
    mixes = [(64, 456, False), (12, 456, False), (18, 548, False),
             (12, 456, True)]
    fixed = {"interpret": True} if args.rehearse else {}
    if args.rehearse:
        mixes = [(B, 40, False), (3, 70, False), (3, 70, True)]
    for name in args.shapes:
        hq, hkv, layers = SHAPES[name]
        if name in PAIRED:
            _paired(name, args, dev)
            continue
        for live, ctx, quantized in mixes:
            for idle_ctx in (args.idle_context if live < B else [0]):
                inp = _inputs(hq, hkv, live=live, ctx=ctx, idle_ctx=idle_ctx,
                              quantized=quantized, seed=args.seed)
                ref = A.paged_decode_attention(*inp, impl="xla")
                lens = np.asarray(inp[4])
                for W in args.pages_per_chunk:
                    kw = dict(fixed)
                    if W is not None:
                        kw["pages_per_chunk"] = W
                    call = _call(kernel, **kw)
                    out = np.asarray(jax.jit(call)(*inp), np.float32)
                    err = np.abs(out - np.asarray(ref, np.float32))[lens > 1].max()
                    ms = _time_ms(_chain(call, args.calls), inp, args.calls,
                                  1 if args.rehearse else 10)
                    print(json.dumps({
                        "shape": name, "kernel": args.kernel_file or "this tree",
                        "live": live, "ctx": ctx, "idle_ctx": idle_ctx,
                        "int8_resident": quantized,
                        "pages_per_chunk": W or "default",
                        "call_ms": round(ms, 4),
                        "step_ms": round(ms * layers, 3),
                        "max_abs_err_vs_xla_live": float(err),
                        "idle_rows_zero": bool((out[lens == 0] == 0).all()),
                        "device": dev.device_kind,
                    }), flush=True)


def _paired(name, args, dev) -> None:
    """Narrow heads cached in pairs: the public call on the stored rows, the
    Pallas form and the XLA form timed alike, one line a mix."""
    hq, hkv, layers = SHAPES[name]
    width, pack, mixes = PAIRED[name]
    kernel_impl = "pallas_interpret" if args.rehearse else "pallas"
    for live, ctx in ([(3, 70)] if args.rehearse else mixes):
        inp = _inputs(hq, hkv, live=live, ctx=ctx, idle_ctx=0, quantized=False,
                      seed=args.seed, D=width, pack=pack)
        lens = np.asarray(inp[4])
        forms = {
            impl: lambda q, k, v, t, n, impl=impl: A.paged_decode_attention(
                q, k, v, t, n, impl=impl)
            for impl in (kernel_impl, "xla")
        }
        out = {k: np.asarray(jax.jit(f)(*inp), np.float32) for k, f in forms.items()}
        ms = {
            k: _time_ms(_chain(f, args.calls), inp, args.calls, 1 if args.rehearse else 10)
            for k, f in forms.items()
        }
        print(json.dumps({
            "shape": name, "head_width": width, "heads_a_row": pack,
            "live": live, "ctx": ctx, "idle_ctx": 0,
            "call_ms": round(ms[kernel_impl], 4),
            "step_ms": round(ms[kernel_impl] * layers, 3),
            "xla_call_ms": round(ms["xla"], 4),
            "xla_step_ms": round(ms["xla"] * layers, 3),
            "max_abs_err_vs_xla_live": float(
                np.abs(out[kernel_impl] - out["xla"])[lens > 1].max()),
            "idle_rows_zero": bool((out[kernel_impl][lens == 0] == 0).all()),
            "device": dev.device_kind,
        }), flush=True)
    # the flash prefill kernel over the same stored rows (one prompt of P
    # tokens, the last eighth padding): no cell's traffic dispatches the
    # single-prompt program, so this is its only reading on the chip
    for P in ([128] if args.rehearse else PAIRED_PREFILL):
        keys = jax.random.split(jax.random.PRNGKey(args.seed + P), 3)
        q = jax.random.normal(keys[0], (P, hq, width), jnp.bfloat16)
        k, v = (jax.random.normal(x, (P, hkv // pack, width * pack), jnp.bfloat16)
                for x in keys[1:])
        valid = jnp.int32(P - P // 8)
        forms = {
            impl: lambda q, k, v, n, impl=impl: A.causal_prefill_attention(
                q, k, v, n, impl=impl)
            for impl in (kernel_impl, "xla")
        }
        inp = (q, k, v, valid)
        out = {n: np.asarray(jax.jit(f)(*inp), np.float32) for n, f in forms.items()}
        ms = {
            n: _time_ms(_chain(f, args.calls), inp, args.calls, 1 if args.rehearse else 10)
            for n, f in forms.items()
        }
        print(json.dumps({
            "shape": name, "prefill_tokens": P, "valid": int(valid),
            "head_width": width, "heads_a_row": pack,
            "call_ms": round(ms[kernel_impl], 4),
            "xla_call_ms": round(ms["xla"], 4),
            "max_abs_err_vs_xla_valid": float(
                np.abs(out[kernel_impl] - out["xla"])[: int(valid)].max()),
            "device": dev.device_kind,
        }), flush=True)


if __name__ == "__main__":
    main()

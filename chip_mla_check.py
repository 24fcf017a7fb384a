#!/usr/bin/env python3
"""chip_mla_check.py — the latent decode kernel against the XLA form and the
plain reference, on the chip, at the published widths.

    chiprun -- python chip_mla_check.py      # one TPU chip
    python chip_mla_check.py --cpu-rehearsal # toy widths, interpret kernel

`tests/test_tpu_compile.py` compiles `ops/pallas_mla.py` for a v5e and the
CPU tests run it interpreted at a toy size; neither runs what Mosaic
compiled. The benchmark's `correct` cannot stand in: its number is set by
expert flips, and attention over random weights is near uniform, so a
fault in the kernel would hardly move it. Two phases, one process:

1. *the kernel alone*: 64 lanes, 32 heads, rows of 576 stored 640 wide,
   values the first 512, blocks of 16, contexts from 0 (idle) over page
   edges to 8192; queries scaled so that scores spread over about 3.5 (a
   peaked softmax: a wrong row, mask or page would show). Pallas and
   `ops/mla.decode_attention(impl="xla")` on the same bfloat16 operands,
   each against a float32 softmax over the gathered rows at `highest`
   precision: the RMS difference as a share of the output's RMS (a wrong
   row, mask or page reads 0.1 to 1), the largest difference beside it.
2. *the path around it*: JoyAI-LLM-Flash's widths with two dense layers (no
   experts: a routing flip between two attention forms would drown what is
   compared), packed prefill of four prompts (per-head form, rows written to
   the plane), then four greedy `decode` steps (rope, absorbed query, the
   kernel, absorbed output) with the kernel and with the XLA form; every
   step's logits against `cellbench/reference/mla_moe.forward` (per-head,
   no cache, float32, its own draw of the same weights) by the benchmark's
   number over the whole vocabulary.

Every line is one JSON object; the last is `{"ok": true, "device": ...}`.
Any limit passed exits 1 and prints no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys

import numpy as np

KERNEL_LIMIT = 0.01  # RMS; bfloat16 probabilities and output round at 2**-9 a value
PATH_LIMIT = 0.03  # the dense configurations' limit for bfloat16 against float32;
# the two forms round apart from each other, so they differ by as much again


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def kernel_alone(rehearsal: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import mla

    B, Hq, width, stored, value, bs = 64, 32, 576, 640, 512, 16
    max_ctx, pool = (8192, 4096) if not rehearsal else (256, 64)
    if rehearsal:
        B, Hq = 8, 4
    rng = np.random.default_rng(7)
    edges = [0, 1, bs - 1, bs, bs + 1, 16 * bs - 1, 16 * bs, 16 * bs + 1, max_ctx]
    ctx = np.array(
        [c for c in edges if c <= max_ctx]
        + list(rng.integers(1, max_ctx, B))[: B - len([c for c in edges if c <= max_ctx])],
        np.int32,
    )[:B]
    tables = rng.integers(1, pool, (B, max_ctx // bs)).astype(np.int32)
    plane = np.zeros((1, pool, bs, stored), np.float32)
    plane[..., :width] = rng.standard_normal((1, pool, bs, width))
    q = np.zeros((B, Hq, stored), np.float32)
    q[..., :width] = 2.0 * rng.standard_normal((B, Hq, width))
    scale = 1.0 / np.sqrt(192.0)
    qb, pb = jnp.asarray(q, jnp.bfloat16), jnp.asarray(plane, jnp.bfloat16)
    tb, cb = jnp.asarray(tables), jnp.asarray(ctx)
    run = lambda impl: np.asarray(jax.jit(functools.partial(
        mla.decode_attention, value_width=value, scale=scale, impl=impl,
    ))(qb, pb, tb, cb).astype(jnp.float32))
    got = {
        "pallas": run("pallas_interpret" if rehearsal else "pallas"),
        "xla": run("xla"),
    }

    @jax.jit
    def exact(qb, pb, tb, cb):
        rows = pb[0, tb].reshape(B, -1, stored).astype(jnp.float32)
        s = jnp.einsum("bhw,bsw->bhs", qb.astype(jnp.float32), rows,
                       precision="highest") * scale
        mask = (jnp.arange(rows.shape[1])[None] < cb[:, None])[:, None]
        p = jnp.where(mask, jax.nn.softmax(jnp.where(mask, s, -1e30), -1), 0.0)
        return jnp.einsum("bhs,bsv->bhv", p, rows[..., :value], precision="highest")

    want = np.asarray(exact(qb, pb, tb, cb))
    rms = float(np.sqrt(np.mean(want[ctx > 0] ** 2)))
    out = {
        "lanes": B, "contexts": sorted(set(int(c) for c in ctx))[:12],
        "score_spread": float(np.std(
            np.einsum("hw,sw->hs", q[-1], plane[0, tables[-1, 0]]) * scale)),
    }
    for name, o in got.items():
        out[f"{name}_rms_rel"] = float(np.sqrt(np.mean((o - want)[ctx > 0] ** 2)) / rms)
        out[f"{name}_max_over_rms"] = float(np.max(np.abs(o - want)) / rms)
        out[f"{name}_idle_lane_max"] = float(np.max(np.abs(o[ctx == 0])))
    out["pallas_against_xla_rms_rel"] = float(
        np.sqrt(np.mean((got["pallas"] - got["xla"])[ctx > 0] ** 2)) / rms)
    return out


def path_around_it(rehearsal: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from cellbench import manifest
    from cellbench.compare import logit_error
    from cellbench.reference import mla_moe as ref
    from dynamo_tpu.models import mla_moe

    hf = {k: v for k, v in manifest.load_json(
        "cellbench", "configs", "joyai-flash-bf16-l5.json").items() if k != "bench"}
    hf.update(num_hidden_layers=2, first_k_dense_replace=2)
    lengths, bs, steps = [60, 200, 333, 576], 16, 4
    if rehearsal:
        hf.update(
            hidden_size=64, intermediate_size=160, num_attention_heads=4,
            q_lora_rank=48, kv_lora_rank=128, qk_nope_head_dim=16,
            qk_rope_head_dim=64, v_head_dim=16, vocab_size=300,
        )
        lengths = [11, 20, 33, 40]
    cfg = mla_moe.MlaMoeConfig.from_hf_dict(hf)
    params = mla_moe.init_params(cfg, jax.random.PRNGKey(0))
    d = ref.dims(hf)
    *layers, top = list(ref.seeded_layers(d, 0))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(3, hf["vocab_size"], n).tolist() for n in lengths]
    # a table as wide as whole chunks of 16 pages, as the server's are
    B, max_blocks = 8, -(-((max(lengths) + steps) // bs + 1) // 16) * 16
    tables = np.zeros((B, max_blocks), np.int32)
    for i in range(len(prompts)):
        tables[i] = 1 + i * max_blocks + np.arange(max_blocks)
    P = -(-sum(lengths) // 128) * 128
    tokens, positions = np.zeros(P, np.int32), np.zeros(P, np.int32)
    segments, slots = np.full(P, -1, np.int32), np.zeros(P, np.int32)
    last, at = [], 0
    for i, p in enumerate(prompts):
        pos = np.arange(len(p))
        tokens[at:at + len(p)], positions[at:at + len(p)] = p, pos
        segments[at:at + len(p)] = i
        slots[at:at + len(p)] = tables[i, pos // bs] * bs + pos % bs
        at += len(p)
        last.append(at - 1)
    stored = cfg.cache_kind().stored_width
    planes = tuple(
        jnp.zeros((1, 1 + B * max_blocks, bs, stored), jnp.bfloat16)
        for _ in range(cfg.num_layers)
    )
    logits, planes, _ = jax.jit(functools.partial(mla_moe.prefill_packed, params, cfg))(
        jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(segments),
        jnp.asarray(slots), planes, (), jnp.asarray(last, jnp.int32),
    )
    first = np.asarray(jnp.argmax(logits, -1), np.int32)
    kernel = "pallas_interpret" if rehearsal else "pallas"
    steppers = {
        impl: jax.jit(functools.partial(
            mla_moe.decode, params, dataclasses.replace(cfg, attn_impl=impl)))
        for impl in (kernel, "xla")
    }
    n = len(prompts)
    state = {impl: planes for impl in steppers}
    seen = {impl: [] for impl in steppers}
    tok = np.zeros(B, np.int32)
    tok[:n] = first
    sequences = [list(p) + [int(first[i])] for i, p in enumerate(prompts)]
    for h in range(steps):
        pos = np.zeros(B, np.int32)
        pos[:n] = [len(p) + h for p in prompts]
        slot = np.zeros(B, np.int32)  # the null block: an idle lane
        slot[:n] = [tables[i, pos[i] // bs] * bs + pos[i] % bs for i in range(n)]
        for impl, step in steppers.items():
            out, state[impl], _ = step(
                jnp.asarray(tok), jnp.asarray(pos), state[impl], (),
                jnp.asarray(tables), jnp.asarray(slot),
            )
            seen[impl].append(np.asarray(out[:n], np.float32))
        # both forms are fed the kernel's tokens: the same positions compared
        tok[:n] = np.argmax(seen[kernel][-1], -1)
        for i in range(n):
            sequences[i].append(int(tok[i]))
    want = [
        np.asarray(ref.forward(
            layers, top, d, [seq[:-1]],
            list(range(len(prompts[i]), len(prompts[i]) + steps)),
        ))[0]
        for i, seq in enumerate(sequences)
    ]  # [n][steps, vocab]
    flat = lambda per_step: [
        [float(x) for x in per_step[h][i]] for i in range(n) for h in range(steps)
    ]
    reference = [[float(x) for x in want[i][h]] for i in range(n) for h in range(steps)]
    stds = [float(np.std(want[i][h])) for i in range(n) for h in range(steps)]
    return {
        "prompts": lengths, "steps": steps, "positions": n * steps,
        "kernel_against_reference": logit_error(flat(seen[kernel]), reference, stds)["rms_rel"],
        "xla_against_reference": logit_error(flat(seen["xla"]), reference, stds)["rms_rel"],
        "kernel_against_xla": logit_error(flat(seen[kernel]), flat(seen["xla"]), stds)["rms_rel"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    if not args.cpu_rehearsal and dev.platform != "tpu":
        emit(ok=False, why="no TPU: use --cpu-rehearsal", device=device)
        return 1
    alone = kernel_alone(args.cpu_rehearsal)
    emit(phase="kernel", limit=KERNEL_LIMIT, **alone)
    path = path_around_it(args.cpu_rehearsal)
    emit(phase="path", limit=PATH_LIMIT, **path)
    faults = [
        name for name, ok in (
            ("pallas_rms_rel", alone["pallas_rms_rel"] <= KERNEL_LIMIT),
            ("xla_rms_rel", alone["xla_rms_rel"] <= KERNEL_LIMIT),
            ("pallas_idle_lane_max", alone["pallas_idle_lane_max"] == 0.0),
            ("kernel_against_reference", path["kernel_against_reference"] <= PATH_LIMIT),
            ("xla_against_reference", path["xla_against_reference"] <= PATH_LIMIT),
            ("kernel_against_xla", path["kernel_against_xla"] <= PATH_LIMIT),
        ) if not ok
    ]
    if faults:
        emit(ok=False, over_their_limit=faults, device=device)
        return 1
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())

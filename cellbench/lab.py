#!/usr/bin/env python3
"""The builder's bench-top: one server start, several readings. Not run by
the driver; it is how the numbers in `cellbench/sweeps/` and the limits in
the configurations' files were read on the chip.

    python cellbench/lab.py --workload <cell> [--check-seeds 1,2,3 --control-seeds 1,2,3]
                            [--sweep 1.2,1.5,1.8 --step-s 60]

* check: for each seed, the check's probes are sent to the served path and
  compared with the float32 reference (the number `run.py` prints in every
  run); for each control seed the reference is also computed in the next
  lower precisions and compared with itself at float32, as if it stood in
  the program's place. Both at the cell's own widths.
* sweep: the cell's mix offered at each rate in turn for `--step-s` seconds,
  without a pause between steps; every 5 s the streams in decode (live
  lanes), the requests sent and still waiting for a first token (backlog) and
  the tokens delivered are noted.

Results go to `chiprun_out/lab/<cell>.json` and, abridged, to stdout.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from cellbench import client, manifest  # noqa: E402
from cellbench.run import Reference, probe_question, probe_requests, warm_up  # noqa: E402
from cellbench.server import BenchFailure, Server  # noqa: E402

LOWER = ["int4_weights", "int8_activations"]


async def check_seed(server, cell, seed: int, reference, control: bool) -> dict:
    config = cell.config
    groups = range(len(config["bench"]["check"]["probes"]))
    probes = [p for g in groups for p in probe_requests(config, seed, g)]
    recs = await client.offer(
        server.port, server.model, probes, time.monotonic(), None, 0.0,
        top_logprobs=config["bench"]["check"]["top_logprobs"],
    )
    reference.ask(dict(probe_question(probes, recs), reset=True))
    out = {"seed": seed, "served": (await asyncio.to_thread(reference.verdict))}
    if control:
        # the controls on the short sequences only: three passes a seed
        short = min(len(p["token_ids"]) for p in probes)
        keep = [i for i, p in enumerate(probes) if len(p["token_ids"]) == short]
        reference.ask(dict(probe_question(
            [probes[i] for i in keep], [recs[i] for i in keep], LOWER
        ), reset=True))
        out["control"] = await asyncio.to_thread(reference.verdict)
    return out


async def sweep(server, cell, rates: list[float], step_s: float, seed: int) -> list[dict]:
    gen = cell.generator()
    requests, edges, t = [], [], 0.0
    for i, rate in enumerate(rates):
        mix = dict(cell.mix, rate_rps=rate, ramp_s=0)
        part = gen.generate(mix, seed + i, step_s, cell.config["vocab_size"])
        for r in part:
            r["due_s"] += t
            r["index"] = len(requests)
            requests.append(r)
        edges.append((rate, t, t + step_s))
        t += step_s
    t0 = time.monotonic() + 0.5
    slices: list[dict] = []

    async def tick(now: float, recs: list[dict]) -> None:
        if now - t0 < 5.0 * (len(slices) + 1):
            return
        live = sum(1 for r in recs if r["tokens"] and not r["done"] and not r["error"])
        waiting = sum(
            1 for r in recs
            if r["sent"] is not None and not r["tokens"] and not r["error"]
        )
        delivered = sum(len(r["tokens"]) for r in recs)
        slices.append({"t": now - t0, "live": live, "waiting": waiting,
                       "delivered": delivered})

    recs = await client.offer(
        server.port, server.model, requests, t0, t0 + t, cell.mix["temperature"],
        on_tick=tick,
    )
    out = []
    for rate, a, b in edges:
        mine = [s for s in slices if a < s["t"] <= b]
        half = [s for s in mine if s["t"] > (a + b) / 2]
        due = [r for r, q in zip(recs, requests) if a <= q["due_s"] < b]
        offered = sum(r["output_tokens"] for r in due)
        tok = sum(
            1 for r in recs for x in r["tokens"] if t0 + (a + b) / 2 <= x < t0 + b
        )
        out.append({
            "rate_rps": rate, "requests_due": len(due),
            "offered_tok_s": offered / (b - a),
            "delivered_tok_s_second_half": tok / ((b - a) / 2),
            "completed_share": sum(1 for r in due if r["done"]) / max(1, len(due)),
            "failed": sum(1 for r in due if r["error"]),
            "backlog_at_end": mine[-1]["waiting"] if mine else None,
            "backlog_max_second_half": max((s["waiting"] for s in half), default=None),
            "live_lanes_by_slice": [s["live"] for s in mine],
            "waiting_by_slice": [s["waiting"] for s in mine],
        })
    return out


async def main_async(args) -> dict:
    cell = manifest.Cell(args.workload)
    out_dir = os.path.join(ROOT, "cellbench_out", "lab-" + cell.name)
    os.makedirs(out_dir, exist_ok=True)
    if args.cpu_rehearsal:
        cell.rehearse()
    server = Server(cell.config, out_dir, args.cpu_rehearsal)
    if not args.cpu_rehearsal:
        server.env["JAX_PLATFORMS"] = "tpu"
    reference = Reference(cell.config_file, out_dir)
    result: dict = {"workload": cell.name}
    try:
        server.start()
        await asyncio.to_thread(server.wait_ready)
        result["engine"] = server.facts
        t = time.monotonic()
        warm = await warm_up(server, cell, 1, reference)
        for task in warm["lingering"]:
            await task
        result["warm_s"] = time.monotonic() - t
        result["first_dispatch_seconds"] = server.goodput()["compile_s_by_label"]
        print(json.dumps({"phase": "warm", **{k: result[k] for k in ("warm_s", "first_dispatch_seconds")}}), flush=True)
        result["check"] = []
        for seed in args.check_seeds:
            r = await check_seed(server, cell, seed, reference, seed in args.control_seeds)
            result["check"].append(r)
            print(json.dumps({"phase": "check", **r}), flush=True)
        if args.sweep:
            result["sweep"] = await sweep(server, cell, args.sweep, args.step_s, 77)
            for row in result["sweep"]:
                print(json.dumps({"phase": "sweep", **row}), flush=True)
            result["ledger_after_sweep"] = server.goodput()
    finally:
        await asyncio.to_thread(server.stop)
        await asyncio.to_thread(reference.stop)
    result["device"] = {
        "platform": server.facts.get("platform"), "kind": server.facts.get("device_kind"),
        "count": server.facts.get("device_count"),
    }
    return result


def floats(text: str) -> list[float]:
    return [float(x) for x in text.split(",") if x]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--check-seeds", type=lambda s: [int(x) for x in s.split(",") if x], default=[])
    ap.add_argument("--control-seeds", type=lambda s: [int(x) for x in s.split(",") if x], default=[])
    ap.add_argument("--sweep", type=floats, default=[])
    ap.add_argument("--step-s", type=float, default=60.0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    try:
        result = asyncio.run(main_async(args))
    except BenchFailure as e:
        print(f"lab FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    out = os.path.join(ROOT, "chiprun_out", "lab")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, args.workload + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"phase": "done", "device": result["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""`cellbench/lab.py` for a cell whose batch is resident: the long sessions
that the mix starts in warm-up (lingering `requests` phases) stay on the
server while the rates are swept, where `lab.py` waits for every lingering
stream to end before it sweeps; and the controls are read over EVERY position
of the check, the sequences that cross the window included, after the sweep.

    chiprun -- python benchmarks/long_short_lab.py --workload <cell> \
        --sweep 0.88,1.18,1.47 --step-s 45 --check-seeds 31,32 --control-seeds 31,32

One server start: warm-up (the sessions' prefill is waited for: the ledger's
prompt tokens stand still), the sweep with the sessions decoding beside it
(`lab.sweep`: the mix's short requests at each rate in turn, no pause), the
ledger after it (preemptions, the pools), then for each check seed the
probes again and the reference's verdict, with the controls the
configuration's file names where the seed is a control seed. It stands here
and not as an option of `lab.py` because that file is the accepted
benchmark's, which the PR that adds a cell may not edit.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.conv_moe_check_lab import check_seed  # noqa: E402
from cellbench import lab, manifest  # noqa: E402
from cellbench.run import Reference, warm_up  # noqa: E402
from cellbench.server import BenchFailure, Server  # noqa: E402


async def prefill_done(server, quiet_s: float = 3.0, budget_s: float = 240.0) -> float:
    """Wait until the ledger's prompt tokens stand still for `quiet_s`."""
    t0, last, since = time.monotonic(), None, time.monotonic()
    while time.monotonic() - t0 < budget_s:
        now = (await asyncio.to_thread(server.goodput))["prefill_tokens"]
        if now != last:
            last, since = now, time.monotonic()
        elif time.monotonic() - since >= quiet_s:
            break
        await asyncio.sleep(1.0)
    return time.monotonic() - t0


async def main_async(args) -> dict:
    cell = manifest.Cell(args.workload)
    out_dir = os.path.join(ROOT, "cellbench_out", "lab-" + cell.name)
    os.makedirs(out_dir, exist_ok=True)
    server = Server(cell.config, out_dir, False)
    server.env["JAX_PLATFORMS"] = "tpu"
    reference = Reference(cell.config_file, out_dir)
    result: dict = {"workload": cell.name}
    lingering = []
    try:
        server.start()
        await asyncio.to_thread(server.wait_ready)
        result["engine"] = server.facts
        t = time.monotonic()
        warm = await warm_up(server, cell, 1, reference)
        lingering = warm["lingering"]
        result["warm_s"] = time.monotonic() - t
        result["warm_verdict"] = warm["verdict"]
        result["prefill_wait_s"] = await prefill_done(server)
        result["ledger_before_sweep"] = server.goodput()
        print(json.dumps({"phase": "warm", "warm_s": result["warm_s"],
                          "prefill_wait_s": result["prefill_wait_s"],
                          "served": warm["verdict"]["served"]}), flush=True)
        if args.sweep:
            result["sweep"] = await lab.sweep(server, cell, args.sweep, args.step_s, 77)
            for row in result["sweep"]:
                print(json.dumps({"phase": "sweep", **row}), flush=True)
            result["ledger_after_sweep"] = server.goodput()
            result["sessions_still_streaming"] = sum(1 for task in lingering if not task.done())
        result["check"] = []
        for seed in args.check_seeds:
            r = await check_seed(server, cell, seed, reference, seed in args.control_seeds)
            result["check"].append(r)
            print(json.dumps({"phase": "check", **r}), flush=True)
    finally:
        for task in lingering:
            task.cancel()
        await asyncio.gather(*lingering, return_exceptions=True)
        await asyncio.to_thread(server.stop)
        await asyncio.to_thread(reference.stop)
    result["device"] = {
        "platform": server.facts.get("platform"), "kind": server.facts.get("device_kind"),
        "count": server.facts.get("device_count"),
    }
    return result


def main() -> int:
    import argparse

    ints = lambda s: [int(x) for x in s.split(",") if x]
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--check-seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--sweep", type=lab.floats, default=[])
    ap.add_argument("--step-s", type=float, default=45.0)
    args = ap.parse_args()
    check = manifest.Cell(args.workload).config["bench"]["check"]
    lab.LOWER = list(check.get("controls", lab.LOWER))
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

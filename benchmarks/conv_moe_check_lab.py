#!/usr/bin/env python3
"""`cellbench/lab_controls.py` for the readings that set a limit on `correct`
in a sparse-expert cell (`bench.check.readings` of the configuration's file):

    chiprun -- python benchmarks/conv_moe_check_lab.py --workload <cell> \
        --check-seeds 31,32,33 --control-seeds 31,32,33 [--fixed-routing 100]

* the controls are read over EVERY position of the check, the long sequences
  included (`lab.py` keeps the shortest sequences only, and a control that
  lowers what a lane's tail keeps shows where the tail crosses chunk
  boundaries in its slot);
* `--fixed-routing <scale>`: the same readings with the choice of experts
  taken out of the comparison. Both the server and the reference draw
  `expert_bias` that many times larger (`fixed_routing/sitecustomize.py`), so
  a layer's four experts are the same for every token on both sides and the
  served path's number is what bfloat16 arithmetic, the tail, the cache and
  the kernels leave, with no flipped choice in it. The scale must put every
  gap between neighbouring biases at the cut over 1 (100 does, for the 14
  expert layers of lfm2-8b-a1b-bf16-l16 from `weights_seed` 0: the smallest
  gap of the unit draw is 0.036).

Same options and output as `lab.py` otherwise. It stands here and not as an
option of `lab.py` because that file is the accepted benchmark's, which the
PR that adds a cell may not edit; a `benchmark` PR can fold it in.
"""

from __future__ import annotations

import asyncio
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from cellbench import client, lab, manifest  # noqa: E402
from cellbench.run import probe_question, probe_requests  # noqa: E402


async def check_seed(server, cell, seed: int, reference, control: bool) -> dict:
    """`lab.check_seed`, the controls over all the probes."""
    config = cell.config
    groups = range(len(config["bench"]["check"]["probes"]))
    probes = [p for g in groups for p in probe_requests(config, seed, g)]
    recs = await client.offer(
        server.port, server.model, probes, time.monotonic(), None, 0.0,
        top_logprobs=config["bench"]["check"]["top_logprobs"],
    )
    lower = lab.LOWER if control else []
    reference.ask(dict(probe_question(probes, recs, lower), reset=True))
    return {"seed": seed, "served": await asyncio.to_thread(reference.verdict)}


def main() -> int:
    argv = sys.argv
    if "--fixed-routing" in argv:
        i = argv.index("--fixed-routing")
        os.environ["CONV_MOE_EXPERT_BIAS_SCALE"] = argv[i + 1]
        del argv[i: i + 2]
        # both children (the server, the reference) copy this environment
        os.environ["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(ROOT, "benchmarks", "fixed_routing"), ROOT,
             os.environ.get("PYTHONPATH", "")]
        )
    if "--workload" in argv:
        check = manifest.Cell(argv[argv.index("--workload") + 1]).config["bench"]["check"]
        lab.LOWER = list(check.get("controls", lab.LOWER))
    lab.check_seed = check_seed
    return lab.main()


if __name__ == "__main__":
    sys.exit(main())

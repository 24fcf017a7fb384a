"""The plain reference as a process of its own, on the host's CPU.

    python cellbench/refcheck.py <configuration file>

It makes the configuration's seeded weights (its own draw: see the
reference's module), prints `{"ready": ...}`, then answers one JSON line on
stdin with one on stdout until stdin closes. A question holds `probes`, each
with the `tokens` of a whole sequence (prompt then generated), the `rows`
whose next-token logits the server reported, and the server's `top_ids` and
`top_lps` there; and `lower`, a list of controls to compute as well. The
answer holds, for the served path and for each control, the compared number
of `cellbench/compare.py` over every probe asked about so far (`"reset":
true` in a question forgets the earlier ones), so a run can ask about its
sequences as they finish and judge them together.

The benchmark's parent starts it beside the server child, so the weights are
drawn while the server warms up; it runs on the CPU whatever the machine
holds (`JAX_PLATFORMS=cpu` is set by the parent), so it never touches a chip.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from cellbench.manifest import hf_config  # noqa: E402


def answer(ref, layers, top, d, question: dict, seen: dict) -> dict:
    import numpy as np

    from cellbench.compare import logit_error

    lowers = question.get("lower") or []
    if question.get("reset"):
        seen.clear()
    # sequences of one length share a pass
    by_len: dict[int, list[dict]] = {}
    for p in question["probes"]:
        by_len.setdefault(len(p["tokens"]), []).append(p)
    served = seen.setdefault("served", [])
    reference = seen.setdefault("reference", [])
    stds = seen.setdefault("stds", [])
    control = seen.setdefault("control", {})
    for name in lowers:
        control.setdefault(name, [])
    for _, group in sorted(by_len.items()):
        rows = group[0]["rows"]
        if any(p["rows"] != rows for p in group):
            raise ValueError("probes of one length must report the same rows")
        tokens = [p["tokens"] for p in group]
        logits = np.asarray(ref.forward(layers, top, d, tokens, rows))
        lower_logits = {
            name: np.asarray(ref.forward(layers, top, d, tokens, rows, lower=name))
            for name in lowers
        }
        for i, p in enumerate(group):
            for r in range(len(rows)):
                ids = p["top_ids"][r]
                served.append(p["top_lps"][r])
                reference.append([float(x) for x in logits[i, r, ids]])
                stds.append(float(np.std(logits[i, r])))
                for name in lowers:
                    control[name].append(
                        [float(x) for x in lower_logits[name][i, r, ids]]
                    )
    out = {"served": logit_error(served, reference, stds)}
    for name, values in control.items():
        if len(values) == len(reference):
            out[name] = logit_error(values, reference, stds)
    return out


def main() -> int:
    with open(sys.argv[1]) as f:
        config = json.load(f)
    bench = config["bench"]
    ref = importlib.import_module(f"cellbench.reference.{bench['reference']}")
    d = ref.dims(hf_config(config))
    t0 = time.monotonic()
    *layers, top = list(ref.seeded_layers(d, int(bench["weights_seed"])))
    print(json.dumps({"ready": True, "weights_s": time.monotonic() - t0}), flush=True)
    seen: dict = {}
    for line in sys.stdin:
        if not line.strip():
            continue
        t1 = time.monotonic()
        try:
            out = answer(ref, layers, top, d, json.loads(line), seen)
        except Exception as e:  # noqa: BLE001 — the parent reports it and fails
            out = {"error": f"{type(e).__name__}: {e}"}
        out["seconds"] = time.monotonic() - t1
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

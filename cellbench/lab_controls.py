#!/usr/bin/env python3
"""`cellbench/lab.py` with the controls the configuration's file names
(`bench.check.controls`) in place of lab.py's fixed pair: a configuration
states its own precision, so what "one precision lower" is differs (int4
weights under int8 weights; int8 weights under bfloat16 ones). Same options,
same output."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from cellbench import lab, manifest  # noqa: E402


def main() -> int:
    for i, arg in enumerate(sys.argv):
        if arg == "--workload" and i + 1 < len(sys.argv):
            check = manifest.Cell(sys.argv[i + 1]).config["bench"]["check"]
            lab.LOWER = list(check.get("controls", lab.LOWER))
    return lab.main()


if __name__ == "__main__":
    sys.exit(main())

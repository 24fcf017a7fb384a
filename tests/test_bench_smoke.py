"""Smoke tests of the side harnesses under `benchmarks/` (slow tier): each
drives the real server process, so harness rot cannot ship silently."""

import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_frontend_saturation_bench_runs():
    """The SSE saturation harness (benchmarks/bench_frontend.py) must
    drive the real `in=http out=echo_core` process and clear a floor far
    below the recorded ceiling (~7k tok/s in frontend_bench.json) —
    catching harness rot and order-of-magnitude framing regressions."""
    import asyncio

    from benchmarks.bench_frontend import run_bench

    results = asyncio.run(
        run_bench(levels=[1, 4], requests=8, max_tokens=32)
    )
    assert len(results) == 2
    for r in results:
        assert r["tokens"] >= 8 * 32
        assert r["tok_per_s"] > 300, r
        assert r["itl_p99_ms"] < 500, r


@pytest.mark.slow
def test_perf_sweep_harness_runs(tmp_path):
    """The concurrency-sweep harness (benchmarks/perf_sweep.py, the
    reference's perf.sh + plot_pareto.py role) must drive the real
    `in=http out=jax` process, produce monotone-sane stats, and plot."""
    import asyncio
    import json as _json

    from benchmarks.perf_sweep import pareto_frontier, run_sweep

    results = asyncio.run(
        run_sweep(
            model_path=None, levels=[1, 4], requests_per_level=4,
            prompt_tokens=32, max_tokens=8,
        )
    )
    assert len(results) == 2
    for r in results:
        assert r["output_tokens"] == r["requests"] * 8  # ignore_eos held
        assert r["output_tok_per_s"] > 0
    assert pareto_frontier(results)  # never empty
    # plot path (matplotlib Agg)
    sweep = tmp_path / "sweep.json"
    sweep.write_text(_json.dumps({"results": results, "pareto": results}))
    out = tmp_path / "pareto.png"
    import subprocess as sp
    import sys as _sys

    sp.run(
        [_sys.executable, "-m", "benchmarks.plot_pareto", str(sweep),
         "--out", str(out)],
        check=True, cwd=REPO,
    )
    assert out.stat().st_size > 1000

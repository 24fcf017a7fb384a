"""Put on a child's PYTHONPATH by `benchmarks/conv_moe_check_lab.py
--fixed-routing`, and by nothing else: Python imports a `sitecustomize` it
finds there before the child's own program starts.

It multiplies the draw of `expert_bias` by `CONV_MOE_EXPERT_BIAS_SCALE` in
both makers of the weights, the program's (`models/conv_moe.py`) and the
plain reference's (`cellbench/reference/conv_moe.py`). The bias takes part in
the choice of experts only, never in their weights, and a sigmoid score lies
in (0, 1): once every gap between neighbouring biases is over 1, the four
experts with the largest bias are chosen for every token of a layer whatever
the scores are, on both sides alike, so no rounding of a hidden state can
move a choice. What the two sides then differ by is arithmetic alone.
"""

import os

_scale = os.environ.get("CONV_MOE_EXPERT_BIAS_SCALE")
if _scale:
    import cellbench.reference.conv_moe as _reference
    import dynamo_tpu.models.conv_moe as _program

    _reference.EXPERT_BIAS_SCALE = _program.EXPERT_BIAS_SCALE = float(_scale)

"""The comparison that decides `correct`: served top log-probs against the
plain reference's logits at the same token ids.

The server reports, for each generated position, its top-k token ids and
their log-probs. A log-softmax is the logits shifted by one constant per
position (and the server masks the end-of-sequence id while `ignore_eos`
holds), so both sides are centred over the k reported ids before they are
compared: what is left is the difference between logits, which is what the
precision of the path moves. The number compared is the root mean square of
those differences over every position and id, as a share of the root mean
square spread of the reference's logits over the whole vocabulary. A mean
over some hundreds of values is steady from seed to seed where the largest
single difference is not; the largest is printed beside it and not judged.
"""

from __future__ import annotations

import math


def centred(values: list[float]) -> list[float]:
    mean = sum(values) / len(values)
    return [v - mean for v in values]


def logit_error(served: list[list[float]], reference: list[list[float]],
                reference_std: list[float]) -> dict:
    """`served[p]` and `reference[p]`: values at the same k ids of position
    p (log-probs or logits: centring makes them comparable).
    `reference_std[p]`: spread of the reference's logits over the vocabulary
    at p. Returns the compared number `rms_rel` and, for the record,
    `max_rel` and the counts."""
    if not served or len(served) != len(reference):
        raise ValueError("nothing to compare, or positions do not match")
    sq, n, worst = 0.0, 0, 0.0
    for a, b in zip(served, reference):
        if len(a) != len(b) or not a:
            raise ValueError("ids do not match at a position")
        for x, y in zip(centred(a), centred(b)):
            if not (math.isfinite(x) and math.isfinite(y)):
                return {"rms_rel": math.inf, "max_rel": math.inf,
                        "positions": len(served), "values": n}
            sq += (x - y) ** 2
            worst = max(worst, abs(x - y))
            n += 1
    scale = math.sqrt(sum(s * s for s in reference_std) / len(reference_std))
    return {
        "rms_rel": math.sqrt(sq / n) / scale,
        "max_rel": worst / scale,
        "positions": len(served),
        "values": n,
    }

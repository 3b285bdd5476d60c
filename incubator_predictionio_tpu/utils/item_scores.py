"""Top-k arrays → response bytes, for the engines' columnar fast path.

One hand-mirrored JSON format for every engine that answers
``{"itemScores": [{"item": ..., "score": ...}, ...]}`` from batched top-k
arrays (core/base.py ``batch_serve_json``): byte for byte what
``json.dumps(to_jsonable(PredictedResult))`` gives on the object path
(pinned by tests/test_prediction_server.py and
tests/test_sequence_block.py).
"""

from __future__ import annotations

import json
import math
from typing import Callable, Optional

#: a masked slot's score (ops/topk.NEG_INF is −3e38): never an answer
_FILLER_BELOW = -1e37


def render_item_scores(top_s, top_i, num: int, item_of: Callable,
                       more: Optional[Callable] = None) -> Optional[bytes]:
    """One answer: the first ``num`` of a row's top-k (``top_s`` scores,
    ``top_i`` indices, numpy) with ``item_of(index)`` the item's id and
    ``more(item id)`` further fields of the entry (``', "key": value'``).
    None when a score is not finite: ``repr(inf)`` is not JSON
    (``json.dumps`` says ``Infinity``), so an overflowed score falls back
    to the object path rather than diverge from it."""
    dumps = json.dumps
    parts = []
    for s, i in zip(top_s[:num].tolist(), top_i[:num].tolist()):
        if s > _FILLER_BELOW:
            if not math.isfinite(s):
                return None
            iid = item_of(i)
            # mirror json.dumps' default formatting exactly (', '/': '
            # separators, float repr)
            parts.append('{"item": %s, "score": %s%s}'
                         % (dumps(iid), repr(s), more(iid) if more else ""))
    return ('{"itemScores": [' + ", ".join(parts) + "]}").encode("utf-8")

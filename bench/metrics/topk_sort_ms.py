"""topk_sort_ms: device milliseconds per step of the sort and top-k
operations, found by HLO op name in the trace. In the training cells only
the gradient compressor sorts (``lax.top_k`` over each leaf), so this is
the compressor's selection time. None where the trace holds no such op."""
import re

NAMES = re.compile(r"^(sort|topk|top-k|top_k|TopK|approx-top-k)([.\-_]|$)",
                   re.IGNORECASE)


def read(r: dict):
    red = r["red"]
    secs = [v for k, v in red["ops"].items() if NAMES.match(k)]
    if not secs or r["steps"] == 0:
        return None
    return 1e3 * sum(secs) / r["steps"]

"""moe_expert_ms: device milliseconds per step of the held experts'
grouped products, found by HLO op name in the trace: the megablox kernel
calls of ``repro.models.moe.grouped_matmul`` carry the names of the jitted
``gmm`` (forward, and the input gradient) and ``tgmm`` (the weight
gradient), e.g. ``jvp_jit_gmm__.4`` and ``transpose_jvp_jit_tgmm___.5``.
None where the trace holds no such op (a program without them)."""
import re

NAMES = re.compile(r"(^|_)t?gmm(_|\.|$)")


def read(r: dict):
    red = r["red"]
    secs = [v for k, v in red["ops"].items() if NAMES.search(k)]
    if not secs or r["steps"] == 0:
        return None
    return 1e3 * sum(secs) / r["steps"]

"""mfu: model FLOPs of the traced steps over the traced window's seconds
times the chips times the bf16 peak (bench/peaks.json), in %.

The FLOPs are bench/flops.py's count of the configuration's forward and
backward products per token, times the tokens of a step, times the steps
inside the window; whatever implements the step, the count is the same.
"""


def read(r: dict):
    red = r["red"]
    if red["window_s"] <= 0 or r["steps"] == 0:
        return None
    flops = r["flops_per_step"] * r["steps"]
    return 100.0 * flops / (red["window_s"] * r["chips"]
                            * r["peak"]["bf16_flops_per_s"])

"""moe_gmm_roofline: the share of their roofline that the held experts'
grouped products reach, in %: the time their FLOPs a step take at the
bf16 peak, or their bytes at the HBM bandwidth if longer
(bench/peaks.json), over ``moe_expert_ms``'s device time of the same
kernels.

FLOPs and bytes are ``bench/flops_moonlight.py``'s count of the products
a step runs (``GMM_PRODUCTS`` a row) at the held experts' expected load.
A reader is given the trace's reading alone, so the cell is the one among
those this metric lists in BENCHMARK.json whose model FLOPs a step are
the reading's. None where no listed cell has them, or where the trace
holds no grouped product.
"""
from pathlib import Path

from bench import flops
from bench import manifest as M

HERE = Path(__file__).resolve()


def _cell(r: dict, root: Path):
    """-> (configuration, tokens a step) of the listed cell whose model
    FLOPs a step are the reading's, or None."""
    man = M.load(root)
    entry = next(m for m in man["per_layer"] if m["name"] == HERE.stem)
    for name in entry.get("workloads", []):
        cell = M.cell(man, name)
        conf = M.config(man, cell["config"], root)
        step = M.read_json(M.traffic_path(cell["traffic"], root))["step"]
        tokens = step["batch"] * step["seq"]
        want = flops.train_flops_per_token(conf, step["seq"]) * tokens
        if abs(want - r["flops_per_step"]) <= 1e-9 * want:
            return conf, tokens
    return None


def read(r: dict):
    root = HERE.parents[2]
    ms = M.load_module(HERE.with_name("moe_expert_ms.py")).read(r)
    found = _cell(r, root)
    if ms is None or found is None:
        return None
    conf, tokens = found
    count = M.load_module(root / "bench" / f"flops_{conf['flops']}.py")
    seconds = max(count.gmm_flops_per_step(conf, tokens)
                  / r["peak"]["bf16_flops_per_s"],
                  count.gmm_bytes_per_step(conf, tokens)
                  / r["peak"]["hbm_bytes_per_s"])
    return 100.0 * seconds / (ms * 1e-3)

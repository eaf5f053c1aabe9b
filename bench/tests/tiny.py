"""A cell cut to a size the CPU runs in seconds, for the tests: the same
files and code as the chip run, with widths, vocabulary, batch and
sequence shrunk. Each reference module names its cut published keys in
``TINY``; a configuration's ``program.tiny_set`` gives the trainer
fields with no published key at that size. A new configuration brings
both in its own files."""
from __future__ import annotations

import copy
import importlib

from bench import manifest as M
from bench.run import Ctx, context


def tiny_ctx(cell: str, seed: int = 3, seconds: float = 2.0,
             batch: int | None = None, seq: int = 32, root=M.ROOT) -> Ctx:
    ctx = context(root, M.load(root), cell, seed, seconds, 0, 1,
                  {"bf16_flops_per_s": 1e12})
    conf = copy.deepcopy(ctx.conf)
    ref = importlib.import_module(f"bench.reference.{conf['reference']}")
    conf.update(ref.TINY)
    prog = conf["program"]
    prog.setdefault("set", {}).update(prog.get("tiny_set", {}))
    traffic = copy.deepcopy(ctx.traffic)
    batch = batch or traffic["step"]["batch"]
    argv = list(traffic["argv"])
    argv[argv.index("--batch") + 1] = str(batch)
    argv[argv.index("--seq") + 1] = str(seq)
    traffic["argv"] = argv
    traffic["step"].update(batch=batch, seq=seq)
    traffic["warm_steps"] = 1
    ctx.conf, ctx.traffic = conf, traffic
    return ctx

"""Tiny cells for CPU tests: the real cell's files with every size cut
so a driver runs in seconds on the CPU."""
from __future__ import annotations

import copy
import json
import os
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CPU_PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12,
             "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(workload):
    """(workload entry, tiny configuration, tiny traffic) of a cell."""
    from bench import run as run_lib
    w, config, traffic = run_lib.cell(spec(), workload)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    if traffic["driver"] == "fl_round":
        config["model"].update(image_size=32, patch=8, vision_layers=2,
                               text_layers=2, d_model=64, n_heads=4,
                               d_ff=128, vocab=512, max_text_len=8,
                               proj_dim=32)
        traffic.update(n_clients=4, local_steps=2, batch=8, image_size=32,
                       class_counts=[4, 12, 12, 12, 12, 12, 12])
        config["pacs_images"] = sum(traffic["class_counts"])
    elif traffic["driver"] == "trunk_step":
        config["model"].update(n_layers=2, d_model=128, n_heads=4,
                               n_kv_heads=4, head_dim=32, d_ff=256,
                               vocab_size=512, lora_rank=4,
                               adapter_heads=4)
        traffic.update(batch=4, seq=16, vocab=512, docs_per_client=32)
    return w, config, traffic


def args(workload, seed=2**31 + 11, seconds=0.5, trace=0):
    return types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace)


def run_tiny(workload, capsys, **kw):
    """Run a tiny cell through the harness (past its look for a chip)
    and return the parsed result line."""
    from bench import run as run_lib
    w, config, traffic = cell(workload)
    rc = run_lib.run_cell(args(workload, **kw), w, config, traffic, spec(),
                          CPU_PEAKS)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

"""A cell at a size a CPU test run can hold: the same harness, engine and
comparison as the chip cells, on a two-layer model of each family."""
from __future__ import annotations

import copy

from bench import harness as H

TINY_MODEL = {
    "internlm2": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                  "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab_size": 500},
    "mamba2": {"num_layers": 2, "d_model": 64, "vocab_size": 500,
               "ssm_state": 16, "ssm_headdim": 16, "ssm_chunk": 16},
}
#: the SPC limit a standard deviation below the mean: the controller fires
#: in a tiny window, so its trips are there to count and to compare
FIRING = {"k_sigma": -1.0}
TINY_TRAFFIC = {"n_batches": 4, "batch_per_chip": 2, "seq_len": 64,
                "chunk_steps": 2, "warmup_epochs": 1,
                "markov": {"n_ctx": 64, "branch": 4, "p_follow": 0.8}}


def tiny_cell(name: str, *, chips: int = 1, limits=None) -> H.Cell:
    """The cell ``name`` of BENCHMARK.json at a tiny size."""
    cell = H.find_cell(name)
    cell = copy.deepcopy(cell)
    cell.chips = chips
    cell.config["model"].update(TINY_MODEL[cell.config["reference"]])
    hard = cell.traffic["hard_batches"]
    cell.traffic.update(TINY_TRAFFIC, hard_batches=hard,
                        batch_per_chip=2 if chips == 1 else 1)
    if limits is not None:
        cell.limits = {"limits": limits}
    return cell

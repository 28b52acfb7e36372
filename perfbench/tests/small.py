"""Small versions of the benchmark's cells, for CPU tests."""

import copy
import types

from perfbench import run

SMALL = {
    "thumb256.codec": {"config": {"images": [
        {"generator": "lena_like", "height": 64, "width": 64},
        {"generator": "cablecar_like", "height": 64, "width": 64}]},
        "traffic": {"pixels_per_step": 4 * 64 * 64, "warm_steps": 1}},
    "paper512.codec": {"config": {"images": [
        {"generator": "lena_like", "height": 64, "width": 64},
        {"generator": "cablecar_like", "height": 64, "width": 48}]},
        "traffic": {"pixels_per_step": 4 * 64 * 64, "warm_steps": 1}},
    "paper512.roundtrip": {"config": {"images": [
        {"generator": "lena_like", "height": 64, "width": 64},
        {"generator": "cablecar_like", "height": 64, "width": 48}]},
        "traffic": {"batch": 4, "batches_per_combo": 1, "warm_rounds": 1,
                    "sample": 64}},
}


def small_cell(name: str) -> run.Cell:
    cell = run.Cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config.update(SMALL[name]["config"])
    cell.traffic = dict(cell.traffic, **SMALL[name]["traffic"])
    return cell


def execute(cell: run.Cell, seed: int = 2**31 + 11, seconds: float = 1.0,
            trace: int = 0) -> dict:
    import jax
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
    return run.execute(cell, args, jax.devices())

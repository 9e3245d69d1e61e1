"""Tiny presets for the CPU tests: the real cell files with sizes cut in
the test, never through an option of the benchmark."""

import copy
import time

from bench_matrix import spec
from bench_matrix.context import CompileCounter, Context

TINY_MODEL = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, vocab_size=256, num_hidden_layers=2,
)
FAKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(name: str, arrival: dict = None) -> dict:
    """`arrival` replaces the mix's arrival process (an open loop, say)."""
    cell = copy.deepcopy(spec.load_cell(name))
    cell["config"].update(TINY_MODEL)
    t = cell["traffic"]
    if t["kind"] == "train_batches":
        t.update(seq=64, n_batches=2, steps_per_subwindow=2,
                 warmup_max_subwindows=2, trace_steps=2)
        cell["correctness"]["last_positions"] = 32
    else:
        t["engine"].update(block_size=8, pool_blocks=96, prefill_chunk_tokens=32,
                           max_seq_len=128, min_bucket=16, slots=4)
        t["arrival"].update(clients=4, ramp_seconds=0.2)
        if arrival:
            t["arrival"] = arrival
        t["prompt_tokens"].update(median=24, min=8, max=60)
        t["output_tokens"].update(median=6, min=3, max=12)
        t.update(strata=8, warmup_seconds=0.5, trace_seconds=0.5)
        cell["correctness"].update(prompt_tokens=64, decode_positions=4,
                                   last_positions=16)
    return cell


def kept_steps(steps, shared=0) -> dict:
    """The runner's samples of a traced slice's decode steps: each step's
    rows' keys, `shared` of a step's keys in blocks another row holds too."""
    return {"decode_steps": [{"keys": list(k), "distinct": sum(k) - shared} for k in steps]}


def context(seconds: float, devices, seed: int = 3, trace_dir: str = "") -> Context:
    return Context(
        seed=seed, seconds=seconds, devices=list(devices),
        t_start=time.perf_counter(), trace_dir=trace_dir,
        compiles=CompileCounter(),
    )

"""A tiny cell of each traffic shape, for the harness's CPU tests: the
smoke sizes of the Qwen3 family (2 layers, d_model 256) through the same
harness, engine and reference as the chip cells."""
from __future__ import annotations

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_MODEL = {"num_layers": 2, "d_model": 256, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 64, "d_ff": 512,
              "vocab_size": 512, "qk_norm": True, "tie_embeddings": True,
              "rope_theta": 1000000.0, "norm_eps": 1e-06,
              "kv_block_size": 16, "dtype": "bfloat16"}


def files(traffic: str = "step-pressure", **engine) -> dict:
    """The ``files`` argument of ``harness.run`` for a tiny cell with the
    named traffic's shape (lengths, pool and batch cut to CPU size);
    ``engine`` overrides the mix's engine settings."""
    mix = copy.deepcopy(
        json.loads((BENCH / "traffic" / f"{traffic}.json").read_text()))
    config = {"name": "tiny", "family": "qwen3",
              "program_config": "repro.configs.qwen3_1_7b",
              "program_overrides": dict(TINY_MODEL),
              "model": dict(TINY_MODEL), "weights_seed": 7,
              "step_row_scale": 2.5,
              "num_blocks": 48}
    mix.update(requests=6, n_traces=4, max_new_tokens=400,
               prompt_len={"min": 20, "max": 60, "median": 30, "sigma": 0.5})
    mix["engine"].update(max_batch=8, capacity=512, prefill_chunk_size=32)
    mix["engine"].update(engine)
    limits = {"limits": {"logprob_err": 0.05}}
    if mix["policy"] == "step":
        limits["limits"]["score_err"] = 0.01
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = {"name": "tiny", "config": "tiny", "traffic": traffic, "chips": 1}
    return {"cell": cell, "config": config, "mix": mix, "limits": limits,
            "end_to_end": bench["end_to_end"], "per_layer": bench["per_layer"]}

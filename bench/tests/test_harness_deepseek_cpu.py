"""The harness end to end on a tiny DeepSeek-V2 cell on the CPU, past its
look for a chip: the ``deepseek_v2`` family's weights, plain reference and
counts, the engine's latent pool (chunked prefill, the prefix cache, the
latent decode), a leading dense layer and a dropless MoE layer over a
share of the router's experts. A clean run is correct; a token altered
where it is sampled is caught."""
import copy
import json
import time
from pathlib import Path

from bench import harness

SEED = 3_000_000_019          # above 2**31, as the benchmark's seeds may be
BENCH = Path(__file__).resolve().parents[1]

TINY_MODEL = {
    "num_layers": 3, "d_model": 256, "num_heads": 4, "num_kv_heads": 4,
    "d_ff": 384, "vocab_size": 512, "use_mla": True, "kv_lora_rank": 64,
    "q_lora_rank": 0, "qk_nope_head_dim": 64, "qk_rope_head_dim": 32,
    "v_head_dim": 64, "rope_theta": 10000.0, "rope_scaling_factor": 40.0,
    "rope_original_max_positions": 128, "yarn_beta_fast": 32.0,
    "yarn_beta_slow": 1.0, "yarn_mscale": 0.707,
    "yarn_mscale_all_dim": 0.707, "num_experts": 8,
    "num_experts_per_tok": 2, "num_shared_experts": 1, "moe_d_ff": 128,
    "first_k_dense": 1, "norm_topk_prob": False,
    "routed_scaling_factor": 1.0, "experts_held": 2,
    "tie_embeddings": False, "norm_eps": 1e-06, "kv_block_size": 16,
    "dtype": "bfloat16"}


def files() -> dict:
    mix = copy.deepcopy(json.loads(
        (BENCH / "traffic" / "step-pressure.json").read_text()))
    config = {"name": "tiny-mla", "family": "deepseek_v2",
              "program_config": "repro.configs.deepseek_v2_lite",
              "program_overrides": dict(TINY_MODEL),
              "model": dict(TINY_MODEL), "weights_seed": 11,
              "step_row_scale": 2.5, "num_blocks": 48}
    mix.update(requests=6, n_traces=4, max_new_tokens=400,
               prompt_len={"min": 20, "max": 60, "median": 30, "sigma": 0.5})
    mix["engine"].update(max_batch=8, capacity=512, prefill_chunk_size=32)
    # by the cell rule, lower * (upper / lower) ** 0.6: five seeds read
    # at most 0.254 and 0.0235, the fp8 control at least 0.905 and 0.0825
    # (an untied head with logits spread by about one, and routing near
    # ties, read higher than the tied qwen3 tiny cell)
    limits = {"limits": {"logprob_err": 0.5, "score_err": 0.045}}
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = {"name": "tiny-mla", "config": "tiny-mla",
            "traffic": "step-pressure", "chips": 1}
    return {"cell": cell, "config": config, "mix": mix, "limits": limits,
            "end_to_end": bench["end_to_end"], "per_layer": bench["per_layer"]}


def run(control=False):
    return harness.run("tiny-mla", SEED, 2.0, False,
                       t_process=time.perf_counter(), require_chip=False,
                       files=files(), control=control)


def test_clean_run_is_correct():
    out = run()
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"setup_s", "tokens_per_s"} <= set(out["metrics"])


def test_altered_token_is_caught(monkeypatch):
    import repro.serving.engine as engine
    real = engine.sample_logits

    def off_by_one(*a, **kw):
        tokens, conf = real(*a, **kw)
        return (tokens + 1) % 512, conf

    monkeypatch.setattr(engine, "sample_logits", off_by_one)
    out = run()
    assert not out["correct"]
    assert out["check"]["logprob_err"]["value"] > \
        out["check"]["logprob_err"]["limit"]

"""The DeepSeek-V3 sessions cell driven at a small size on the CPU through
``bench.run.run_cell``: it parks and resumes and is correct; the float8
control in the program's place is not; nor is a run whose tokens are
altered where the engine produces them."""

import time

import jax
import numpy as np

from bench.run import run_cell
from test_bench_cells import KEYS

CELL = "deepseek-v3-671b.sessions-full"
# float32 at this size: with 16 experts of which 4 are chosen, bfloat16
# rounding of the router's input flips near-tied choices on some seeds,
# and one flipped expert moves a 64-wide model's logits by up to 0.6 (on
# the chip the published widths run in bfloat16, PERF.md "Limits")
SMOKE_DEEPSEEK = {
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 2, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "vocab_size": 503, "torch_dtype": "float32",
    "published": {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                  "n_routed_experts": 16, "num_attention_heads": 16,
                  "vocab_size": 129280, "num_nextn_predict_layers": 1},
    "serving": {"max_batch": 4, "max_seq": 256}}
SMOKE_MIX = {"prompt_lens": [16, 32], "prompt_weights": [1, 1],
             "output_median": 6, "output_clip": [2, 8], "think_mean_s": 0.3,
             "check_tokens": 60}


def serve(seed=2 ** 33 + 7, trace=False, rate=2.0, hook=None, **mix):
    return run_cell(CELL, seed, 3.0, trace, jax.devices(),
                    t_start=time.perf_counter(), hook=hook,
                    cfg_override=SMOKE_DEEPSEEK,
                    mix_override=dict(SMOKE_MIX, rate_per_s=rate, **mix))


def test_sessions_park_resume_and_are_correct():
    line, run = serve()
    c = run.readings.extra["counts"]
    assert list(line) == KEYS
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == c["turns"] > c["sessions"] > 0
    assert c["parks"] > 0 and c["resumes"] > 0
    assert c["parks"] == c["resumes"] + c["parked_at_end"]
    assert set(line["metrics"]) == {"itl_mean_ms", "setup_s"}
    gap = line["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_traced_run_reports_the_serving_spans():
    line, _ = serve(trace=True)
    # on the CPU the trace has no TPU plane: device metrics are left out
    assert {"prefill_ms", "park_resume_ms", "decode_step_ms",
            "slot_write_ms"} <= set(line["metrics"])
    assert "moe_decode_roofline" not in line["metrics"]


def test_float8_control_is_not_correct():
    line, run = serve(seed=3, control=True, check_tokens=200,
                      output_median=12, output_clip=[4, 24])
    sound = run.readings.extra["sound_gap"]
    control = run.readings.extra["control_gap"]
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] == control
    assert control > line["checks"]["logit_gap"]["limit"] >= sound


def test_token_altered_where_produced_is_not_correct():
    def hook(eng):
        be = eng.backend
        calls = {"n": 0}

        def decode(params, state, tokens):
            logits, state = be._decode(params, state, np.asarray(tokens))
            arg = np.asarray(logits[:, -1].argmax(-1))
            calls["n"] += 1
            if calls["n"] % 5 == 0:          # the worst token, in every slot
                vocab = SMOKE_DEEPSEEK["vocab_size"]
                arg = np.asarray(logits[:, -1, :vocab].argmin(-1))
            return arg, state

        be.decode = decode

    line, _ = serve(hook=hook)
    assert line["correct"] is False
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]

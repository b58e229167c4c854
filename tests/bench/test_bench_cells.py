"""Each cell's loop driven at a small size on the CPU through
``bench.run.run_cell`` (not ``main()``, which refuses a CPU): the counts,
and the result line's keys."""

import time

import jax
import numpy as np
import pytest

from bench.run import run_cell

SMOKE_GRANITE = {"hidden_size": 128, "num_hidden_layers": 2,
                 "num_attention_heads": 4, "num_key_value_heads": 2,
                 "head_dim": 32, "intermediate_size": 256, "vocab_size": 503,
                 "serving": {"max_batch": 4, "max_seq": 256}}
SMOKE_SERVE = {"prompt_lens": [16, 32], "prompt_weights": [1, 1],
               "output_median": 6, "output_scale": 1.0, "output_clip": [2, 8],
               "think_mean_s": 0.3, "check_tokens": 60}
SMOKE_MONTAGE = {"image_pixels": [32, 64], "grid": [2, 3], "n_images": 6,
                 "overlap_pixels": [4, 8], "shrink_factor": 2}
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def serve(cell, seed=2 ** 33 + 7, trace=False, rate=3.0, hook=None, **mix):
    return run_cell(cell, seed, 3.0, trace, jax.devices(),
                    t_start=time.perf_counter(), hook=hook,
                    cfg_override=SMOKE_GRANITE,
                    mix_override=dict(SMOKE_SERVE, rate_per_s=rate, **mix))


def montage(seed=5, trace=False, hook=None, **mix):
    return run_cell("montage-2mass.exec", seed, 2.0, trace, jax.devices(),
                    t_start=time.perf_counter(), hook=hook,
                    cfg_override=SMOKE_MONTAGE, mix_override=mix)


def test_sessions_park_every_idle_session_and_resume_it():
    line, run = serve("granite-3-2b.sessions", rate=2.0)
    c = run.readings.extra["counts"]
    assert list(line) == KEYS
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == c["turns"] > c["sessions"] > 0
    assert c["parks"] > 0 and c["resumes"] > 0
    assert c["parks"] == c["resumes"] + c["parked_at_end"]
    assert c["prefills"] == c["sessions"]
    assert set(line["metrics"]) == {"itl_mean_ms", "setup_s"}
    assert line["checks"]["logit_gap"]["value"] <= \
        line["checks"]["logit_gap"]["limit"]


def test_short_never_parks():
    line, run = serve("granite-3-2b.short", rate=4.0)
    c = run.readings.extra["counts"]
    assert list(line) == KEYS
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == c["turns"] == c["sessions"] == c["prefills"]
    assert c["parks"] == c["resumes"] == 0
    assert set(line["metrics"]) == {"itl_mean_ms", "setup_s"}


def test_traced_serving_run_reports_the_host_spans():
    line, _ = serve("granite-3-2b.sessions", trace=True, rate=2.0)
    # on the CPU the trace has no TPU plane: device metrics are left out
    assert {"prefill_ms", "park_resume_ms",
            "decode_step_ms"} <= set(line["metrics"])
    assert "device_idle.serve" not in line["metrics"]
    assert list(line)[-1] == "checks"


def test_montage_workflows_back_to_back():
    line, run = montage()
    c = run.readings.extra["counts"]
    assert list(line) == KEYS
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == c["workflows"] == c["workflows_completed"] >= 2
    assert c["tasks"] == 26 * c["workflows"]
    assert set(line["metrics"]) == {"makespan_s", "setup_s"}
    assert line["checks"]["mosaic_max_abs_diff"]["value"] == 0.0


def test_traced_montage_reports_scheduler_executor_and_prefetch():
    line, _ = montage(trace=True)
    assert {"sched_ms_per_task", "io_wait_share",
            "prefetched_share"} <= set(line["metrics"])
    assert 0.0 <= line["metrics"]["prefetched_share"]["value"] <= 100.0


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11, 2 ** 40 + 3])
def test_a_seed_permutes_the_same_work(seed):
    from bench import traffic

    mix = traffic.load_mix("sessions")
    a = traffic.serving_schedule(mix, seed, 30.0, 1000)
    b = traffic.serving_schedule(mix, seed + 1, 30.0, 1000)
    assert a == traffic.serving_schedule(mix, seed, 30.0, 1000)
    assert a != b
    for key in ("out_lens", "think_s"):
        assert sorted(x for p in a for x in getattr(p, key)) == \
            sorted(x for p in b for x in getattr(p, key))
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in b)
    assert [p.arrival_s for p in a] != [p.arrival_s for p in b]
    assert np.allclose(sorted(np.diff([0] + [p.arrival_s for p in a])),
                       sorted(np.diff([0] + [p.arrival_s for p in b])))
    assert max(p.arrival_s for p in a) == \
        pytest.approx(max(p.arrival_s for p in b))
    assert max(p.arrival_s for p in a) < 30.0

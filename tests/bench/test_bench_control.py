"""The correctness check can fail: the control (the reference in the next
precision down in the program's place) separates from sound runs, and a
run with its timed path broken underneath comes out not correct."""

import numpy as np

from test_bench_cells import SMOKE_GRANITE, montage, serve


def test_serving_control_reads_far_above_sound_runs():
    sound, control = [], []
    for seed in (1, 2, 3):
        # some 300 checked tokens, as a run on the chip checks some 400
        line, run = serve("granite-3-2b.short", seed=seed, rate=4.0,
                          control=True, check_tokens=300, output_median=12,
                          output_clip=[4, 24])
        sound.append(run.readings.extra["sound_gap"])
        control.append(run.readings.extra["control_gap"])
        # the control in the program's place goes through the same judgement
        assert line["correct"] is False
        assert line["checks"]["logit_gap"]["value"] == control[-1]
    assert min(control) >= 3 * max(max(sound), 0.01), (sound, control)
    assert max(sound) <= line["checks"]["logit_gap"]["limit"]


def test_serving_token_altered_where_produced_is_not_correct():
    def hook(eng):
        be = eng.backend
        calls = {"n": 0}

        def decode(params, state, tokens):
            logits, state = be._decode(params, state, np.asarray(tokens))
            arg = np.asarray(logits[:, -1].argmax(-1))
            calls["n"] += 1
            if calls["n"] % 5 == 0:          # the worst token, in every slot
                vocab = SMOKE_GRANITE["vocab_size"]
                arg = np.asarray(logits[:, -1, :vocab].argmin(-1))
            return arg, state

        be.decode = decode

    line, _ = serve("granite-3-2b.short", rate=4.0, hook=hook)
    assert line["correct"] is False
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_montage_control_in_bfloat16_is_not_correct():
    line, run = montage(control=True)
    assert run.readings.extra["sound_diff"] == 0.0
    assert run.readings.extra["control_diff"] > 0.0
    assert line["correct"] is False
    assert line["checks"]["mosaic_max_abs_diff"]["value"] == \
        run.readings.extra["control_diff"]


def test_montage_answer_altered_where_produced_is_not_correct():
    def hook(tg):
        body = tg.tasks["mBackground0"].fn

        def altered(**kw):
            out = body(**kw)
            return {k: v.at[0, 0].add(1e-3) for k, v in out.items()}

        tg.tasks["mBackground0"].fn = altered

    line, _ = montage(hook=hook)
    assert line["correct"] is False
    assert line["checks"]["mosaic_max_abs_diff"]["value"] > 0.0

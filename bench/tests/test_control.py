"""The control: the reference computed with float8 (e4m3) operands, the
step below the configuration's bfloat16, put in the program's place.  On
the chip it is read by ``bench/control.py`` at the cell's own size
(PERF.md gives the readings); here, at a CPU size with the cell's depth
and routing (``smoke.qwen_control``), a whole run with the control's
readings in place of the program's has to come out as not correct
through the run's own checks."""
import numpy as np

import smoke
import harness

REF = harness.module("reference", "qwen1.5-moe-a2.7b")


def test_fp8_control_moves_the_rows_and_logits_far_beyond_float32():
    spec = smoke.qwen_control()
    rng = np.random.default_rng(0)
    seqs = [{"prompt": rng.integers(1, 512, 40).tolist(),
             "out": rng.integers(1, 512, 30).tolist()}]
    f32 = REF.forward_logits(spec, 3, seqs)[0]
    f8 = REF.forward_logits(spec, 3, seqs, lp=True)[0]
    again = REF.forward_logits(spec, 3, seqs)[0]
    assert np.abs(f32 - again).max() == 0
    assert np.abs(f8 - f32).max() > 1e-2 * np.abs(f32).max()
    got = REF.readings(spec, 3, seqs, control=True)
    assert got["tokens"] == 30 and got["kv_rows"] == 69
    assert got["kv_prompt_err"] > REF.KV_PROMPT_LIMIT
    assert got["kv_decode_err"] > REF.KV_DECODE_LIMIT


def test_a_run_with_the_control_in_the_programs_place_is_not_correct(
        monkeypatch):
    good = REF.readings
    monkeypatch.setattr(REF, "readings", lambda spec, seed, seqs: good(
        spec, seed, seqs, control=True))
    res, err = smoke.run_smoke("qmoe.chat-over", smoke.qwen_control(),
                               smoke.chat_smoke(), seconds=2.0)
    assert not res["correct"]
    assert not res["checks"]["kv_prompt_err"]["ok"]
    assert not res["checks"]["kv_decode_err"]["ok"]
    assert "check kv_decode_err" in err and "FAILED" in err

"""The end-to-end arithmetic: a rate over all the work and all the time of
the window, the check that compares a number with its limit, and the
result line."""
from __future__ import annotations

import bench_chip_util as u
import suite
from drivers.engine import window_tokens


def step(prompts=(), generated=0):
    return {"prompt_tokens": sum(prompts), "prompts": list(prompts),
            "admitted": len(prompts), "generated": generated}


def test_window_tokens_count_prompts_and_generated_tokens():
    steps = [step((512, 2048), 2), step(generated=4), step((768,), 5)]
    assert window_tokens(steps) == 512 + 2048 + 2 + 4 + 768 + 5


def test_rate_is_over_the_whole_window(tmp_path):
    root = u.checkout(tmp_path)
    res = u.run(root, "tiny.engine", seconds=1.0, trace=False)
    assert res["correct"], res["checks"]
    # the rate is every token the window's steps made over the window's
    # length, not a median of steps
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0


def test_check_passes_at_its_limit_and_fails_above():
    assert suite.check("gap", 0.5, 0.5)["ok"]
    assert not suite.check("gap", 0.51, 0.5)["ok"]


def test_result_line_puts_the_checks_last():
    import json
    line = suite.result_line(
        correct=False, attempted=4, failed=0,
        metrics={"tokens_per_s": {"value": 1.5, "unit": "tokens/s"}},
        device={"platform": "tpu"},
        checks=[suite.check("logit_gap_max", 2.0, 0.5)])
    out = json.loads(line)
    assert list(out)[-1] == "checks"
    assert out["checks"][0] == {"name": "logit_gap_max", "value": 2.0,
                                "limit": 0.5, "ok": False}

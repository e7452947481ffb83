"""The harness driven end to end on the CPU at a tiny size: the shape of
the result line, the chip gate, and the correctness check failing on the
float8 control and on faults planted in the timed path.

Tiny limits (``tiny.LIMITS``, ``MEAN``). The widest gap, 0.05: readings on
the CPU over seeds 200-207 with the ``tiny-chat`` mix, 95-144 served tokens
each, the program's widest gap 0 to 0.0121, the float8 control's 0.165 to
0.306. The mean gap, 0.001: over seeds 300-307 with the ``tiny-docs`` mix,
112-131 served tokens each, the program's mean gap 0 to 0.000152, the
control's 0.0035 to 0.0132.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import tiny

ROOT = tiny.harness.ROOT
MEAN = {"mean_logit_gap": 0.001}


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


@pytest.mark.parametrize("cell,mix", [(tiny.CHAT, "tiny-chat"),
                                      (tiny.DOCS, "tiny-docs")])
def test_result_line_on_cpu(cell, mix):
    [line] = tiny.run(cell, tiny.config(), tiny.mix(mix), 2 ** 31 + 11)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["attempted"] >= 0 and line["failed"] == 0
    want = {"decode_tok_s", "uplink_B_per_tok", "setup_s"}
    if cell == tiny.CHAT:
        want |= {"tpot_p50_ms"}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    # tiny d_model 128: mode 0 sends 256 B a token, mode 1 34 B
    assert 34 <= line["metrics"]["uplink_B_per_tok"]["value"] <= 256
    assert line["compiles_in_window"] == 0
    gap = line["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"] == 0.05
    assert line["checks"]["served_tokens_compared"]["value"] >= 1
    json.dumps(line)


def test_float8_control_is_not_correct():
    prog, ctrl = tiny.run(tiny.CHAT, tiny.config("tiny-control"),
                          tiny.mix("tiny-chat"), 204, seconds=6.0,
                          control=True)
    # the readings above compared 95-144 tokens; fewer would flip fewer
    assert prog["checks"]["served_tokens_compared"]["value"] >= 80
    assert prog["correct"] is True
    assert prog["checks"]["max_logit_gap"]["value"] <= 0.05
    assert ctrl["correct"] is False
    assert ctrl["checks"]["max_logit_gap"]["value"] > 0.05
    assert list(ctrl)[-1] == "checks"


def test_float8_control_is_not_correct_on_the_mean_gap():
    prog, ctrl = tiny.run(tiny.DOCS, tiny.config("tiny-control-docs"),
                          tiny.mix("tiny-docs"), 303, seconds=4.0,
                          control=True, limits=MEAN)
    assert prog["checks"]["served_tokens_compared"]["value"] >= 80
    assert prog["correct"] is True
    assert set(ctrl["checks"]) == {"mean_logit_gap", "served_tokens_compared"}
    assert ctrl["correct"] is False
    assert ctrl["checks"]["mean_logit_gap"]["value"] > 0.001


def test_altered_token_is_not_correct(monkeypatch):
    from repro.kernels import ops

    real = ops.decode_tail_op

    def altered(*a, **k):
        return (real(*a, **k) + 1) % 512
    monkeypatch.setattr(ops, "decode_tail_op", altered)
    [line] = tiny.run(tiny.CHAT, tiny.config("tiny-fault-token"),
                      tiny.mix("tiny-chat"), 5, seconds=5.0, warm_up=False)
    assert line["correct"] is False
    assert line["checks"]["max_logit_gap"]["value"] > 0.05


def test_unchanged_decode_state_is_not_correct(monkeypatch):
    from repro.models import transformer

    real = transformer.paged_decode_attention

    def stale(p, h, arena, *a, **k):
        out, _ = real(p, h, arena, *a, **k)
        return out, arena               # the step's K/V rows are dropped
    monkeypatch.setattr(transformer, "paged_decode_attention", stale)
    [line] = tiny.run(tiny.DOCS, tiny.config("tiny-fault-state"),
                      tiny.mix("tiny-docs"), 6, seconds=5.0, warm_up=False)
    assert line["correct"] is False
    assert line["checks"]["max_logit_gap"]["value"] > 0.05


def test_decode_plan_reaches_every_window():
    from bench import harness, loadgen
    m = tiny.mix("tiny-chat")
    plan = harness.decode_plan(m, 6)
    # K = 16 at width 4 needs a row past 32 with 16 tokens to come: a
    # 16-token prompt that has decoded one token first
    assert (4, 16, 16, [1], 18) in plan
    m = loadgen.load_mix("chat-mmwave")
    plan = harness.decode_plan(m, m["engine"]["n_pages"])
    got = {(w, k) for w, k, *_ in plan}
    assert got == {(w, k) for w in (8, 16, 32, 64, 128, 256)
                   for k in (1, 2, 4, 8, 16)}
    for w, k, p_a, parts, b_a in plan:
        rows = p_a + sum(parts) + k     # A's rows in the joined window
        assert harness._table_width(-(-rows // 16), 1536) == w
        assert m["prompt_len"]["min"] <= p_a <= m["prompt_len"]["max"]
        assert b_a <= m["output_len"]["max"]


def test_run_refuses_without_a_tpu():
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2.5-3b.chat-mmwave", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=_env(), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2.5-3b.chat-mmwave", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""

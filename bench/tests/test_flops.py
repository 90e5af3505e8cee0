"""The FLOPs-per-token function against the hand count."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import json  # noqa: E402

import pytest  # noqa: E402
import tiny  # noqa: E402

from harness import runner  # noqa: E402


def _conf(name):
    with open(os.path.join(tiny.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


REF = runner.load_module(os.path.join(tiny.BENCH, "configs", "gpt2_reference.py"),
                         "reference_gpt2_reference")


@pytest.mark.parametrize("name,want", [
    # 6 x 123.5M matmul parameters + 12 * 12 * 1024 * 768
    ("gpt2_small", 6 * 123_532_032 + 113_246_208),
    # 6 x 353.5M matmul parameters + 12 * 24 * 1024 * 1024
    ("gpt2_medium", 6 * 353_453_056 + 301_989_888),
])
def test_flops_per_token_matches_hand_count(name, want):
    assert REF.flops_per_token(_conf(name), 1024) == want


def test_flops_per_token_is_about_the_issue_estimates():
    assert REF.flops_per_token(_conf("gpt2_small"), 1024) == pytest.approx(854e6, rel=1e-3)
    assert REF.flops_per_token(_conf("gpt2_medium"), 1024) == pytest.approx(2.42e9, rel=2e-3)


def test_lm_head_counts_the_published_vocabulary_not_the_padded_one():
    conf = _conf("gpt2_small")
    padded = dict(conf, vocab_size=REF.padded_vocab(conf))
    extra = REF.flops_per_token(padded, 1024) - REF.flops_per_token(conf, 1024)
    assert extra == 6 * conf["n_embd"] * (REF.padded_vocab(conf) - conf["vocab_size"])

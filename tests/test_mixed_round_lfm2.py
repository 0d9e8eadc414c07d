"""Admitted prompts riding a decode round where the recurrent layers are gated
short convolutions (`tiny-lfm2`: a tail and no matrix state, rope on the
attention layers, two leading dense layers with pool rows of their own):
tests/test_mixed_round_hybrid.py's bodies for the kind "conv", in a file and so
a process of its own (that file says why)."""

import pytest

import test_mixed_round_hybrid as rounds
from test_mixed_round import _engines_end  # noqa: F401 (an autouse fixture: the engines a test built end with it)


@pytest.mark.parametrize("case", list(rounds.HYBRID_CASES))
def test_a_mixed_round_over_tails_is_admit_fn_and_a_plain_round(monkeypatch, case):
    rounds.test_a_hybrid_mixed_round_is_admit_fn_and_a_plain_round(monkeypatch, "conv", case)


def test_two_prompts_packed_in_one_rung_leave_their_own_tails(monkeypatch):
    rounds.test_two_prompts_packed_in_one_rung_do_not_see_each_other(monkeypatch, "conv")


def test_a_queued_request_rides_a_round_over_tails(monkeypatch):
    rounds.test_a_queued_request_rides_a_round_with_recurrent_layers(monkeypatch, "conv")


def test_the_plans_mixed_round_over_tails_is_the_one_the_live_call_lowers(monkeypatch):
    rounds.test_the_plans_hybrid_mixed_round_is_the_one_the_live_call_lowers(monkeypatch, "conv")


def test_every_mixed_shape_over_tails_is_in_the_zoo(monkeypatch):
    rounds.every_mixed_shape_is_in_the_zoo(monkeypatch, "tiny-lfm2")

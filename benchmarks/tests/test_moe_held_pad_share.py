"""`moe_held_pad_share` reads the step's own `moe_routed_here` against its
`moe_rows_walked` and nothing else; where a program reports no rows walked
(the parent of PR 52, a model that holds all its experts) it reads nothing
and does not raise. CPU only, not part of tier-1:

    python -m pytest benchmarks/tests -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import cells  # noqa: E402

METRIC = "moe_held_pad_share"


@pytest.fixture(scope="module")
def read():
    cell = cells.resolve("qwen3next-steady", root=ROOT)
    assert METRIC in [m["name"] for m in cell.per_layer]
    return cells.layer_reader(cell, METRIC)


def test_the_entry_names_the_cell_that_walks_and_the_rate_it_moves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "Model step",
        "moves": "tokens_per_s_per_chip", "workloads": ["qwen3next-steady"]}
    assert bench["per_layer"][-1] == entry     # appended, nothing moved


def test_it_is_the_median_step_s_mean_over_the_layers_that_walked(read):
    """Warm-up records left out; a layer that took no trip has no rows to
    pad and is left out of its step's mean; a step in which no layer walked
    is left out of the median."""
    window = {"tokens_per_step": 100, "first_window_record": 1,
              "step_records": [
                  {"moe_routed_here": [1, 1], "moe_rows_walked": [100, 100]},
                  # (1 - 0.5 + 1 - 1.0) / 2 = 25%
                  {"moe_routed_here": [50, 100],
                   "moe_rows_walked": [100, 100]},
                  # 40%
                  {"moe_routed_here": [60, 60], "moe_rows_walked": [100, 100]},
                  # the first layer took no trip: 1 - 40 / 400 = 90%
                  {"moe_routed_here": [0, 40], "moe_rows_walked": [0, 400]},
                  {"moe_routed_here": [0, 0], "moe_rows_walked": [0, 0]}]}
    assert read({"window": window}) == pytest.approx(40.0)
    window["step_records"] = window["step_records"][:3]
    assert read({"window": window}) == pytest.approx(32.5)


@pytest.mark.parametrize("window", [
    {}, {"step_records": []}, {"step_records": [{"loss": 1.0}]},
    # the parent's steps: the router's count and no rows walked
    {"first_window_record": 0, "step_records": [
        {"moe_routed_here": [50, 70]}, {"moe_routed_here": [60, 100]}]},
    # a model that holds all its experts
    {"step_records": [{"moe_expert_tokens": [3, 4, 5]}]},
    {"step_records": [{"moe_routed_here": 7, "moe_rows_walked": 9}]},
    {"step_records": None, "first_window_record": None}, None],
    ids=["no_records", "empty", "dense", "the_parent", "all_held",
         "not_lists", "nones", "no_window"])
def test_a_program_without_the_counter_reads_as_nothing(read, window):
    assert read({"window": window}) is None
    assert read({}) is None

"""``prefill_dispatch_passes_per_call.lm``: the reader over the engine's two
counters, and its place in ``BENCHMARK.json``."""
import json
import os

import pytest

from benchmark.harness import spec

NAME = "prefill_dispatch_passes_per_call.lm"


@pytest.mark.parametrize("lm,want", [
    ({"dispatch_passes": 76, "dispatch_calls": 76}, 1.0),
    ({"dispatch_passes": 85, "dispatch_calls": 84}, 85 / 84),
    ({"dispatch_passes": 0, "dispatch_calls": 0}, None),   # another form
    ({"launches": 3}, None),             # a program from before the counters
    (None, None),                        # a cell without a language model
], ids=["one-pass", "a-second-pass", "no-call", "older-program", "no-lm"])
def test_passes_over_calls_or_nothing(lm, want):
    read = spec.load_metric_reader(NAME)
    assert read({"counters": {} if lm is None else {"lm": lm}}) == want


def test_it_is_declared_for_the_four_cells_with_experts():
    declared = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    mine = [m for m in declared["per_layer"] if m["name"] == NAME]
    assert mine == [declared["per_layer"][-1]]
    assert mine[0]["source"] == "program_counter"
    assert mine[0]["layer"] == "expert layer" and mine[0]["moves"] == "req_per_s"
    local = next(m for m in declared["per_layer"]
                 if m["name"] == "expert_load_max_over_mean.lm")
    assert mine[0]["workloads"] == local["workloads"]

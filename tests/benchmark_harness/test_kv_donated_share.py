"""The ``kv_donated_share`` reader (``benchmarks/layer_metrics/``) on
counter maps, and its two entries in the real manifest. On the CPU, in
this process."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402


def _read(name, ctx):
    return harness.load_module(harness.reader_path(name)).read(
        dict(ctx, metric=name))


@pytest.mark.parametrize("counters, want", [
    ({"serving.kv.donated_calls": 40, "serving.kv.copied_calls": 0}, 100.0),
    ({"serving.kv.donated_calls": 30, "serving.kv.copied_calls": 10}, 75.0),
    ({"serving.kv.donated_calls": 0, "serving.kv.copied_calls": 5}, 0.0),
    ({"serving.kv.donated_calls": 0, "serving.kv.copied_calls": 0}, None),
    ({"serving.steps": 9}, None)],
    ids=["all-donated", "some-copied", "donation-refused",
         "no-call-in-the-window", "the-parent-has-no-such-counters"])
def test_kv_donated_share_is_donated_over_all_pool_writing_calls(
        counters, want):
    got = _read("kv_donated_share.x", {"counters": counters})
    assert got == (want if want is None else pytest.approx(want))


def test_the_real_manifest_lists_kv_donated_share_in_the_serving_cells():
    with open(harness.MANIFEST) as f:
        by = {m["name"]: m for m in json.load(f)["per_layer"]}
    for tag, cell, moves in (
            ("sat", "mistral7b-batch-saturated", "serve_tok_s"),
            ("steady", "mistral7b-chat-steady", "itl_p95_ms")):
        m = by["kv_donated_share." + tag]
        assert m["workloads"] == [cell] and m["moves"] == moves
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "%", "higher", "program_counter", "KV cache")

"""The ``decode_ahead_share`` reader (``benchmarks/layer_metrics/``) on
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
    ({"serving.decode.ahead": 99, "serving.decode.in_order": 1}, 99.0),
    ({"serving.decode.ahead": 30, "serving.decode.in_order": 10}, 75.0),
    ({"serving.decode.ahead": 0, "serving.decode.in_order": 7}, 0.0),
    ({"serving.decode.ahead": 12}, 100.0),
    ({"serving.decode.ahead": 0, "serving.decode.in_order": 0}, None),
    ({"serving.steps": 9}, None)],
    ids=["one-in-order", "some-in-order", "never-ahead",
         "no-in-order-counter-moved", "no-dispatch-in-the-window",
         "the-parent-has-no-such-counters"])
def test_decode_ahead_share_is_ahead_over_all_decode_dispatches(
        counters, want):
    got = _read("decode_ahead_share.x", {"counters": counters})
    assert got == (want if want is None else pytest.approx(want))


def test_the_real_manifest_lists_decode_ahead_share_in_the_mistral_cells():
    with open(harness.MANIFEST) as f:
        manifest = json.load(f)
    assert not harness.manifest_problems(manifest)
    by = {m["name"]: m for m in manifest["per_layer"]}
    for tag, cell, moves in (
            ("sat", "mistral7b-batch-saturated", "serve_tok_s"),
            ("steady", "mistral7b-chat-steady", "itl_p95_ms")):
        m = by["decode_ahead_share." + tag]
        assert m["workloads"] == [cell] and m["moves"] == moves
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "%", "higher", "program_counter", "scheduler")
    # the block path is in order by construction: no entry for its cell
    assert "decode_ahead_share.sdar" not in by

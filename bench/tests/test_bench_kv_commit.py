"""The ``kv_commit_share`` reader, on the recorded trace slice checked in
beside this file (data/trace_slice.pbtxt: one decode block and one interval
scrub of the qwen2-7b chat cell, no prompt commit) and on a made-up
reduction that holds the commit program."""

from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import registry, tracing  # noqa: E402

FIXTURE = os.path.join(BENCH, "tests", "data", "trace_slice.pbtxt")


@pytest.fixture(scope="module")
def reduced():
    import jax

    with open(FIXTURE) as f:
        text = f.read()
    return tracing.reduce(jax.profiler.ProfileData.from_serialized_xspace(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text)))


def test_kv_commit_share_reads_the_commit_program(reduced):
    """The checked-in slice holds no prompt commit: the reader finds nothing.
    A reduction that holds one reads its share of the window per chip."""
    read = registry.metric_reader("kv_commit_share", ROOT)
    assert "jit__commit_tokens" not in reduced.module_ns
    assert read({"reduced": reduced}) is None
    red = tracing.Reduced((0, 4_000), 2, 1.0, {}, {"jit__commit_tokens": 2_000.0}, [], [])
    assert read({"reduced": red}) == pytest.approx(25.0)
